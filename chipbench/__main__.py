import sys

from chipbench.harness import main

if __name__ == "__main__":
    sys.exit(main())
