"""One runner per KIND of cell (``train``, ``serve``); a configuration's
file names its runner."""
