"""Cells added to the benchmark after PR 35.

``test_chipbench_scope_ms.py`` holds the step module of every cell the
scope metrics list (``MODULES``, as of PR 35) and a PR that adds a cell
may not edit it: the cells added since give theirs here, so that the
contract test holds the scope metrics' module patterns to the new
cells' step modules too. A ``benchmark`` PR folds this into the table."""

import pytest

STEP_MODULES = {
    # PR 37: the digest carries the window variant's row of the phases
    "serve_trinity_decode_mixedctx": "jit_lm_decode_paged_s367a",
}


@pytest.fixture(autouse=True)
def _step_modules_of_cells_added_since(request):
    table = getattr(request.module, "MODULES", None)
    if isinstance(table, dict):
        for cell, module in STEP_MODULES.items():
            table.setdefault(cell, module)
