"""CPU rehearsals of every cell at a tiny size: a whole run (set-up,
window, check) through the program's entry point, traced and not, comes
out correct and prints the contract's keys."""

import time

import pytest

import run

CELLS = ["dense4.place", "dense4.serve"]


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_tiny_size(small_cell, name, traced):
    cell = small_cell(name)
    res = run.run_cell(cell, 2**33 + 7, 0.5, traced, time.perf_counter())
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    names = set(res["metrics"])
    if traced:
        assert names <= {m["name"] for m in cell.per_layer}
        compiles = [v["value"] for k, v in res["metrics"].items()
                     if k.startswith("window_compiles")]
        assert compiles == [0]
    else:
        assert names == {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
