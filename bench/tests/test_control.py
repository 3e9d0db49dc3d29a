"""The control, the reference computed one precision step lower (float32
products from bfloat16 halves) put in the program's place, is judged not
correct under every cell's limits."""

import pytest

import run
from harness import cells, check, traffic

from calibrate import as_output


@pytest.mark.parametrize("name", ["dense4.place", "dense4.serve"])
def test_control_fails_the_check(small_cell, name):
    cell = small_cell(name, 20000)
    g = cell.grid()
    caps = cell.entry().caps(cell, g["ci_hourly"].shape[0])
    stream = traffic.generate(cell.traffic, g["ci_hourly"].shape[0],
                              traffic.stream_rng(3, 0))
    ref = cell.reference().problem(cell, g, caps, stream)
    ctrl = as_output(cell.reference().problem(cell, g, caps, stream,
                                              precision="high").solve())
    own = as_output(ref.solve())
    ok_ref, _ = check.judge(check.worst(run.compare([own], ref)),
                            cells.load(name).limits)
    ok_ctrl, table = check.judge(check.worst(run.compare([ctrl], ref)),
                                 cells.load(name).limits)
    assert ok_ref
    assert not ok_ctrl, table
