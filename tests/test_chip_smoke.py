"""Rehearsal of ``chip_smoke.py`` on the CPU: every phase at about 2,000
requests, through the same phase functions the chip run calls, must agree
with its reference on every row; and the script itself must refuse to run
without a TPU."""

import json

import jax
import pytest

import chip_smoke

N = 2_000


@pytest.fixture(scope="module")
def clock():
    return chip_smoke.CompileClock()


@pytest.mark.parametrize("make", [
    chip_smoke.place_phase, chip_smoke.temporal_phase,
    chip_smoke.serve_phase, chip_smoke.sparse_phase,
], ids=lambda f: f.__name__)
def test_phase_matches_reference(make, clock):
    rec = chip_smoke.run_phase(make(N), clock)
    assert rec["rows_differ"] == 0, rec
    assert rec["carbon_rel_err"] <= chip_smoke.RTOL, rec
    assert rec["device"] == rec["reference"] == "cpu"
    assert rec["ok"], rec


def test_phases_route_real_load():
    """The capped phases bind: some rows spill or shed, so admission (not
    just scoring) is what the comparison covers."""
    phase = chip_smoke.place_phase(N)
    out = phase.run(phase.build(), phase.stream)
    batch, region, _ = phase.stream
    moved = (out.exec_region != region).sum()
    assert moved > 0 or out.shed.any()


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
