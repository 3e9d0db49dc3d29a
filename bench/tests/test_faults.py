"""A run over a broken timed path comes out not correct: each fault a cell
can have is planted in the program underneath an otherwise whole run (the
harness's look for a chip skipped), at a tiny size on the CPU."""

import time

import jax
import jax.numpy as jnp
import pytest

import run
from repro.serve import placement
from repro.serve.router import FleetRouter


def _run(cell):
    return run.run_cell(cell, 424242, 0.2, False, time.perf_counter())


def _state_unchanged(monkeypatch, name):
    """The step hands back its capacity state unchanged: admission rounds
    never advance the cell ledger, and serve drafts never see what earlier
    drafts of the hour committed."""
    real_ranks = placement.device_prefix_ranks
    monkeypatch.setattr(
        placement, "device_prefix_ranks",
        lambda rank, totals, cell, axis: (
            real_ranks(rank, totals, cell, axis)[0], jnp.zeros_like(totals)))
    real = FleetRouter._route_arrays
    if name.endswith("serve"):
        monkeypatch.setattr(
            FleetRouter, "_route_arrays",
            lambda self, *a, used0=None, **k: real(self, *a, **k))


def _half_batch(monkeypatch, name):
    """Half of the batch is routed; the rest repeats what was computed."""
    real = FleetRouter._route_arrays

    def half(self, batch, region, hour, **kw):
        n = len(batch)
        m = n // 2
        from repro.serve.forecast import slice_batch
        import numpy as np
        idx = np.arange(m)
        res, state = real(self, slice_batch(batch, idx, m), region[:m],
                          hour[:m], **{k: v for k, v in kw.items()
                                       if k != "slack_np"})
        grow = lambda x: (jnp.concatenate([x, x[:n - m]])
                          if getattr(x, "ndim", 0) and x.shape[0] == m
                          else x)
        return jax.tree.map(grow, res), jax.tree.map(grow, state)

    monkeypatch.setattr(FleetRouter, "_route_arrays", half)


def _answer_altered(monkeypatch, name):
    """One row's tier is changed where the router produces it."""
    real = FleetRouter._route_arrays

    def altered(self, *a, **kw):
        res, state = real(self, *a, **kw)
        t = res.target.at[0].set((res.target[0] + 1) % 3)
        import dataclasses
        return dataclasses.replace(res, target=t), state

    monkeypatch.setattr(FleetRouter, "_route_arrays", altered)


FAULTS = {
    "dense4.place": [_state_unchanged, _half_batch, _answer_altered],
    "dense4.serve": [_state_unchanged, _half_batch, _answer_altered],
}


@pytest.mark.parametrize("name,fault", [(c, f) for c, fs in FAULTS.items()
                                        for f in fs],
                         ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_the_run_not_correct(small_cell, monkeypatch, name,
                                         fault):
    cell = small_cell(name)
    fault(monkeypatch, name)
    res = _run(cell)
    assert not res["correct"], res["checks"]
