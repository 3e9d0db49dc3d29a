"""The benchmark's stream generator draws the published request mix, on
the program's diurnal curve, reproducibly from any seed."""

import numpy as np
import pytest

from harness import cells, traffic
from repro.serve.streams import diurnal_hours


@pytest.mark.parametrize("peak", [20.0, 2.0])
def test_arrival_curve_is_the_programs(peak):
    ours = traffic.diurnal_hours(np.random.default_rng(7), 5000, peak)
    np.testing.assert_array_equal(
        ours, diurnal_hours(np.random.default_rng(7), 5000, peak))


@pytest.mark.parametrize("cell", ["dense4.place", "dense4.serve"])
def test_classes_keep_their_published_medians_and_objectives(cell):
    mix = cells.load(cell).traffic
    s = traffic.generate(mix, 4, traffic.stream_rng(11, 0), 200_000)
    ctx = mix["mix"]["context"]
    assert (s.prompt_tokens >= 1).all() and (s.max_new_tokens >= 1).all()
    assert (s.prompt_tokens + s.max_new_tokens <= ctx).all()
    assert s.available.all()
    for c in mix["mix"]["classes"]:
        budget = c["ttft_s"] + c["tpot_s"] * s.max_new_tokens
        mine = np.isclose(s.latency_budget_s, budget)
        # budgets of the two classes meet at a few output lengths only
        assert mine.mean() > c["share"] - 0.01
        both = [cc for cc in mix["mix"]["classes"] if cc is not c]
        only = mine & ~np.any([np.isclose(
            s.latency_budget_s, o["ttft_s"] + o["tpot_s"] * s.max_new_tokens)
            for o in both], axis=0)
        assert np.median(s.prompt_tokens[only]) == pytest.approx(
            c["prompt_median"], rel=0.03)
        assert np.median(s.max_new_tokens[only]) == pytest.approx(
            c["new_median"], rel=0.06)


def test_large_seeds_give_distinct_reproducible_streams():
    mix = cells.load("dense4.place").traffic
    big = 2**31 + 12345
    a = traffic.generate(mix, 4, traffic.stream_rng(big, 0), 1000)
    b = traffic.generate(mix, 4, traffic.stream_rng(big, 0), 1000)
    c = traffic.generate(mix, 4, traffic.stream_rng(big, 1), 1000)
    np.testing.assert_array_equal(a.t_hours, b.t_hours)
    np.testing.assert_array_equal(a.prompt_tokens, b.prompt_tokens)
    assert not np.array_equal(a.t_hours, c.t_hours)
    assert (a.t_hours >= 0).all() and (a.t_hours < 24).all()


def test_skewed_arrivals_ramp_region_shares():
    mix = cells.load("dense4.place").traffic
    s = traffic.generate(mix, 4, traffic.stream_rng(3, 0), 200_000)
    share = np.bincount(s.region, minlength=4) / len(s)
    w = np.linspace(3.0, 1.0, 4)
    np.testing.assert_allclose(share, w / w.sum(), atol=0.005)
