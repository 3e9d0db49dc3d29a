"""The program's own spans and device scopes in the trace reduction
(``harness.program_trace``): each reader built on them gives its known
value on synthetic events (device time under a scope is a union, so a
``while`` op that encloses its body counts once); on the recorded trace of
a program without them the nine readers that time layers from outside
give the values they always gave and the new ones give None; on a
recorded chip trace of one serve day with them, the program's spans and
scopes cover the layers the benchmark times from outside."""

import gzip
import types
from pathlib import Path

import numpy as np
import pytest

from harness import cells, program_trace, trace

NEW = ("prepare_host_ms.route", "factors_device_ms.route",
       "admit_device_ms.route", "draft_host_ms.serve", "fetch_host_ms.serve",
       "commit_host_ms.serve", "factors_device_ms.serve",
       "admit_device_ms.serve")
DATA = Path(__file__).parent / "data"


def _observed(reduced, drafts=()):
    return types.SimpleNamespace(trace=reduced, drafts_per_step=list(drafts),
                                 step_s=[], programs_in_window=0, calls=1,
                                 cell=None)


def _read(name, o):
    return cells.metric_reader(name)(o)


def _ops(*rows):
    """(name, start, end, scope path) rows -> ops and scopes of TPU:0."""
    names, s, e, paths = zip(*rows)
    return ({"TPU:0": (list(names), np.array(s, float), np.array(e, float))},
            {"TPU:0": list(paths)})


F, S, A, C = ("jit(_fleet_route)/factors/vmap()/mul",
              "jit(_fleet_route)/score/add", "jit(_fleet_route)/admit/while",
              "jit(_fleet_route)/account/reduce_sum")
BODY = "jit(_fleet_route)/admit/while/body/add"


def test_route_readers_on_synthetic_events():
    ops, scopes = _ops(
        ("fusion.1", 10, 20, F), ("fusion.2", 20, 30, S),
        # the while op's event encloses its body's op: admission is 30 ns
        ("while.3", 30, 60, A), ("fusion.4", 35, 45, BODY),
        ("fusion.5", 60, 70, C), ("copy.6", 70, 72, ""),
        ("fusion.1", 110, 125, F), ("while.3", 125, 150, A),
        ("fusion.4", 130, 140, BODY))
    spans = [("window", 0.0, 200.0), ("route_call", 5.0, 80.0),
             ("gs.route.prepare", 5.0, 9.0), ("gs.route.dispatch", 9.0, 10.0),
             ("copy_back", 72.0, 80.0), ("route_call", 100.0, 160.0),
             ("gs.route.prepare", 100.0, 105.0),
             ("gs.route.dispatch", 105.0, 110.0), ("copy_back", 150.0, 160.0)]
    r = program_trace.Scoped(
        program_trace.ScopedEvents(ops=ops, spans=spans, scopes=scopes),
        ["TPU:0"])
    o = _observed(r)
    ms = lambda ns: pytest.approx(ns * 1e-6)
    assert _read("prepare_host_ms.route", o) == ms((4 + 5) / 2)
    assert _read("factors_device_ms.route", o) == ms((10 + 15) / 2)
    assert _read("admit_device_ms.route", o) == ms((30 + 25) / 2)
    assert r.device_in_scope("route_call", "factors", "score", "admit",
                             "account") == pytest.approx([60e-9, 40e-9])
    # the program's spans label idle gaps like the benchmark's own
    assert r.idle_gaps()[2] == ["gs.route.prepare", pytest.approx(10e-9)]
    for name in NEW:
        if name.endswith(".serve"):
            assert _read(name, o) is None, name


def test_serve_readers_on_synthetic_events():
    ops, scopes = _ops(
        ("fusion.1", 12, 20, F), ("while.3", 20, 40, A),
        ("fusion.4", 25, 30, BODY), ("fusion.1", 62, 70, F),
        ("while.3", 70, 80, A), ("fusion.1", 82, 88, F),
        ("while.3", 88, 95, A))
    spans = [("window", 0.0, 100.0),
             ("serve_step", 0.0, 50.0), ("gs.serve.step", 0.0, 50.0),
             ("gs.serve.draft", 0.0, 10.0), ("gs.serve.fetch", 40.0, 44.0),
             ("gs.serve.commit", 44.0, 50.0),
             ("serve_step", 50.0, 100.0), ("gs.serve.step", 50.0, 100.0),
             ("gs.serve.draft", 50.0, 60.0), ("gs.serve.fetch", 80.0, 81.0),
             ("gs.serve.commit", 81.0, 82.0), ("gs.serve.fetch", 95.0, 97.0),
             ("gs.serve.commit", 97.0, 100.0)]
    r = program_trace.Scoped(
        program_trace.ScopedEvents(ops=ops, spans=spans, scopes=scopes),
        ["TPU:0"])
    o = _observed(r, drafts=[1, 2])
    ms = lambda ns: pytest.approx(ns * 1e-6)
    # per step: divided by the benchmark's two serve_step spans
    assert _read("draft_host_ms.serve", o) == ms((10 + 10) / 2)
    assert _read("fetch_host_ms.serve", o) == ms((4 + 1 + 2) / 2)
    assert _read("commit_host_ms.serve", o) == ms((6 + 1 + 3) / 2)
    # per draft: three drafts in the two steps
    assert _read("factors_device_ms.serve", o) == ms((8 + 8 + 6) / 3)
    assert _read("admit_device_ms.serve", o) == ms((20 + 10 + 7) / 3)


def test_events_with_scopes_round_trip():
    ops, scopes = _ops(("fusion.1", 1, 2, F), ("copy.2", 2, 3, ""),
                       ("fusion.3", 3, 4, F))
    ev = program_trace.ScopedEvents(ops=ops, spans=[("window", 0.0, 5.0)],
                                    scopes=scopes)
    back = program_trace.ScopedEvents.from_json(ev.to_json())
    assert back.scopes == scopes and back.spans == ev.spans
    # a recording without scopes reads as one with none
    plain = program_trace.ScopedEvents.from_json(
        trace.Events(ops=ops, spans=ev.spans).to_json())
    assert plain.scopes == {}


#: the nine readers that time layers from outside, on the recorded trace
#: of a program without spans or scopes (24 steps, 29 drafts)
BEFORE = {
    "device_idle_pct": 29.258802271966832,
    "device_idle_pct.serve": 29.258802271966832,
    "drafts_per_step": 1.2083333333333333,
    "host_ms.route": None,
    "host_ms.serve": 48.315849541666665,
    "route_device_ms.route": None,
    "route_device_ms.serve": 97.35520731034482,
    "window_compiles": 0,
    "window_compiles.serve": 0,
}


@pytest.mark.parametrize("name", sorted(BEFORE) + list(NEW))
def test_readers_on_a_trace_without_program_marks(name, tmp_path,
                                                  monkeypatch):
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path)
    with gzip.open(DATA / "trace_dense4_serve_call.json.gz", "rt") as f:
        text = f.read()
    drafts = [1] * 19 + [2] * 5
    plain = trace.Reduced(trace.Events.from_json(text), ["TPU:0"])
    scoped = program_trace.Scoped(program_trace.ScopedEvents.from_json(text),
                                  ["TPU:0"])
    assert _read(name, _observed(plain, drafts)) == BEFORE.get(name)
    assert _read(name, _observed(scoped, drafts)) == BEFORE.get(name)


STEP_CHILDREN = ("gs.serve.pool", "gs.serve.draft", "gs.route.prepare",
                 "gs.route.dispatch", "gs.serve.fetch", "gs.serve.commit")


def test_program_marks_cover_a_recorded_serve_day():
    """One serve_stream day of dense4.serve traced on a TPU v5e with the
    program's spans and scopes: they cover the host and device time the
    benchmark's own serve_step spans see, and every reader finds them."""
    with gzip.open(DATA / "trace_dense4_serve_day_scoped.json.gz", "rt") as f:
        r = program_trace.Scoped(
            program_trace.ScopedEvents.from_json(f.read()), ["TPU:0"])
    steps = r.spans("serve_step")
    assert len(steps) == len(r.spans("gs.serve.step")) == 24
    fetch = r.spans("gs.serve.fetch")
    drafts = [sum(a <= f[1] <= b for f in fetch) for _, a, b in steps]
    assert sum(drafts) == len(r.spans("gs.serve.commit")) > 24
    host = sum(r.host_in("serve_step"))
    assert sum(sum(r.host_in(s)) for s in STEP_CHILDREN) >= 0.9 * host
    device = sum(r.device_in("serve_step"))
    scoped = r.device_in_scope("serve_step", "factors", "score", "admit",
                               "account")
    assert sum(scoped) >= 0.95 * device
    # the ten longest idle stretches of the chip fall in program spans
    assert all(label.startswith("gs.") for label, _ in r.idle_gaps())
    o = _observed(r, drafts)
    for name in NEW:
        if name.endswith(".serve"):
            assert _read(name, o) > 0, name
    assert _read("factors_device_ms.serve", o) > 4 * _read(
        "admit_device_ms.serve", o)
