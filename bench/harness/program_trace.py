"""The program's own spans and device scopes, read from the traced window.

The program marks its layers itself: host spans named ``gs.*``
(``jax.profiler.TraceAnnotation``, on the profiler's clock like the
benchmark's ``bench.*`` spans) and ``jax.named_scope`` names on the device
ops of its programs (``factors``, ``score``, ``admit``, ``account``,
``settle``). This module extends ``harness.trace``: ``read_xplane`` reads
the events ``trace.read_xplane`` reads, plus the ``gs.*`` spans under their
full names and the scope path of every device op; ``Scoped`` answers every
question ``trace.Reduced`` answers, idle gaps labelled by the innermost
span of either kind among them, and adds device time inside a scope.

A trace of a program without spans or scopes (an older commit) carries
none; the readers built on this module then return None.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from pathlib import Path

import numpy as np

from harness import cells, trace

PROGRAM_PREFIX = "gs."
#: where ``run.py`` writes the traced window's profile: one directory per
#: process, so that runs in processes side by side never share one
TRACE_DIR = cells.ROOT / ".bench_trace" / f"pid{os.getpid()}"


@dataclasses.dataclass
class ScopedEvents(trace.Events):
    """``trace.Events`` plus ``scopes[device]``: the ``op_name`` path of
    each op, in the order of ``ops[device]`` ("" where the op has none)."""

    scopes: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        raw = json.loads(super().to_json())
        table = sorted({p for paths in self.scopes.values() for p in paths})
        index = {p: i for i, p in enumerate(table)}
        raw["scopes"] = dict(table=table, ops={
            d: [index[p] for p in paths] for d, paths in self.scopes.items()})
        return json.dumps(raw)

    @classmethod
    def from_json(cls, text: str) -> "ScopedEvents":
        ev = trace.Events.from_json(text)
        raw = json.loads(text).get("scopes") or dict(table=[], ops={})
        scopes = {d: [raw["table"][i] for i in idx]
                  for d, idx in raw["ops"].items()}
        return cls(ops=ev.ops, spans=ev.spans, scopes=scopes)


def _xspace_messages():
    """The profile's protobuf classes (``XSpace``) from the installed
    TensorFlow's generated ``xplane_pb2.py``, loaded from that file alone:
    importing the package would load all of TensorFlow. None where it is
    not installed."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = (Path(spec.submodule_search_locations[0])
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    if not path.is_file():
        return None
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def op_scopes(path: str) -> dict:
    """Device op (its instruction text, the event's name) -> ``op_name``
    path (``jit(f)/admit/while/body/add:``). The profiler keeps the path in
    the ``tf_op`` stat of each op's event metadata, which
    ``jax.profiler.ProfileData`` does not expose, so the profile is read
    once more as a protobuf. Ops the compiler made without metadata (a
    ``while``, a copy, some fusions) have none."""
    messages = _xspace_messages()
    if messages is None:
        return {}
    space = messages.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        tf_op = {k for k, m in plane.stat_metadata.items()
                 if m.name == "tf_op"}
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if stat.metadata_id in tf_op:
                    out[meta.name] = stat.str_value
    return out


def read_xplane(path: str) -> ScopedEvents:
    from jax.profiler import ProfileData

    base = trace.read_xplane(path)
    by_op = op_scopes(path)
    scopes = {d: [by_op.get(n, "") for n in names]
              for d, (names, _, _) in base.ops.items()}
    spans = list(base.spans)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return ScopedEvents(ops=base.ops, spans=spans, scopes=scopes)


class Scoped(trace.Reduced):
    """``trace.Reduced`` over ``ScopedEvents``: the program's spans are
    among ``spans``, ``host_in`` and ``idle_gaps``, and device time can be
    asked of a scope."""

    def has_scope(self, scope: str) -> bool:
        """Does any op of the busiest chip carry ``scope``?"""
        return any(scope in p.split("/")
                   for p in set(self.ev.scopes.get(self.busiest, ())))

    def device_in_scope(self, name: str, *scopes: str) -> list[float]:
        """Seconds of each span ``name`` in which the busiest chip runs an
        op that carries one of ``scopes`` as a component of its path: the
        union of those ops' intervals (a ``while`` op's event encloses the
        ops of its body, so a sum would count them twice)."""
        names, s, e = self.ev.ops[self.busiest]
        paths = self.ev.scopes.get(self.busiest, [""] * len(names))
        keep = {p: bool(set(p.split("/")) & set(scopes)) for p in set(paths)}
        mask = np.fromiter((keep[p] for p in paths), bool, len(paths))
        u = trace.union(s[mask], e[mask])
        return [trace.covered(u, a, b) * 1e-9 for _, a, b in self.spans(name)]


def of(o) -> Scoped | None:
    """The window of ``o`` (``run.Observed``) with the program's spans and
    scopes: ``o.trace`` itself when it already is one, else the profile
    that ``run.py`` wrote, read once and kept on ``o``. None when the run
    was not traced or the profile on disk is not this window's."""
    if o.trace is None or isinstance(o.trace, Scoped):
        return o.trace
    if not hasattr(o, "program_trace"):
        o.program_trace = None
        try:
            path = trace.latest_xplane(str(TRACE_DIR))
        except FileNotFoundError:
            return None
        r = Scoped(read_xplane(path), o.trace.devices)
        if (r.t0, r.t1) == (o.trace.t0, o.trace.t1):
            o.program_trace = r
    return o.program_trace


def host_ms_per(o, span: str, per: str) -> float | None:
    """Milliseconds of the program's ``span`` in which no chip runs an op,
    summed over the window and divided by the benchmark's ``per`` spans."""
    r = of(o)
    if r is None or not r.spans(span) or not r.spans(per):
        return None
    return 1e3 * sum(r.host_in(span)) / len(r.spans(per))


def scope_ms_per_call(o, scope: str) -> float | None:
    """Milliseconds of device time under ``scope`` inside each
    ``route_call`` span, averaged over calls."""
    r = of(o)
    if r is None or not r.has_scope(scope) or not r.spans("route_call"):
        return None
    v = r.device_in_scope("route_call", scope)
    return 1e3 * sum(v) / len(v)


def scope_ms_per_draft(o, scope: str) -> float | None:
    """Milliseconds of device time under ``scope`` inside the window's
    ``serve_step`` spans, divided by the drafts those steps routed."""
    r = of(o)
    drafts = sum(o.drafts_per_step)
    if r is None or not r.has_scope(scope) or not drafts:
        return None
    return 1e3 * sum(r.device_in_scope("serve_step", scope)) / drafts
