"""Reduction of a profiler trace to the per-layer numbers.

Two stages, so that the second can be checked on a small recorded trace:

1. ``read_xplane`` turns the profiler's ``.xplane.pb`` into plain events:
   per device, the operations that ran (name, start, end) from the
   device plane's op line; and the benchmark's own spans (``bench.*``
   trace annotations) from the host plane. Both are on the profiler's
   clock.
2. ``Reduced`` answers questions about those events: device busy time (the
   union of op intervals), idle gaps labelled by the benchmark span they
   fall in, device and host time inside spans.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os

import numpy as np

#: device op lines, in order of preference
OP_LINES = ("XLA Ops", "XLA Modules")
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Events:
    """Plain trace events. ``ops[device] = (names, starts, ends)`` with
    times in ns; ``spans = [(name, start, end), ...]``."""

    ops: dict
    spans: list

    def to_json(self) -> str:
        return json.dumps(dict(
            ops={d: [list(n), s.tolist(), e.tolist()]
                 for d, (n, s, e) in self.ops.items()},
            spans=self.spans))

    @classmethod
    def from_json(cls, text: str) -> "Events":
        raw = json.loads(text)
        ops = {d: (list(n), np.asarray(s, float), np.asarray(e, float))
               for d, (n, s, e) in raw["ops"].items()}
        return cls(ops=ops, spans=[tuple(x) for x in raw["spans"]])


def op_label(text: str) -> str:
    """"%fusion.155 = s32[1000000]{0:T(1024)} fusion(...)" ->
    "fusion.155 s32[1000000]": the instruction and its output shape."""
    name, _, rest = text.partition(" = ")
    shape = ("tuple" if rest.startswith("(")
             else rest.split("{", 1)[0].split(" ", 1)[0])
    return f"{name.lstrip('%')} {shape}".strip()


def latest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str) -> Events:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[k] for k in OP_LINES if k in lines), None)
            if line is None:
                continue
            names, starts, ends = [], [], []
            for ev in line.events:
                names.append(ev.name)
                starts.append(ev.start_ns)
                ends.append(ev.start_ns + ev.duration_ns)
            ops[plane.name[len("/device:"):]] = (
                names, np.asarray(starts, float), np.asarray(ends, float))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      float(ev.start_ns),
                                      float(ev.start_ns + ev.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return Events(ops=ops, spans=spans)


def union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """(k, 2) sorted, disjoint cover of the intervals."""
    if not len(starts):
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > run_end[:-1]]
    idx = np.flatnonzero(new)
    return np.stack([s[idx], run_end[np.r_[idx[1:] - 1, len(s) - 1]]], 1)


def covered(u: np.ndarray, a: float, b: float) -> float:
    """Length of the cover ``u`` inside [a, b]."""
    if not len(u):
        return 0.0
    return float(np.maximum(np.minimum(u[:, 1], b)
                            - np.maximum(u[:, 0], a), 0.0).sum())


def gaps(u: np.ndarray, a: float, b: float) -> np.ndarray:
    """(k, 2) stretches of [a, b] that the cover ``u`` leaves idle."""
    inside = u[(u[:, 1] > a) & (u[:, 0] < b)] if len(u) else u
    edges = np.r_[a, np.clip(inside.ravel(), a, b), b].reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


class Reduced:
    """Questions about one traced window. ``devices`` names the chips the
    cell uses (others in the trace are ignored)."""

    def __init__(self, ev: Events, devices: list[str]):
        self.ev = ev
        win = [s for s in ev.spans if s[0] == "window"]
        if not win:
            raise ValueError("trace has no bench.window span")
        self.t0, self.t1 = win[0][1], win[0][2]
        self.devices = [d for d in devices if d in ev.ops]
        self.cover = {d: union(*ev.ops[d][1:]) for d in self.devices}
        self.all_cover = union(
            np.concatenate([ev.ops[d][1] for d in self.devices] or [[]]),
            np.concatenate([ev.ops[d][2] for d in self.devices] or [[]]))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_s(self, device: str) -> float:
        return covered(self.cover[device], self.t0, self.t1) * 1e-9

    @property
    def busiest(self) -> str | None:
        if not self.devices:
            return None
        return max(self.devices, key=self.busy_s)

    def mean_busy_s(self) -> float:
        return float(np.mean([self.busy_s(d) for d in self.devices]))

    def spans(self, name: str) -> list[tuple]:
        return [s for s in self.ev.spans
                if s[0] == name and s[1] >= self.t0 and s[2] <= self.t1]

    def device_in(self, name: str) -> list[float]:
        """Busy seconds of the busiest chip inside each span ``name``."""
        u = self.cover[self.busiest]
        return [covered(u, a, b) * 1e-9 for _, a, b in self.spans(name)]

    def host_in(self, name: str) -> list[float]:
        """Seconds of each span ``name`` in which no chip runs an op."""
        return [((b - a) - covered(self.all_cover, a, b)) * 1e-9
                for _, a, b in self.spans(name)]

    def top_ops(self, k: int = 10) -> list[list]:
        """Device ops of the busiest chip that took most time in the
        window, by HLO instruction name (the trace gives the whole
        instruction text; the name is what precedes " = "), with the
        instruction's output shape: [[name, seconds], ...]."""
        names, s, e = self.ev.ops[self.busiest]
        keep = (s >= self.t0) & (e <= self.t1)
        totals: dict[str, float] = {}
        for n, d in zip(np.asarray(names, object)[keep], (e - s)[keep]):
            n = op_label(n)
            totals[n] = totals.get(n, 0.0) + d * 1e-9
        return [[n, v] for n, v in sorted(totals.items(),
                                          key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Longest idle stretches of the busiest chip in the window, each
        labelled by the innermost benchmark span around its midpoint:
        [[label, seconds], ...]."""
        g = gaps(self.cover[self.busiest], self.t0, self.t1)
        g = g[np.argsort(g[:, 0] - g[:, 1], kind="stable")][:k]
        inner = [s for s in self.ev.spans if s[0] != "window"]
        out = []
        for a, b in g:
            mid = 0.5 * (a + b)
            around = [s for s in inner if s[1] <= mid <= s[2]]
            label = (min(around, key=lambda s: s[2] - s[1])[0] if around
                     else "between_calls")
            out.append([label, (b - a) * 1e-9])
        return out
