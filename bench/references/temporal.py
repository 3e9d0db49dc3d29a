"""Reference of policy kind ``temporal``: the plain numpy model of joint
(defer, region, tier) placement, the day plan of a deferral deployment.
Imports nothing of the program.

``harness.reference`` fixes the execution hour to the arrival hour; this
model adds the time axis and reuses its Table-1 factors, pair scoring,
round-based admission, shed tail and settlement:

* a request arriving in hour ``a`` may execute in any hour ``a + d`` with
  ``0 <= d <=`` its slack (whole hours, at most ``temporal.max_defer_h``)
  and ``a + d`` inside the grid's horizon; hours index the horizon
  absolutely, so a deferral past midnight lands in day two's cells;
* each (defer, region, tier) candidate is scored as ``reference.score``
  scores a (region, tier) pair, at the CI of its own hour: the request's
  device and access network billed at home in that hour, the DC
  components at the candidate region;
* candidates are ordered defer-major, then region, then tier, and an
  exact tie goes to the lowest column;
* admission runs in rounds over the whole stream at once, since a
  deferred request competes in a later window's cells: each round every
  unplaced request aims at its best open candidate, and each (absolute
  hour, region, tier) cell admits its contenders by (arrival window,
  stream order) up to its remaining whole budget; a routable request
  left without an open candidate is shed at its arrival hour;
* carbon is settled at the executing (region, absolute hour).
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import reference
from harness.grids import component_table
from harness.reference import N_TIERS, Decisions, Scored


def _rows(fac: reference.Factors, idx: np.ndarray) -> reference.Factors:
    """The Table-1 factors of the rows ``idx``."""
    return reference.Factors(*(getattr(fac, f.name)[idx]
                               for f in dataclasses.fields(fac)))


class Problem:
    """One stream of the day plan, scored once; ``solve`` runs the
    admission, following another solver's near-ties as
    ``reference.Problem.solve`` does, with the whole stream as one
    segment."""

    def __init__(self, stream, cfg: dict, g: dict, caps: np.ndarray,
                 precision: str = "highest"):
        self.table = component_table(g)
        self.horizon = self.table.shape[1]
        self.precision = precision
        self.fac = reference.table1(
            stream, reference.n_active_params(cfg["model"]),
            reference.infra_arrays(cfg["fleet"], cfg["embodied_model"]))
        self.home = np.asarray(stream.region, np.int64)
        self.hour = (np.floor(np.asarray(stream.t_hours, np.float64))
                     .astype(np.int64) % self.horizon)
        self.max_defer = int(cfg["temporal"]["max_defer_h"])
        slack = (np.zeros(len(self.home)) if stream.slack_hours is None
                 else np.floor(np.asarray(stream.slack_hours)))
        slack = np.clip(slack.astype(np.int64), 0, self.max_defer)
        self.caps = np.asarray(caps, np.float64).reshape(-1)
        n_pairs = self.caps.size
        s = np.full((len(self.home), (self.max_defer + 1) * n_pairs), np.inf,
                    np.float32)  # +inf: not a candidate

        def score_rows(d: int, idx: np.ndarray) -> None:
            sc = reference.score(_rows(self.fac, idx), self.home[idx],
                                 self.hour[idx] + d, g, self.table, precision)
            # a dense grid's columns are its (region, tier) pairs in order
            assert (sc.pair == np.arange(n_pairs)).all()
            s[idx, d * n_pairs:(d + 1) * n_pairs] = sc.s

        # defer d is a candidate of the rows whose slack reaches it inside
        # the horizon; blocks of rows are scored on threads (numpy's array
        # operations release the interpreter's lock)
        jobs = []
        for d in range(self.max_defer + 1):
            idx = np.flatnonzero((d <= slack) & (self.hour + d < self.horizon))
            jobs += [(d, idx[lo:lo + reference.BLOCK])
                     for lo in range(0, len(idx), reference.BLOCK)]
        with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
            for f in [pool.submit(score_rows, *job) for job in jobs]:
                f.result()
        self.order = np.argsort(self.hour, kind="stable")  # priority
        # the stream in admission priority, (arrival window, stream order)
        self.s = s[self.order]
        self.finite = np.isfinite(self.s)
        self.win = self.hour[self.order]

    def _follow(self, other: dict | None):
        """Per-row column of ``other``'s placement (-1 where it shed the
        row or placed it off the candidate list) and per-row tier of its
        shed rows (-1 elsewhere), in priority order."""
        if other is None:
            return None, None
        other = {k: np.asarray(v)[self.order] for k, v in other.items()}
        shed = other["shed"].astype(bool)
        target = other["target"].astype(np.int64)
        d = other["exec_hour"].astype(np.int64) - self.win
        col = (d * self.caps.size
               + other["exec_region"].astype(np.int64) * N_TIERS + target)
        col = np.where(~shed & (d >= 0) & (d <= self.max_defer), col, -1)
        return col, np.where(shed, target, -1)

    def _admit(self, follow_col, flip=None):
        """``reference.admit`` over the whole stream in priority order, a
        candidate column d * pairs + pair of a row arriving in window a
        standing for the cell (a + d, pair): round-based best-open
        admission, the same near-tie following and flips. It reads which
        candidates are open from a per-(arrival window, column) table
        rather than per row and column, a few times faster at a million
        rows of 288 candidates.

        Returns (placed, column, widest followed gap, near-ties not
        taken: a dict from priority position to the tied column)."""
        n_pairs, s, finite, win = self.caps.size, self.s, self.finite, self.win
        width = s.shape[1]
        caps = np.tile(self.caps, self.horizon)
        used = np.zeros_like(caps)
        # arrival window a, column d * pairs + p -> flat cell (a + d, p)
        d, p = np.divmod(np.arange(width), n_pairs)
        at_cell = (np.arange(self.horizon)[:, None] + d) * n_pairs + p
        inside = at_cell < caps.size
        at_cell = np.where(inside, at_cell, 0)
        placed_col = np.full(len(s), -1, np.int64)
        untaken: dict[int, int] = {}
        flip_col = np.full(len(s), -1, np.int64)
        for r, c in (flip or {}).items():
            flip_col[r] = c
        live = np.flatnonzero(finite.any(1))
        tie_gap = 0.0

        def contest(cell):
            """Who of this round's contenders fits, by priority per cell."""
            order = np.argsort(cell, kind="stable")
            sorted_cell = cell[order]
            first = np.r_[True, sorted_cell[1:] != sorted_cell[:-1]]
            starts = np.flatnonzero(first)
            rank = np.empty(len(cell), np.int64)
            rank[order] = np.arange(len(cell)) - starts[np.cumsum(first) - 1]
            fits = rank < np.floor(caps[cell] - used[cell])
            return fits, sorted_cell[starts], np.diff(np.r_[starts, len(cell)])

        def tied(alt, mask, s_open, col, at):
            best = s_open[at, col]
            a = np.maximum(alt, 0)
            gap = (s_open[at, a] - best) / best
            return ((alt >= 0) & (a != col) & mask[at, a]
                    & (gap <= reference.TIE), gap)

        def aim(live, open_at):
            """What each of ``live`` aims at this round, row by row: the
            rows with an open candidate, their best open column and, when
            following, their near-ties (followed, flipped, runner-up)."""
            mask = open_at[win[live]] & finite[live]
            keep = mask.any(1)
            live, mask = live[keep], mask[keep]
            s_open = np.where(mask, s[live], np.inf)
            col = np.argmin(s_open, axis=1)
            if follow_col is None:
                return live, col
            at = np.arange(len(live))
            t_follow, g_follow = tied(follow_col[live], mask, s_open, col, at)
            t_flip, g_flip = tied(flip_col[live], mask, s_open, col, at)
            s_open[at, col] = np.inf
            runner = np.argmin(s_open, axis=1)
            best = s[live, col]
            near = (s_open[at, runner] - best) / best <= reference.TIE
            return (live, col, t_follow, g_follow, t_flip, g_flip, runner,
                    near)

        def contest(cell):
            """Who of this round's contenders fits, by priority per cell."""
            order = np.argsort(cell, kind="stable")
            sorted_cell = cell[order]
            first = np.r_[True, sorted_cell[1:] != sorted_cell[:-1]]
            starts = np.flatnonzero(first)
            rank = np.empty(len(cell), np.int64)
            rank[order] = np.arange(len(cell)) - starts[np.cumsum(first) - 1]
            fits = rank < np.floor(caps[cell] - used[cell])
            return fits, sorted_cell[starts], np.diff(np.r_[starts, len(cell)])

        while live.size:
            open_at = (np.floor(caps - used) >= 1.0)[at_cell] & inside
            # rows aim independently: in blocks, to bound host memory
            parts = [aim(live[lo:lo + reference.BLOCK], open_at)
                     for lo in range(0, live.size, reference.BLOCK)]
            live, col, *ties = (np.concatenate(x) for x in zip(*parts))
            if not live.size:
                break
            cell_of = lambda col: at_cell[win[live], col]
            fits, uniq, total = contest(cell_of(col))
            if follow_col is not None:
                t_follow, g_follow, t_flip, g_flip, runner, near = ties
                move = (t_follow & fits) | t_flip
                gap = np.where(t_flip, g_flip, g_follow)
                # near-ties left alone this round: the followed cell where
                # the row is turned away, else the runner-up open cell
                alt = np.where(t_follow, follow_col[live], runner)
                for j in np.flatnonzero((t_follow | near) & ~move):
                    untaken.setdefault(int(live[j]), int(alt[j]))
                if move.any():
                    tie_gap = max(tie_gap, float(gap[move].max()))
                    col = np.where(move, np.where(t_flip, flip_col[live],
                                                  follow_col[live]), col)
                    fits, uniq, total = contest(cell_of(col))
            placed_col[live[fits]] = col[fits]
            used[uniq] += np.minimum(np.maximum(
                np.floor(caps[uniq] - used[uniq]), 0.0), total)
            live = live[~fits]
        return placed_col >= 0, placed_col, tie_gap, untaken

    def _attempt(self, follow_col, follow_tier, flip=None):
        """One admission of the whole stream: (decisions in stream order,
        widest followed gap, near-ties not taken by priority position)."""
        n_pairs, n = self.caps.size, len(self.s)
        placed, col, gap, untaken = self._admit(follow_col, flip)
        rows = np.arange(n)
        pairs = Scored(self.s, np.broadcast_to(
            np.arange(self.s.shape[1]) % n_pairs, self.s.shape))
        tier, region, shed, gap2 = reference.finish(
            pairs, rows, self.home[self.order], placed, col % n_pairs,
            follow_tier)
        defer = np.where(placed, col // n_pairs, 0)
        out = dict(target=np.zeros(n, np.int64),
                   exec_region=np.zeros(n, np.int64),
                   shed=np.zeros(n, bool), exec_hour=np.zeros(n, np.int64))
        for f, v in zip(out, (tier, region, shed, self.win + defer)):
            out[f][self.order] = v
        return out, max(gap, gap2), untaken

    def _misses(self, out: dict, follow: dict) -> np.ndarray:
        """Priority positions whose decision differs from ``follow``'s."""
        bad = np.zeros(len(self.home), bool)
        for f in ("target", "exec_region", "shed", "exec_hour"):
            bad |= out[f] != np.asarray(follow[f])
        return bad[self.order]

    def solve(self, follow: dict | None = None) -> Decisions:
        """The reference's decisions; with ``follow`` (another solver's
        ``target``, ``exec_region``, ``exec_hour`` and ``shed`` columns)
        near-ties are resolved the way that solver resolved them, trying
        the untaken near-ties of differing rows one at a time and keeping
        each that brings the plan closer (``reference.MAX_FLIPS``)."""
        follow_col, follow_tier = self._follow(follow)
        out, gap, untaken = self._attempt(follow_col, follow_tier)
        if follow is not None:
            bad = self._misses(out, follow)
            flip: dict[int, int] = {}
            tried: set = set()
            for _ in range(reference.MAX_FLIPS):
                todo = [(r, c) for r, c in untaken.items()
                        if bad[r] and (r, c) not in tried]
                if not todo:
                    break
                tried.add(todo[0])
                trial = self._attempt(follow_col, follow_tier,
                                      {**flip, todo[0][0]: todo[0][1]})
                trial_bad = self._misses(trial[0], follow)
                if trial_bad.sum() < bad.sum():
                    flip[todo[0][0]] = todo[0][1]
                    (out, gap, untaken), bad = trial, trial_bad
        carbon = reference.settle(self.fac, self.home, out["exec_region"],
                                  out["exec_hour"], out["target"],
                                  self.table, self.precision)
        return Decisions(carbon_g=carbon, tie_gap=gap, **out)


def problem(cell, g: dict, caps, stream, precision: str = "highest"):
    """The plain reference of one stream of the day plan (entry
    ``route``), under the policy's caps in every hourly window."""
    if cell.traffic["entry"] != "route":
        raise ValueError("the temporal reference models the day plan "
                         "(entry 'route') only")
    return Problem(stream, cell.config, g,
                   np.asarray(caps, np.float32).astype(np.float64),
                   precision)
