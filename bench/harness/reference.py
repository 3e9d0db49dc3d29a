"""Plain reference of the routing semantics the benchmark checks.

Independent of the program: numpy only, fed with the configuration file,
the grid tables ``grids.py`` builds and the generated stream. It follows
the published Table-1 carbon model (GreenScale, arXiv:2304.00404) and the
repository's documented placement semantics:

* every (candidate region, tier) pair of a request is scored by its carbon
  at the request's arrival hour, with the request's own device and access
  network billed at home and the DC components at the candidate; the QoS
  check adds the WAN round trip; remote on-device pairs do not exist; a
  remote score is multiplied by the grid's latency penalty;
* admission runs in rounds: each round every unplaced request with an open
  candidate cell aims at its best open cell, and a cell admits its
  contenders in stream order up to its remaining whole budget; a request
  with finite scores but no open cell left is shed and keeps its first
  choice as nominal placement; a request with no finite score runs on the
  device at home, outside capacity;
* carbon is settled at the executing (region, hour) cell.

``precision="highest"`` multiplies in float32. ``"high"`` is the control:
each float32 product is formed from bfloat16 halves the way a three-pass
bfloat16 matrix unit forms it (``hi*hi + hi*lo + lo*hi``), which is the
step below float32 at highest precision.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from harness.grids import component_table as grids_table

J_PER_KWH = np.float32(3.6e6)
N_TIERS = 3
BLOCK = 1 << 16  # rows scored per block, to bound host memory


def n_active_params(model: dict) -> int:
    """Parameters touched per token of a dense decoder with SwiGLU feed-
    forward and an untied output head (h2o-danube-1.8b's layout)."""
    d, hd, ff = model["d_model"], model["head_dim"], model["d_ff"]
    attn = d * hd * (2 * model["n_heads"] + 2 * model["n_kv_heads"])
    layer = 2 * d + attn + 3 * d * ff
    return 2 * model["vocab_size"] * d + model["n_layers"] * layer + d


def infra_arrays(fleet: dict, embodied: str = "act") -> dict:
    """Table-1 infrastructure constants as float32 (DC power carries PUE;
    embodied carbon is ACT, a fixed share of the LCA report, for compute
    tiers and the LCA report for networks)."""
    tiers = [fleet["mobile"], fleet["edge_dc"], fleet["hyper_dc"]]
    nets = [fleet["edge_net"], fleet["core_net"]]
    share = fleet["act_over_lca"] if embodied == "act" else 1.0
    f = lambda v: np.asarray(v, np.float32)
    return dict(
        eff_flops=f([t["eff_flops"] for t in tiers]),
        eff_mem_bw=f([t["eff_mem_bw"] for t in tiers]),
        p_comp=f([t["p_comp"] * t["pue"] for t in tiers]),
        p_idle=f([t["p_idle"] * t["pue"] for t in tiers]),
        p_comm=f(fleet["mobile"]["p_comm"]),
        ecf=f([t["ecf_lca_g"] * share for t in tiers]),
        life=f([t["lifetime_s"] for t in tiers]),
        net_bw=f([n["bandwidth_bps"] for n in nets]),
        net_lat=f([n["base_latency_s"] for n in nets]),
        net_p=f([n["p_active"] for n in nets]),
        net_users=f([n["n_user"] for n in nets]),
        net_ecf=f([n["ecf_lca_g"] for n in nets]),
        net_life=f([n["lifetime_s"] for n in nets]),
        n_user_edge=f(fleet["n_user_edge"]),
        n_user_dc=f(fleet["n_user_dc"]),
        n_batch_dc=f(fleet["n_batch_dc"]),
    )


@dataclasses.dataclass(frozen=True)
class Factors:
    """Per-request Table-1 quantities, float32."""

    op_unit: np.ndarray  # (N, 3, 5) operational carbon per unit CI
    emb: np.ndarray  # (N, 3) embodied carbon, summed over components
    latency: np.ndarray  # (N, 3) end-to-end seconds
    budget: np.ndarray  # (N,) QoS latency budget
    avail: np.ndarray  # (N, 3) tier exists for the request


def table1(stream, n_active: int, inf: dict) -> Factors:
    """Table 1 of the paper, all three execution targets, per request."""
    f32 = lambda x: np.asarray(x, np.float32)
    flops = f32(2.0 * n_active * (stream.prompt_tokens
                                  + stream.max_new_tokens))
    mem = f32(2.0 * n_active * np.maximum(stream.max_new_tokens, 1))
    d_in = f32(stream.bytes_per_token * stream.prompt_tokens)
    d_out = f32(stream.bytes_per_token * stream.max_new_tokens)
    t = [np.maximum(flops / inf["eff_flops"][k], mem / inf["eff_mem_bw"][k])
         for k in range(N_TIERS)]
    t_m, t_e, t_h = t
    payload = d_in + d_out
    t_ce = payload / inf["net_bw"][0] + inf["net_lat"][0]
    t_cr = payload / inf["net_bw"][1] + inf["net_lat"][1]
    pc, pi, pm = inf["p_comp"], inf["p_idle"], inf["p_comm"]
    nue, nud, nb = inf["n_user_edge"], inf["n_user_dc"], inf["n_batch_dc"]
    net_p, net_u = inf["net_p"], inf["net_users"]
    ecf, life = inf["ecf"], inf["life"]
    net_ecf, net_life = inf["net_ecf"], inf["net_life"]
    zero = np.zeros_like(t_m)
    e = lambda x: x / J_PER_KWH
    # rows: target; columns: [mobile, edge net, edge DC, core net, hyper DC]
    op = [
        [e(t_m * pc[0]), zero, e(t_m * pi[1] / nue), zero,
         e(t_m * pi[2] / nud)],
        [e(t_ce * pm + t_e * pi[0]), e(t_ce * net_p[0] / net_u[0]),
         e(t_e * pc[1] / nue), zero, e((t_ce + t_e) * pi[2] / nud)],
        [e(t_ce * pm + (t_cr + t_h) * pi[0]), e(t_ce * net_p[0] / net_u[0]),
         e((t_ce + t_cr + t_h) * pi[1] / nue),
         e(t_cr * net_p[1] / net_u[1]), e(t_h * pc[2] / nb)],
    ]
    emb = [
        [ecf[0] * t_m / life[0], zero, ecf[1] / nue * t_m / life[1], zero,
         ecf[2] / nud * t_m / life[2]],
        [ecf[0] * (t_ce + t_e) / life[0],
         net_ecf[0] / net_u[0] * t_ce / net_life[0],
         ecf[1] / nue * t_e / life[1], zero,
         ecf[2] / nud * (t_ce + t_e) / life[2]],
        [ecf[0] * (t_ce + t_cr + t_h) / life[0],
         net_ecf[0] / net_u[0] * t_ce / net_life[0],
         ecf[1] / nue * (t_ce + t_cr + t_h) / life[1],
         net_ecf[1] / net_u[1] * t_cr / net_life[1],
         ecf[2] / nb * t_h / life[2]],
    ]
    op_unit = np.stack([np.stack(row, -1) for row in op], 1)
    emb_sum = np.stack([_sum(row) for row in emb], 1)
    latency = np.stack([t_m, t_ce + t_e, t_ce + t_cr + t_h], 1)
    return Factors(op_unit=op_unit.astype(np.float32),
                   emb=emb_sum.astype(np.float32),
                   latency=latency.astype(np.float32),
                   budget=f32(stream.latency_budget_s),
                   avail=np.asarray(stream.available, bool))


def _sum(terms):
    out = terms[0]
    for x in terms[1:]:
        out = out + x
    return out


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (to nearest, ties to even)."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + (((u >> 16) & 1) + np.uint32(0x7FFF))) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def mul(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """float32 product, exact-rounded (``highest``) or from bfloat16 halves
    (``high``)."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if precision == "highest":
        return a * b
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return (a_lo * b_hi + a_hi * b_lo) + a_hi * b_hi


def candidates(g: dict) -> np.ndarray:
    """(R, R) int64 candidate regions of each home, ascending: every
    region (the grid's adjacency masks the ones it does not join)."""
    r = g["ci_hourly"].shape[0]
    return np.broadcast_to(np.arange(r), (r, r)).copy()


@dataclasses.dataclass(frozen=True)
class Scored:
    """Candidate scores of a stream in ascending global-pair order."""

    s: np.ndarray  # (N, C*3) float32, +inf = not a candidate
    pair: np.ndarray  # (N, C*3) int32 global pair id region*3 + tier


def score(fac: Factors, home: np.ndarray, hour: np.ndarray, g: dict,
          table: np.ndarray, precision: str = "highest") -> Scored:
    """Carbon score of every candidate (region, tier) of every request."""
    cand_all = candidates(g)
    n, c = len(home), cand_all.shape[1]
    s_out = np.empty((n, c * N_TIERS), np.float32)
    pair_out = np.empty((n, c * N_TIERS), np.int32)
    tiers = np.arange(N_TIERS)
    for lo in range(0, n, BLOCK):
        sl = slice(lo, min(lo + BLOCK, n))
        h, hr = home[sl], hour[sl]
        cand = cand_all[h]  # (B, C)
        op = fac.op_unit[sl]  # (B, 3, 5)
        ci_home = table[h, hr]  # (B, 5)
        ci_cand = table[cand, hr[:, None]]  # (B, C, 5)
        m = lambda i, ci: mul(op[:, None, :, i], ci[..., i][..., None],
                              precision)
        home_part = m(0, ci_home[:, None]) + m(1, ci_home[:, None])
        cand_part = (m(2, ci_cand) + m(3, ci_cand)) + m(4, ci_cand)
        total = home_part + cand_part + fac.emb[sl][:, None, :]  # (B, C, 3)
        avail = fac.avail[sl]
        lat = fac.latency[sl]
        budget = fac.budget[sl]
        ok_base = (lat <= budget[:, None]) & avail
        rtt = g["rtt_s"][h[:, None], cand]  # (B, C)
        ok = ((lat[:, None, :] + rtt[..., None] <= budget[:, None, None])
              & avail[:, None, :])
        s = np.where(ok.any(-1, keepdims=True), np.where(ok, total, np.inf),
                     np.where(ok_base.any(-1)[:, None, None], np.inf,
                              np.where(avail[:, None, :], total, np.inf)))
        remote = cand != h[:, None]
        s = np.where(remote[..., None] & (tiers == 0), np.inf, s)
        pen = g["latency_penalty"][h[:, None], cand][..., None]
        s = np.where(s >= 0, s * pen, s / pen)
        s = np.where(g["adjacency"][h[:, None], cand][..., None], s, np.inf)
        s_out[sl] = s.reshape(len(h), -1)
        pair_out[sl] = (cand[..., None] * N_TIERS + tiers).reshape(len(h), -1)
    return Scored(s_out, pair_out)


@dataclasses.dataclass(frozen=True)
class Decisions:
    """Per-request routing decisions and settled carbon."""

    target: np.ndarray  # (N,) tier
    exec_region: np.ndarray  # (N,) executing region (home when shed)
    exec_hour: np.ndarray  # (N,) executing hour
    shed: np.ndarray  # (N,) bool
    carbon_g: np.ndarray  # (N,) float32 at the executing cell
    #: widest relative score gap of a near-tie resolved the way the
    #: followed decisions resolved it (0.0 when nothing was followed)
    tie_gap: float = 0.0


#: widest relative score gap treated as a near-tie when following another
#: solver's decisions. The chip forms float32 products and sums in other
#: orders and passes than numpy; its per-row carbon differs from this
#: reference's by up to about 5e-7 relative, and the near-ties it resolves
#: the other way lie up to about 2e-6 apart (measured on a TPU v5e). Where
#: two open candidates lie that close, either pick is right.
TIE = 1e-5
#: near-ties of one segment tried the other way, at most
MAX_FLIPS = 32


def admit(sc: Scored, rows: np.ndarray, win: np.ndarray, caps: np.ndarray,
          used: np.ndarray, n_pairs: int, follow_col: np.ndarray | None,
          flip: dict | None = None):
    """Round-based best-open admission of ``rows`` (stream order) against
    the per-(window, pair) cell budgets ``caps`` (flat) with ``used``
    already committed (flat, updated in place).

    ``follow_col`` (per row, -1 for none) is the column another solver
    placed the row in. Where that cell is open and within ``TIE`` of the
    row's best open score (a near-tie), the row aims there instead if it
    would be admitted to its best cell this round; a row that would be
    turned away keeps its aim (the other solver may have reached its cell
    in a later round, after the best one filled). ``flip`` maps a row's
    position in ``rows`` to a column it aims at whenever that column is
    open and near-tied with its best, admitted or not.

    Returns (placed, pair, widest followed gap, near-ties not taken: a dict
    from row position to the tied column to try)."""
    s, pair = sc.s[rows], sc.pair[rows]
    finite = np.isfinite(s)
    cells = win[:, None].astype(np.int64) * n_pairs + pair  # (k, width)
    placed_pair = np.full(len(rows), -1, np.int64)
    untaken: dict[int, int] = {}
    flip_col = np.full(len(rows), -1, np.int64)
    for r, c in (flip or {}).items():
        flip_col[r] = c
    live = np.flatnonzero(finite.any(1))
    tie_gap = 0.0

    def contest(cell):
        """Who of this round's contenders fits, by stream order per cell."""
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
        return (alt >= 0) & (a != col) & mask[at, a] & (gap <= TIE), gap

    while live.size:
        open_cell = np.floor(caps - used) >= 1.0
        mask = open_cell[cells[live]] & finite[live]
        keep = mask.any(1)
        live, mask = live[keep], mask[keep]
        if not live.size:
            break
        s_open = np.where(mask, s[live], np.inf)
        col = np.argmin(s_open, axis=1)
        at = np.arange(len(live))
        fits, uniq, total = contest(cells[live, col])
        if follow_col is not None:
            t_follow, g_follow = tied(follow_col[live], mask, s_open, col, at)
            t_flip, g_flip = tied(flip_col[live], mask, s_open, col, at)
            move = (t_follow & fits) | t_flip
            gap = np.where(t_flip, g_flip, g_follow)
            # near-ties left alone this round: the followed cell where the
            # row is turned away, else the runner-up open cell
            s2 = s_open.copy()
            s2[at, col] = np.inf
            runner = np.argmin(s2, axis=1)
            near = ((s2[at, runner] - s_open[at, col]) / s_open[at, col]
                    <= TIE)
            alt = np.where(t_follow, follow_col[live], runner)
            for j in np.flatnonzero((t_follow | near) & ~move):
                untaken.setdefault(int(live[j]), int(alt[j]))
            if move.any():
                tie_gap = max(tie_gap, float(gap[move].max()))
                col = np.where(move, np.where(t_flip, flip_col[live],
                                              follow_col[live]), col)
                fits, uniq, total = contest(cells[live, col])
        placed_pair[live[fits]] = pair[live[fits], col[fits]]
        used[uniq] += np.minimum(np.maximum(
            np.floor(caps[uniq] - used[uniq]), 0.0), total)
        live = live[~fits]
    return placed_pair >= 0, placed_pair, tie_gap, untaken


def finish(sc: Scored, rows: np.ndarray, home: np.ndarray, placed,
           placed_pair, follow_tier: np.ndarray | None):
    """Shed and fallback placement of ``rows``: (target, exec_region, shed,
    widest followed gap). A shed row keeps its first choice's tier, or the
    tier ``follow_tier`` names (-1: none) where that tier's best score is
    within ``TIE`` of the first choice."""
    s, pair = sc.s[rows], sc.pair[rows]
    at = np.arange(len(rows))
    routable = np.isfinite(s).any(1)
    first = pair[at, np.argmin(s, axis=1)]
    fallback = np.where(routable, first, home[rows] * N_TIERS)
    p = np.where(placed, placed_pair, fallback)
    shed = routable & ~placed
    tier = p % N_TIERS
    tie_gap = 0.0
    if follow_tier is not None:
        ft = follow_tier[rows]
        best = s.min(axis=1)
        in_tier = np.where((pair % N_TIERS) == np.maximum(ft, 0)[:, None],
                           s, np.inf).min(axis=1)
        with np.errstate(invalid="ignore"):
            gap = (in_tier - best) / best
        follow = shed & (ft >= 0) & (gap <= TIE)
        tier = np.where(follow, ft, tier)
        if follow.any():
            tie_gap = float(gap[follow].max())
    return tier, np.where(shed, home[rows], p // N_TIERS), shed, tie_gap


def settle(fac: Factors, home, exec_region, exec_hour, target,
           table: np.ndarray, precision: str = "highest") -> np.ndarray:
    """(N,) float32 carbon of each request at its executing cell: device
    and access network at home, DC components at the executing region."""
    n = len(home)
    out = np.empty(n, np.float32)
    for lo in range(0, n, BLOCK):
        sl = slice(lo, min(lo + BLOCK, n))
        ci = np.concatenate([table[home[sl], exec_hour[sl]][:, :2],
                             table[exec_region[sl], exec_hour[sl]][:, 2:]],
                            axis=1)
        t = target[sl]
        op = np.take_along_axis(fac.op_unit[sl], t[:, None, None], 1)[:, 0]
        terms = [mul(op[:, i], ci[:, i], precision) for i in range(5)]
        emb = np.take_along_axis(fac.emb[sl], t[:, None], 1)[:, 0]
        out[sl] = _sum(terms) + emb
    return out


class Problem:
    """One stream under one deployment, scored once; ``solve`` runs the
    admission. ``serve_batch`` set: the online loop at one-hour steps, each
    hour's arrivals earliest first in drafts of at most that many rows,
    each admitted against what earlier drafts committed (interactive work:
    every decision commits in the step it is made). Unset: the one-shot day
    plan, the whole stream at once, stream order within each window."""

    def __init__(self, stream, cfg: dict, g: dict, caps: np.ndarray,
                 n_active: int, precision: str = "highest",
                 serve_batch: int | None = None):
        self.table = grids_table(g)
        self.windows = self.table.shape[1]
        self.precision = precision
        self.fac = table1(stream, n_active, infra_arrays(
            cfg["fleet"], cfg["embodied_model"]))
        self.home = np.asarray(stream.region, np.int64)
        t = np.asarray(stream.t_hours, np.float64)
        self.hour = np.floor(t).astype(np.int64) % self.windows
        self.sc = score(self.fac, self.home, self.hour, g, self.table,
                        precision)
        self.caps = np.asarray(caps, np.float64).reshape(-1)
        # admission segments, in the order they are admitted: each hour
        # window of the day plan (windows share no cell), or each draft of
        # the online loop, earliest arrival first
        self.segments = []
        for h in range(self.windows):
            idx = np.flatnonzero(self.hour == h)
            if serve_batch is None:
                self.segments.append(idx)
                continue
            idx = idx[np.lexsort((idx, t[idx]))]
            self.segments += [idx[lo:lo + serve_batch]
                              for lo in range(0, len(idx), serve_batch)]

    def _follow(self, other: dict | None):
        """Per-row column of ``other``'s placement (-1 where ``other`` shed
        the row or placed it off the candidate list) and per-row tier of
        its shed rows (-1 elsewhere)."""
        if other is None:
            return None, None
        shed = np.asarray(other["shed"], bool)
        want = (np.asarray(other["exec_region"], np.int64) * N_TIERS
                + np.asarray(other["target"], np.int64))
        hit = self.sc.pair == want[:, None]
        col = np.where(hit.any(1) & ~shed, hit.argmax(1), -1)
        tier = np.where(shed, np.asarray(other["target"], np.int64), -1)
        return col, tier

    def solve(self, follow: dict | None = None) -> Decisions:
        """The reference's decisions; with ``follow`` (another solver's
        ``target``, ``exec_region`` and ``shed`` columns) near-ties are
        resolved the way that solver resolved them. Segments (hour windows
        of the day plan, drafts of the online loop) are admitted one after
        another; where a segment's decisions still differ from
        ``follow``'s, it is admitted once more following also the near-ties
        of rows that were turned away, and the closer of the two kept."""
        n_pairs = self.caps.size
        caps_cell = np.tile(self.caps, self.windows)
        used = np.zeros(self.windows * n_pairs)
        follow_col, follow_tier = self._follow(follow)
        n = len(self.home)
        out = dict(target=np.zeros(n, np.int64),
                   exec_region=np.zeros(n, np.int64),
                   shed=np.zeros(n, bool), exec_hour=self.hour)
        tie_gap = 0.0

        def attempt(rows, ledger, flip=None):
            placed, pp, gap, untaken = admit(
                self.sc, rows, self.hour[rows], caps_cell, ledger, n_pairs,
                None if follow_col is None else follow_col[rows], flip)
            *cols, gap2 = finish(self.sc, rows, self.home, placed, pp,
                                 follow_tier)
            return cols, max(gap, gap2), untaken

        def misses(rows, cols):
            """Positions in ``rows`` whose decision differs from
            ``follow``'s."""
            bad = np.zeros(len(rows), bool)
            for c, f in zip(cols, ("target", "exec_region", "shed")):
                bad |= c != np.asarray(follow[f])[rows]
            return bad

        for rows in self.segments:
            ledger = used.copy()
            cols, gap, untaken = attempt(rows, ledger)
            bad = (np.zeros(len(rows), bool) if follow is None
                   else misses(rows, cols))
            # resolve near-ties of differing rows the other way, one at a
            # time, keeping each flip that brings the segment closer to the
            # followed decisions
            flip: dict[int, int] = {}
            tried: set = set()
            for _ in range(MAX_FLIPS):
                todo = [(r, c) for r, c in untaken.items()
                        if bad[r] and (r, c) not in tried]
                if not todo:
                    break
                r, c = todo[0]
                tried.add((r, c))
                trial_ledger = used.copy()
                trial = attempt(rows, trial_ledger, {**flip, r: c})
                trial_bad = misses(rows, trial[0])
                if trial_bad.sum() < bad.sum():
                    flip[r] = c
                    (cols, gap, untaken), ledger, bad = (
                        trial, trial_ledger, trial_bad)
            used = ledger
            tie_gap = max(tie_gap, gap)
            for f, c in zip(("target", "exec_region", "shed"), cols):
                out[f][rows] = c
        carbon = settle(self.fac, self.home, out["exec_region"],
                        out["exec_hour"], out["target"], self.table,
                        self.precision)
        return Decisions(carbon_g=carbon, tie_gap=tie_gap, **out)
