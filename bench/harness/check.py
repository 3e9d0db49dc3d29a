"""The comparison that decides ``correct``: every call the window made is
compared, field by field and row by row, with the plain reference of its
stream, replayed so that near-ties resolve as the call resolved them
(``reference.TIE``; float32 scores summed in another order differ in the
last bits, and where two candidates are that close either pick is right).

Numbers compared (each has its own limit in ``bench/limits/<cell>.json``):

``rows_differ``     rows of one call whose tier, executing region,
                    executing hour or shed flag differ from the reference,
                    worst call of the window.
``carbon_row_gap``  widest relative gap of a row's settled carbon over the
                    rows whose decisions agree, worst call.

``tie_gap``, the widest near-tie followed, is reported beside them; it is
bounded by ``reference.TIE`` by construction and has no limit of its own.
"""

from __future__ import annotations

import numpy as np

FIELDS = ("target", "exec_region", "exec_hour", "shed")
NUMBERS = ("rows_differ", "carbon_row_gap")
REPORTED = ("tie_gap",)


def readings(out: dict, ref) -> dict:
    """The numbers of one call's host outputs against the reference's
    decisions for the same stream (replayed following ``out``)."""
    differ = np.zeros(len(ref.target), bool)
    for f in FIELDS:
        differ |= np.asarray(out[f]) != np.asarray(getattr(ref, f))
    agree = ~differ
    got = np.asarray(out["carbon_g"], np.float64)
    want = ref.carbon_g.astype(np.float64)
    row_gap = (np.abs(got - want)[agree] / np.abs(want[agree])).max(
        initial=0.0)
    return dict(rows_differ=int(differ.sum()), tie_gap=float(ref.tie_gap),
                carbon_row_gap=float(row_gap))


def worst(per_call: list[dict]) -> dict:
    return {k: max(r[k] for r in per_call) for k in NUMBERS + REPORTED}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct iff no number passes
    its limit."""
    table = {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    return ok, table
