"""Idle share of the busiest chip over the online loop's traced window:
100 x (1 - union of its op intervals / window)."""


def read(o):
    if o.trace is None or o.trace.busiest is None:
        return None
    return 100.0 * (1.0 - o.trace.busy_s(o.trace.busiest) / o.trace.window_s)
