"""Device time per draft in the online loop: milliseconds in which the
busiest chip runs an op inside the window's ``serve_step`` spans,
divided by the drafts those steps routed."""


def read(o):
    if o.trace is None or o.trace.busiest is None or not o.drafts_per_step:
        return None
    v = o.trace.device_in("serve_step")
    drafts = sum(o.drafts_per_step)
    return 1e3 * sum(v) / drafts if v and drafts else None
