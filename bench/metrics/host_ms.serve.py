"""Host time per serve step: milliseconds of each ``serve_step`` span
in which no chip runs an op (ready set, drafting, commit bookkeeping,
uploads), averaged over the window's steps."""


def read(o):
    if o.trace is None or not o.trace.spans("serve_step"):
        return None
    v = o.trace.host_in("serve_step")
    return 1e3 * sum(v) / len(v)
