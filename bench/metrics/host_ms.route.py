"""Host time per routing call: milliseconds of each ``route_call``
span in which no chip runs an op (router entry, host preparation,
sharding, copy-back), averaged over the window's calls."""


def read(o):
    if o.trace is None or not o.trace.spans("route_call"):
        return None
    v = o.trace.host_in("route_call")
    return 1e3 * sum(v) / len(v)
