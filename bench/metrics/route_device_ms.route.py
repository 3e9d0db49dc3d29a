"""Device time per routing call: milliseconds in which the busiest chip
runs an op inside each ``route_call`` span, averaged over calls."""


def read(o):
    if o.trace is None or o.trace.busiest is None:
        return None
    v = o.trace.device_in("route_call")
    return 1e3 * sum(v) / len(v) if v else None
