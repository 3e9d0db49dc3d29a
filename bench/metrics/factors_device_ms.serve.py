"""Device time of the Table-1 factor build per draft in the online loop:
milliseconds in which the busiest chip runs an op of the program's
``factors`` scope inside the window's ``serve_step`` spans, divided by the
drafts those steps routed."""

from harness import program_trace


def read(o):
    return program_trace.scope_ms_per_draft(o, "factors")
