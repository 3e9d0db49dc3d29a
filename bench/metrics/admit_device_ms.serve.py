"""Device time of capacity admission per draft in the online loop:
milliseconds in which the busiest chip runs an op of the program's
``admit`` scope inside the window's ``serve_step`` spans, divided by the
drafts those steps routed."""

from harness import program_trace


def read(o):
    return program_trace.scope_ms_per_draft(o, "admit")
