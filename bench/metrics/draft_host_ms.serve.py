"""Host time of queue order and drafting per serve step: milliseconds of
the program's ``gs.serve.draft`` spans (ready set, earliest-deadline
sort, draft formation) in which no chip runs an op, summed over the window
and divided by its ``serve_step`` spans, as ``host_ms.serve`` is."""

from harness import program_trace


def read(o):
    return program_trace.host_ms_per(o, "gs.serve.draft", "serve_step")
