"""Host time of commit bookkeeping per serve step: milliseconds of the
program's ``gs.serve.commit`` spans (queue transitions, the committed-cell
ledger, the next draft's ``used0`` upload) in which no chip runs an op,
summed over the window and divided by its ``serve_step`` spans, as
``host_ms.serve`` is."""

from harness import program_trace


def read(o):
    return program_trace.host_ms_per(o, "gs.serve.commit", "serve_step")
