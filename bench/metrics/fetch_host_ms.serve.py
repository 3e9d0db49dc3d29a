"""Host time of copying decisions back per serve step: milliseconds of the
program's ``gs.serve.fetch`` spans (target, shed, executing region and
hour to the host) in which no chip runs an op, summed over the window and
divided by its ``serve_step`` spans, as ``host_ms.serve`` is."""

from harness import program_trace


def read(o):
    return program_trace.host_ms_per(o, "gs.serve.fetch", "serve_step")
