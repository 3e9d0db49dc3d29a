"""GreenScale routing benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up generates the cell's seeded streams, builds the deployment and the
program's router, and warms up every shape the window meets (the run's
first process in a checkout compiles; later ones load from the compile
cache in ``.jax_compile_cache``). The window then drives the cell's entry
point, alternating streams, for ``--seconds``. After the window every call
is checked against the plain reference. The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``checks``, each number
compared beside its limit. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of the
window.

Runs only on TPU: with no TPU, or fewer chips than the cell asks for, it
exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from harness import cells, check, program_trace, reference, traffic  # noqa: E402

COMPILE_CACHE = ROOT / ".jax_compile_cache"


class Observed:
    """What a per-layer metric reader may read about the window."""

    def __init__(self, cell, reduced, entry, programs_in_window, calls):
        self.cell = cell
        self.trace = reduced  # harness.trace.Reduced, or None
        self.drafts_per_step = list(entry.drafts)
        self.step_s = list(entry.step_s)
        self.programs_in_window = programs_in_window
        self.calls = calls
        #: the program's ``admit_rounds`` counter, read once the window has
        #: closed: one value a call (day plan) or a draft (online loop)
        self.admit_rounds = [int(x) for r in entry.admit_rounds
                             for x in np.ravel(r)]


def build(cell, seed: int, spans):
    """Set-up of a run, up to the warm-up: the grid tables, the policy's
    caps, the seeded streams and the cell's entry point over them."""
    g = cell.grid()
    n_regions = g["ci_hourly"].shape[0]
    entry_cls = cell.entry()
    caps = entry_cls.caps(cell, n_regions)
    tr = cell.traffic
    streams = [traffic.generate(tr, n_regions, traffic.stream_rng(seed, k))
               for k in range(int(tr["streams"]))]
    return g, caps, streams, entry_cls(cell, g, caps, streams, spans)


def compare(outputs: list[dict], problem) -> list[dict]:
    """Readings of every call's outputs against ``problem``; calls that
    returned identical outputs share one replay of the reference."""
    seen: dict[bytes, dict] = {}
    per_call = []
    for out in outputs:
        h = hashlib.blake2b(digest_size=16)
        for f in (*check.FIELDS, "carbon_g"):
            h.update(np.ascontiguousarray(out[f]).tobytes())
        key = h.digest()
        if key not in seen:
            seen[key] = check.readings(out, problem.solve(follow=out))
        per_call.append(seen[key])
    return per_call


def run_cell(cell, seed: int, seconds: float, traced: bool,
             t_start: float) -> dict:
    """One run of ``cell``: set-up, window, check."""
    import jax

    from harness import program
    from harness.clock import CompileClock
    from repro.serve import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    enable_compile_cache()
    clock = CompileClock()
    log = lambda msg: print(f"[bench {cell.name}] {msg}", file=sys.stderr,
                            flush=True)

    spans = program.Spans(traced)
    g, caps, streams, entry = build(cell, seed, spans)
    for k in range(len(streams)):  # warm-up: every shape the window meets
        entry.once(k)
    entry.step_s.clear()
    entry.drafts.clear()
    entry.admit_rounds.clear()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s} programs compiled={clock.compiles} "
        f"compile_s={clock.seconds} cache_hits={clock.cache_hits}")

    trace_dir = program_trace.TRACE_DIR  # this process's own
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    programs0 = clock.programs
    outputs, decisions, k = [], 0, 0
    t0 = time.perf_counter()
    with spans("window"):
        while True:
            n, out = entry.once(k % len(streams))
            outputs.append((k % len(streams), out))
            decisions += n
            k += 1
            if time.perf_counter() - t0 >= seconds:
                break
    wall = time.perf_counter() - t0
    programs_in_window = clock.programs - programs0
    reduced = None
    if traced:
        jax.profiler.stop_trace()
        from harness import trace as trace_mod
        reduced = trace_mod.Reduced(
            trace_mod.read_xplane(trace_mod.latest_xplane(str(trace_dir))),
            [f"{d.platform.upper()}:{d.id}"
             for d in program.devices_used(cell.chips)])
    peak = program.memory_peak_bytes(cell.chips)
    observed = Observed(cell, reduced, entry, programs_in_window, k)
    log(f"window_s={wall} calls={k} decisions={decisions} "
        f"programs_in_window={programs_in_window}")
    del entry
    gc.collect()

    # --- the check: every call against the reference of its stream -------
    t_ref = time.perf_counter()
    ref = cell.reference()
    per_call = []
    for s_idx, stream in enumerate(streams):
        per_call += compare([out for j, out in outputs if j == s_idx],
                            ref.problem(cell, g, caps, stream))
    worst = check.worst(per_call)
    correct, table = check.judge(worst, cell.limits)
    log(f"reference_s={time.perf_counter() - t_ref} "
        f"tie_gap={worst['tie_gap']!r} (widest near-tie followed, at most "
        f"{reference.TIE!r}; no limit of its own)")

    metrics = {}
    if not traced:
        # the online loop's rate is a metric of its own, with its own bound
        values = {"decisions_per_s": decisions / wall,
                  "serve_decisions_per_s": decisions / wall,
                  "setup_s": setup_s}
        if observed.step_s:
            values["step_p95_ms"] = float(
                np.percentile(observed.step_s, 95)) * 1e3
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"], cell.bench_dir)(observed)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct),
              "attempted": int(decisions),
              "failed": int(sum(r["rows_differ"] for r in per_call)),
              "metrics": metrics, "device": device}
    if reduced is not None and reduced.busiest is not None:
        device["busy_s"] = reduced.mean_busy_s()
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.idle_gaps()}
    result["checks"] = table
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    cell = cells.load(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START)
    print(json.dumps(result), flush=True)
    # repeat the comparison as the last lines of the error stream
    for name, v in result["checks"].items():
        print(f"check {name} value={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
