"""Readings that the limits in ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--control-seeds 1 2 3]

For each seed, in one process and without a measured window: the cell's
streams through the program's timed entry point (the same calls a run
makes), compared with the plain reference exactly as a run compares them
(the lower readings); and, for the control seeds, the reference computed
one precision step lower (float32 products from bfloat16 halves) put in
the program's place (the upper readings). One JSON line per seed and
side on standard output. Runs on the cell's chips, like a run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402
from harness import cells, check, traffic  # noqa: E402


def as_output(dec) -> dict:
    """A reference's decisions in the shape of a program call's output."""
    return dict(target=dec.target, exec_region=dec.exec_region,
                exec_hour=dec.exec_hour, shed=dec.shed,
                carbon_g=dec.carbon_g)


def calibrate(cell, seeds, control_seeds):
    """Yield one reading dict per (seed, side)."""
    from harness import program
    from repro.serve import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.COMPILE_CACHE)
    enable_compile_cache()
    g = cell.grid()
    n_regions = g["ci_hourly"].shape[0]
    entry_cls = cell.entry()
    caps = entry_cls.caps(cell, n_regions)
    ref = cell.reference()
    tr = cell.traffic
    for seed in sorted(set(seeds) | set(control_seeds)):
        streams = [traffic.generate(tr, n_regions,
                                    traffic.stream_rng(seed, k))
                   for k in range(int(tr["streams"]))]
        probs = [ref.problem(cell, g, caps, s) for s in streams]
        if seed in seeds:
            entry = entry_cls(cell, g, caps, streams, program.Spans(False))
            outs = [entry.once(k)[1] for k in range(len(streams))]
            del entry
            yield dict(seed=seed, side="program", **check.worst(
                [run.compare([o], p)[0] for o, p in zip(outs, probs)]),
                shed=[int(o["shed"].sum()) for o in outs],
                spilled=[int(((o["exec_region"] != s.region)
                              & ~o["shed"]).sum())
                         for o, s in zip(outs, streams)])
        if seed in control_seeds:
            ctrl = [as_output(ref.problem(
                cell, g, caps, s, precision="high").solve())
                for s in streams]
            yield dict(seed=seed, side="control", **check.worst(
                [run.compare([c], p)[0] for c, p in zip(ctrl, probs)]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    for rec in calibrate(cell, args.seeds, args.control_seeds):
        print(json.dumps(dict(workload=cell.name, **rec)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
