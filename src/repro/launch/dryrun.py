"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is how the distribution config is proven coherent without hardware:
``jax.jit(step).lower(...).compile()`` must succeed on 512 virtual host
devices for the production meshes, and the compiled artifact yields the
roofline terms (FLOPs / bytes from cost_analysis, collective bytes from the
optimized HLO text).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
"""

# The VERY FIRST lines — before ANY other import (jax locks the platform and
# the device count on first init). The dry run compiles for 512 fake CPU
# devices by design, so it pins the CPU platform: on a machine with an
# accelerator it must neither claim nor land on the chip.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_IDS,
    SHAPE_IDS,
    batch_specs,
    cell_supported,
    decode_specs,
    get_config,
    get_shape,
    param_specs,
)
from repro.configs.base import ShapeKind  # noqa: E402
from repro.launch.mesh import data_axes as mesh_data_axes  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import decode_step, prefill  # noqa: E402
from repro.sharding import (  # noqa: E402
    batch_sharding,
    decode_state_sharding,
    param_shardings,
)
from repro.train.optimizer import adamw, warmup_cosine  # noqa: E402
from repro.train.train_step import make_train_step  # noqa: E402


# ---------------------------------------------------------------------------
# HLO collective-bytes parser (cost_analysis has no collective term)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")
_DEF_RE = re.compile(
    r"^\s*(%[\w.\-]+)\s*=\s*(\([^=]*?\)|(?:" + "|".join(_DTYPE_BYTES)
    + r")\[[\d,]*\][^\s]*)\s+([\w\-]+)\(")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device bytes moved by every collective op in optimized HLO text.

    The SPMD module is per-device, so shapes here are local. Operands are
    %name references — a first pass builds the name -> result-type symbol
    table; collective bytes are max(result, operand) per op (all-gather's
    wire volume shows in its result, reduce-scatter's in its operand).
    Async ``*-start`` forms count once; ``*-done`` are skipped. NOTE: ops
    inside ``while`` bodies (layer scans) appear once — callers scale by
    trip count via the two-point probe (see ``measure_cell``).
    """
    sizes: dict[str, int] = {}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _DEF_RE.match(line)
        if m:
            sizes[m.group(1)] = _shape_bytes(m.group(2))

    out = {k: 0 for k in _COLLECTIVE_KINDS}
    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.groups()
        base = op.removesuffix("-start")
        if op.endswith("-done") or base not in _COLLECTIVE_KINDS:
            continue
        args = line[line.index(op + "(") + len(op) + 1:]
        depth, end = 1, 0
        for i, ch in enumerate(args):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end = i
                break
        operand_bytes = sum(sizes.get(a, 0) for a in
                            re.findall(r"%[\w.\-]+", args[:end]))
        out[base] += max(_shape_bytes(type_str), operand_bytes)
    out["total"] = sum(out[k] for k in _COLLECTIVE_KINDS)
    return out


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DryRunResult:
    arch: str
    shape: str
    mesh: str
    kind: str
    ok: bool
    error: str = ""
    flops: float = 0.0
    hlo_bytes: float = 0.0
    peak_mem_per_device: float = 0.0
    arg_bytes_per_device: float = 0.0
    collectives: dict = dataclasses.field(default_factory=dict)
    compile_s: float = 0.0

    def row(self) -> str:
        if not self.ok:
            return (f"{self.arch:22s} {self.shape:12s} {self.mesh:10s} "
                    f"FAIL {self.error[:90]}")
        return (f"{self.arch:22s} {self.shape:12s} {self.mesh:10s} "
                f"flops={self.flops:.3e} bytes={self.hlo_bytes:.3e} "
                f"coll={self.collectives.get('total', 0):.3e} "
                f"peak/dev={self.peak_mem_per_device / 2**30:.2f}GiB "
                f"compile={self.compile_s:.0f}s")


def _train_batch_shardings(mesh, batch):
    return batch_sharding(mesh, batch)


def lower_cell(arch: str, shape_id: str, mesh, *,
               remat: str = "dots", microbatches: int = 1,
               compression: str = "none",
               seq_shard: bool = True,
               scan_unroll: bool = False,
               grad_dtype: str | None = None,
               extra: dict | None = None) -> DryRunResult:
    """Lower + compile one (arch x shape) cell on ``mesh``; extract terms."""
    cfg = get_config(arch)
    if extra:
        cfg = dataclasses.replace(cfg, **extra)
    shape = get_shape(shape_id)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    kind = "serve" if shape.lowers_serve_step else ("prefill" if
                                                    shape.kind == ShapeKind.PREFILL
                                                    else "train")
    res = DryRunResult(arch=arch, shape=shape_id, mesh=mesh_name, kind=kind,
                       ok=False)
    ok, why = cell_supported(cfg, shape)
    if not ok:
        res.error = "SKIP: " + why
        return res

    daxes = mesh_data_axes(mesh)
    t0 = time.time()
    try:
        params = param_specs(cfg, shape)
        p_shard = param_shardings(mesh, params)

        if kind == "train":
            from repro.train.train_step import TrainState

            # training shards weights + moments ZeRO/FSDP-style (rules.py)
            p_shard_train = param_shardings(mesh, params, fsdp=True)
            opt = adamw(warmup_cosine(3e-4, 2000, 100000))
            opt_state = jax.eval_shape(opt.init, params)
            ef = ef_shard = None
            if compression != "none":
                from repro.train.compression import dp_size
                n_dp = dp_size(mesh, daxes)
                ef = jax.tree.map(
                    lambda p: jax.ShapeDtypeStruct((n_dp,) + tuple(p.shape),
                                                   jnp.float32), params)
                ef_shard = jax.tree.map(
                    lambda e, ps: NamedSharding(mesh, P(daxes, *ps.spec)),
                    ef, p_shard_train)
            state = TrainState(params=params, opt=opt_state, ef=ef)
            state_shard = TrainState(
                params=p_shard_train,
                opt=type(opt_state)(mu=p_shard_train, nu=p_shard_train,
                                    count=NamedSharding(mesh, P())),
                ef=ef_shard)
            batch = batch_specs(cfg, shape)
            b_shard = _train_batch_shardings(mesh, batch)
            act_spec = (P(daxes, "model", None) if seq_shard else None)
            step = make_train_step(cfg, opt, mesh=mesh, remat=remat,
                                   microbatches=microbatches,
                                   compression=compression,
                                   act_spec=act_spec,
                                   scan_unroll=scan_unroll,
                                   grad_dtype=grad_dtype)
            with mesh:
                lowered = jax.jit(
                    step,
                    in_shardings=(state_shard, b_shard),
                    out_shardings=(state_shard, None),
                    donate_argnums=(0,),
                ).lower(state, batch)
        elif kind == "prefill":
            batch = batch_specs(cfg, shape)
            batch.pop("labels")
            b_shard = _train_batch_shardings(mesh, batch)

            def prefill_step(params, batch):
                return prefill(
                    params, cfg, batch["tokens"], max_seq=shape.seq_len,
                    positions=batch.get("positions"),
                    patch_embeds=batch.get("patch_embeds"),
                    encoder_frames=batch.get("encoder_frames"),
                    scan_unroll=scan_unroll)

            with mesh:
                lowered = jax.jit(
                    prefill_step, in_shardings=(p_shard, b_shard),
                ).lower(params, batch)
        else:  # serve (decode / long-context decode)
            from repro.sharding.rules import enforce_divisible
            state, tokens = decode_specs(cfg, shape)
            s_shard = decode_state_sharding(mesh, state)
            t_shard = NamedSharding(
                mesh, enforce_divisible(mesh, P(daxes, None),
                                        tuple(tokens.shape)))

            def serve_step(params, state, tokens):
                return decode_step(params, cfg, state, tokens,
                                   scan_unroll=scan_unroll)

            with mesh:
                # NOTE: donating the state (in-place cache) was tried and
                # REFUTED in §Perf round 1: this XLA version replicates the
                # donated cache across the model axis (360 GiB/dev).
                lowered = jax.jit(
                    serve_step, in_shardings=(p_shard, s_shard, t_shard),
                ).lower(params, state, tokens)

        compiled = lowered.compile()
        res.compile_s = time.time() - t0
        cost = compiled.cost_analysis()
        res.flops = float(cost.get("flops", 0.0))
        res.hlo_bytes = float(cost.get("bytes accessed", 0.0))
        mem = compiled.memory_analysis()
        res.peak_mem_per_device = float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "generated_code_size_in_bytes", 0))
        res.arg_bytes_per_device = float(
            getattr(mem, "argument_size_in_bytes", 0))
        res.collectives = collective_bytes(compiled.as_text())
        res.ok = True
    except Exception as e:  # noqa: BLE001 — report, don't crash the matrix
        res.error = f"{type(e).__name__}: {e}"
        res.compile_s = time.time() - t0
    return res


def measure_cell(arch: str, shape_id: str, mesh, *,
                 remat: str = "minimal", microbatches: int = 1,
                 compression: str = "none",
                 seq_shard: bool = True,
                 grad_dtype: str | None = None,
                 extra: dict | None = None) -> DryRunResult:
    """lower_cell + exact cost extrapolation over the layer scan.

    XLA's cost_analysis counts ``while`` bodies once regardless of trip
    count, so the layer scan hides (n_super - 1)/n_super of the FLOPs.
    Fix: lower two probe configs with n_super=1 and n_super=2 (everything
    else identical — probes reuse the full config's layer pattern). Costs
    are affine in n_super, so

        per_block = c(2) - c(1);   fixed = c(1) - per_block
        total     = fixed + per_block * n_super_full

    exactly recovers FLOPs / bytes / collective bytes of the full model.
    The full config is still compiled for memory analysis + the pass/fail
    of the cell itself. Microbatch scans scale the same way (x
    ``microbatches``).
    """
    from repro.models.transformer import block_period

    cfg = get_config(arch)
    period = block_period(cfg)
    ns_full = cfg.n_layers // period

    res = lower_cell(arch, shape_id, mesh, remat=remat,
                     microbatches=microbatches, compression=compression,
                     seq_shard=seq_shard, grad_dtype=grad_dtype, extra=extra)
    if not res.ok or ns_full == 1:
        return res

    probes = []
    for ns in (1, 2):
        e = dict(extra or {})
        e["n_layers"] = period * ns
        r = lower_cell(arch, shape_id, mesh, remat=remat,
                       microbatches=microbatches, compression=compression,
                       seq_shard=seq_shard, scan_unroll=True,
                       grad_dtype=grad_dtype, extra=e)
        if not r.ok:
            res.error = f"probe ns={ns} failed: {r.error}"
            return res
        probes.append(r)

    c1, c2 = probes

    def extrap(a1: float, a2: float) -> float:
        per_block = a2 - a1
        fixed = a1 - per_block
        return fixed + per_block * ns_full

    res.flops = extrap(c1.flops, c2.flops)
    res.hlo_bytes = extrap(c1.hlo_bytes, c2.hlo_bytes)
    res.collectives = {
        k: max(0.0, extrap(float(c1.collectives.get(k, 0)),
                           float(c2.collectives.get(k, 0))))
        for k in set(c1.collectives) | set(c2.collectives)}
    if microbatches > 1:
        # the microbatch scan body is also counted once
        for f in ("flops", "hlo_bytes"):
            setattr(res, f, getattr(res, f) * microbatches)
        res.collectives = {k: v * microbatches
                           for k, v in res.collectives.items()}
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--remat", default="minimal")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default="none")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the two-point cost extrapolation probes")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(multi_pod=False),
                  make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    cells = []
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPE_IDS)
    if not (args.all or args.arch):
        ap.error("pass --arch/--shape or --all")

    results = []
    fn = lower_cell if args.no_probes else measure_cell
    for mesh in meshes:
        for arch in archs:
            for shape in shapes:
                r = fn(arch, shape, mesh, remat=args.remat,
                       microbatches=args.microbatches,
                       compression=args.compression,
                       seq_shard=not args.no_seq_shard)
                print(r.row(), flush=True)
                results.append(dataclasses.asdict(r))

    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    n_fail = sum(1 for r in results
                 if not r["ok"] and not r["error"].startswith("SKIP"))
    print(f"\n{len(results)} cells, {n_fail} failures")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
