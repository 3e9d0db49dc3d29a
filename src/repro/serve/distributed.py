"""Device-sharded routing: the stream split over a 1-D data mesh.

The routing program itself is ``FleetRouter.route_rows``, the same per-row
core the one-chip program runs. What this module adds is what exists only
on a mesh:

  * Stream sharding (``shard_stream``): the host sorts the stream once by
    the policy's admission-segment order (``FleetRouter.stream_order``, the
    hint the one-chip program gets), pads it to a device multiple with
    unroutable dummies carrying the maximum segment key, and places it
    contiguously over the mesh axis — so every row on an earlier device
    precedes every local row in stream order, and each device's local rows
    stay segment-sorted.
  * The ``shard_map`` wrapper (``_build_sharded_route``): ``route_rows`` on
    each shard with the identity order and the mesh's ``axis_name``. Each
    spill round the policy's admission lifts its local within-cell ranks
    and per-cell totals to global ones with one ``all_gather`` and an
    exclusive cumsum over the device axis (``device_prefix_ranks``), so the
    replicated ``used`` ledger advances identically on every device and
    the (round, stream-order) admission priority is reconstructed exactly:
    int32 counting throughout, so decisions are bit-identical to the
    one-chip program at every device count (parity-tested at 1/2/4/8 fake
    devices). The per-row request buffers are donated.
  * The unsort (``_build_aggregate``): a second, small program on the mesh
    puts the per-row results back in stream order, keeps them split over
    the mesh axis, and sums the aggregates on the device with the one-chip
    program's ``aggregate_rows``. Nothing comes back to the host.

Per-row policies (``OraclePolicy``, ``LearnedPolicy``) shard with no
collectives; ``CapacityLimiter``'s sequential ``lax.scan`` cannot reconcile
and is refused with a pointer to its bit-identical ``PlacementPolicy``
replacement. ``FleetRouter(mesh=...)`` (or ``route_stream(..., mesh=...)``)
routes every stream here — ``serve_stream`` and the rolling re-planner
through the ``_route_arrays`` seam. ``enable_compile_cache`` wires jax's
persistent compilation cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.serve.forecast import slice_batch
from repro.serve.placement import PlacementState
from repro.serve.policy import CapacityLimiter
from repro.serve.router import REF_KEYS, aggregate_rows
from repro.serve.temporal import TemporalState

#: canonical name of the 1-D routing mesh axis (matches ``launch.mesh``'s
#: data axis so production meshes drop in unchanged)
DATA_AXIS = "data"


#: the compile cache's fixed home when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one directory inside the checkout (git-ignored), so every process
#: of every run finds the programs the last one compiled.
DEFAULT_COMPILE_CACHE = str(
    Path(__file__).resolve().parents[3] / ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache so the big admission jits
    compile once across process restarts.

    The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when it is set
    (no other directory is ever chosen then), and in
    ``DEFAULT_COMPILE_CACHE`` otherwise. The thresholds are dropped to zero:
    the routing programs are few and large, so caching everything is
    strictly a win. Returns the directory in use."""
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches the cache state (including "disabled: no dir configured")
    # at the FIRST compile in the process — which import-time jnp ops have
    # usually already triggered by the time this runs. Reset so the next
    # compile re-initializes against the directory configured above.
    compilation_cache.reset_cache()
    return cache_dir


def data_mesh(n_devices: int | None = None, axis: str = DATA_AXIS) -> Mesh:
    """A 1-D routing mesh over the first ``n_devices`` local devices (all of
    them by default) — the CPU-fake-device and single-host entry point; a
    production ``launch.mesh.make_mesh`` data submesh works identically."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if not 1 <= n <= len(devices):
        raise ValueError(f"n_devices must be in [1, {len(devices)}], got {n}")
    return Mesh(np.asarray(devices[:n]), (axis,))


def _check_mesh(mesh: Mesh) -> str:
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"the routing hot path shards over ONE data axis, got mesh axes "
            f"{mesh.axis_names} — pass a 1-D (sub)mesh, e.g. "
            f"Mesh(mesh.devices.reshape(-1), ('data',))")
    return mesh.axis_names[0]


def _build_sharded_route(fr, mesh: Mesh, axis: str):
    """The jitted shard_map routing program for one (router, mesh) pair:
    ``FleetRouter.route_rows`` on each shard, whose rows the host already
    put in admission-segment order (so the local order hint is the
    identity), reconciling admission across the mesh axis. It returns the
    per-row results only, in sorted and padded order (``_build_aggregate``'s
    program unsorts and sums them on the mesh). Replicated state pieces
    are returned device-tiled (leading axis ``D``) because
    ``check_vma=False`` — required for the admission while-loops — forbids
    unmentioned-axis out_specs."""
    n_regions = len(fr.regions)

    def _local(w, avail, region, hour, slack, ci_table, ci_fc,
               cap_scale, used0):
        n_loc = region.shape[0]
        ident = jnp.arange(n_loc, dtype=jnp.int32)
        state = fr.policy.initial_state(n_regions, n_loc)
        rows, new_state = fr.route_rows(
            w, avail, region, hour, ci_table, ci_fc, state, ident, ident,
            slack, cap_scale, used0, axis_name=axis)
        # replicated state pieces, device-tiled for the out_spec (the
        # aggregation reads shard 0; parity across shards is exactly what
        # the reconciliation guarantees and the invariance suite pins)
        tiled = {k: getattr(new_state, k, None)
                 for k in ("counts", "shed_pair", "admit_rounds")}
        return rows, jax.tree.map(lambda x: x[None], tiled)

    row_spec = P(axis)
    in_specs = (row_spec, row_spec, row_spec, row_spec, row_spec,
                P(), P(), P(), P())
    out_specs = (
        dict.fromkeys(("target", "carbon", "feas", "exec_region", "shed",
                       "exec_hour", "defer", *REF_KEYS), row_spec),
        dict.fromkeys(("counts", "shed_pair", "admit_rounds"), row_spec),
    )
    sharded = jax.shard_map(_local, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    # donate the big per-row request buffers (workload columns, avail,
    # region/hour/slack tags): routing consumes the stream in place — at
    # 10M requests that is the difference between one and two resident
    # copies of every column
    return jax.jit(sharded, donate_argnums=(0, 1, 2, 3, 4))


def _program_for(fr, mesh: Mesh, axis: str, sig):
    """The ``(route, aggregate)`` pair of compiled programs per (router,
    mesh, optional-arg signature) — rebuilding the shard_map wrapper per
    call would discard jit's compile cache. ``sig`` captures which optional
    args are None (they change the traced pytree structure). The
    aggregation is a program of its own so that it adds nothing to the
    admission program's compile or memory."""
    cache = fr.__dict__.setdefault("_sharded_programs", {})
    key = (mesh, axis, sig)
    if key not in cache:
        cache[key] = (_build_sharded_route(fr, mesh, axis),
                      _build_aggregate(fr, mesh, axis))
    return cache[key]


def shard_stream(fr, batch, region_np, hour_np, mesh, slack_np=None):
    """Host half of the sharded call: sort the stream by the policy's
    admission-segment key, pad it to a device multiple with unroutable
    max-key dummies, and place it contiguously over the mesh axis. Returns
    ``(rows, inv_np)``: the per-row program inputs (workload, avail,
    region, hour, slack — each a ``NamedSharding`` over the mesh) and the
    inverse permutation that puts per-row outputs back in stream order."""
    axis = _check_mesh(mesh)
    n_devices = int(mesh.devices.size)
    n = len(batch)
    n_regions = len(fr.regions)
    region_np = np.asarray(region_np, np.int32)
    hour_np = np.asarray(hour_np, np.int32)
    # a per-row policy has no segments: it keeps stream order
    order_np, inv_np = (fr.stream_order(region_np, hour_np)
                        or (np.arange(n, dtype=np.int32),) * 2)

    # --- pad to a device multiple with unroutable max-key dummies ---------
    # pads sit at the END of the sorted stream with the maximum segment key
    # (last window, last region), are never routable (all-False avail), and
    # consume no capacity — local segment-sortedness and global stream
    # order are both preserved
    n_pad = max(-(-n // n_devices) * n_devices, n_devices)
    batch_s = slice_batch(batch, order_np, n_pad)
    pad = lambda a, fill: np.concatenate(
        [a[order_np], np.full((n_pad - n,), fill, a.dtype)])
    slack_base = np.asarray(
        batch.slack_h if slack_np is None else slack_np, np.int32)
    shard = NamedSharding(mesh, P(axis))
    rows = jax.device_put(
        (batch_s.workload(fr.cfg), batch_s.avail,
         pad(region_np, n_regions - 1), pad(hour_np, fr.grid.horizon_h - 1),
         pad(slack_base, 0)), shard)
    return rows, inv_np


def _row_sharding(mesh: Mesh, axis: str, n: int) -> NamedSharding:
    """Sharding of an (n,) per-row result in stream order: split over the
    mesh axis, or replicated over the mesh where ``n`` is not a device
    multiple (a jax array cannot split a dimension unevenly; no chip is
    singled out either way)."""
    split = n % int(mesh.devices.size) == 0
    return NamedSharding(mesh, P(axis) if split else P())


def _build_aggregate(fr, mesh: Mesh, axis: str):
    """The jitted aggregation program that follows the shard_map program:
    unsort the per-row outputs into stream order (one gather each by the
    inverse permutation, which drops the pads), keep them on the mesh, and
    sum the ``FleetRouteResult`` aggregates on the device
    (``aggregate_rows``), replicated. The permutation-invariant reference
    sums (``refs``) are taken in sorted order, pads masked. The per-row
    columns it unsorts are donated: their outputs reuse their memory."""
    n_regions = len(fr.regions)
    diag = bool(getattr(fr.policy, "_diag_only", False))
    replicated = NamedSharding(mesh, P())

    def _aggregate(per_row, refs, tiled, inv, region):
        n = inv.shape[0]
        row_sharding = _row_sharding(mesh, axis, n)
        on_rows = lambda a: jax.lax.with_sharding_constraint(a, row_sharding)
        one = lambda a: jax.lax.with_sharding_constraint(a, replicated)
        with jax.named_scope("aggregate"):
            rows = {k: None if a is None else on_rows(a.at[inv].get(
                mode="promise_in_bounds", unique_indices=True))
                for k, a in per_row.items()}
            valid = jnp.arange(per_row["target"].shape[0]) < n
            res = aggregate_rows(rows, region, n_regions, refs={
                k: jnp.where(valid, a, 0).sum() for k, a in refs.items()})
            # the aggregates are replicated; the per-row columns keep
            # their row sharding
            res = jax.tree.map(lambda a: a if a.shape == (n,) else one(a),
                               res)
            if tiled["counts"] is None:  # stateless per-row policy
                return res, ()
            # replicated state pieces: shard 0 of the device-tiled outputs
            first = {k: one(v[0]) for k, v in tiled.items()}
            if rows["exec_hour"] is not None:
                return res, TemporalState(
                    counts=first["counts"], shed=rows["shed"],
                    exec_region=res.exec_region,
                    shed_pair=first["shed_pair"],
                    exec_hour=rows["exec_hour"], defer_hours=rows["defer"],
                    admit_rounds=first["admit_rounds"])
            return res, PlacementState(
                counts=first["counts"], shed=rows["shed"],
                exec_region=None if diag else res.exec_region,
                shed_pair=first["shed_pair"],
                admit_rounds=first["admit_rounds"])

    return jax.jit(_aggregate, donate_argnums=(0,))


def route_arrays_sharded(fr, batch, region_np, hour_np, mesh, *,
                         ci_fc=None, cap_scale=None, used0=None,
                         slack_np=None):
    """Sharded twin of ``FleetRouter._route_arrays`` — same prepared-array
    contract, same ``(FleetRouteResult, state)`` return, decisions
    bit-identical to the single-device program at any device count.

    Host side: ``shard_stream`` sorts, pads and shards the stream, and the
    inverse permutation and the home regions are uploaded in stream order;
    then the shard_map program and the aggregation program are dispatched
    one after the other. Nothing comes back to the host: the per-row
    results stay on the mesh in stream order, the aggregates are summed on
    the device, and the call returns without blocking, as the
    single-device program does. Host spans: ``gs.route.prepare``,
    ``gs.route.dispatch`` and ``gs.route.aggregate`` (the dispatch of the
    aggregation program)."""
    if isinstance(fr.policy, CapacityLimiter):
        raise NotImplementedError(
            "CapacityLimiter's lax.scan admission walks windows "
            "sequentially per device and cannot reconcile caps across a "
            "sharded stream — use PlacementPolicy (identity adjacency "
            "reproduces CapacityLimiter bit-for-bit) on the sharded path")
    axis = _check_mesh(mesh)
    region_np = np.asarray(region_np, np.int32)
    with TraceAnnotation("gs.route.prepare"):
        rows, inv_np = shard_stream(fr, batch, region_np, hour_np, mesh,
                                    slack_np)
        inv, region = jax.device_put(
            (inv_np, region_np), _row_sharding(mesh, axis, len(inv_np)))

    # --- run the sharded program ------------------------------------------
    with TraceAnnotation("gs.route.dispatch"):
        sig = (ci_fc is None, cap_scale is None, used0 is None)
        program, aggregate = _program_for(fr, mesh, axis, sig)
        per_row, tiled = program(*rows, *fr.ci_tables(ci_fc), cap_scale,
                                 used0)
    with TraceAnnotation("gs.route.aggregate"):
        refs = {k: per_row.pop(k) for k in REF_KEYS}
        return aggregate(per_row, refs, tiled, inv, region)
