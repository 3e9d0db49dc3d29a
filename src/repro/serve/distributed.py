"""Device-sharded routing hot path: ``shard_map`` admission over a data mesh.

One device owning the whole stream caps ``FleetRouter`` at ~0.5M req/s; this
module shards the columnar request stream contiguously across a 1-D mesh
axis and runs the existing segment-rank admission *locally per shard*, with
the per-cell capacity ledger reconciled across devices between spill rounds:

  * The stream is sorted ONCE on the host by the policy's admission segment
    key (exactly the ``stream_order_key`` hint the single-device path
    already computes), padded to a device multiple with structurally
    unroutable dummies carrying the maximum segment key, and sharded
    contiguously — so every row on an earlier device precedes every local
    row in stream order, and each device's local rows stay segment-sorted.
  * Each spill round, every device computes its local within-cell arrival
    ranks and per-cell totals (``windowed_segment_ranks``, unchanged); one
    ``all_gather`` of the totals plus an exclusive cumsum over the device
    axis lifts them to GLOBAL ranks/totals (``device_prefix_ranks``), so the
    replicated ``used`` ledger advances identically on every device and the
    (round, stream-order) admission priority is reconstructed EXACTLY — all
    int32 counting arithmetic, so sharded admission is bit-identical to the
    single-device program for ``PlacementPolicy`` and ``TemporalPolicy``
    (parity-tested at 1/2/4/8 fake devices).
  * The big per-row request buffers are donated to the jitted program
    (``donate_argnums``) — routing consumes them in place instead of
    holding a second copy of a 10M-request stream — and
    ``enable_compile_cache`` wires jax's persistent compilation cache so
    the large admission jits compile once across process restarts.

Nothing comes back to the host: a second, small jitted program on the mesh
unsorts the per-row outputs into stream order (they stay split over the
mesh axis, so a caller copies them back from every chip at once) and sums
the aggregates (carbon sums, counts, shed/spill/defer counts) on the
device. Decisions are bit-identical at every device count; the float sums
agree with the single-device program's to float rounding. Per-row
policies (``OraclePolicy``, ``LearnedPolicy``) shard trivially (no
collectives); ``CapacityLimiter``'s sequential ``lax.scan`` cannot
reconcile and is refused with a pointer to its bit-identical
``PlacementPolicy`` replacement.

Surface: ``FleetRouter(mesh=...)`` (or ``route_stream(..., mesh=...)``)
routes every stream through this module — ``serve_stream`` and the rolling
re-planner ride it automatically through the ``_route_arrays`` seam.
Measured on ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` CPU
meshes; pinned in the device-scaling section of
``benchmarks/policy_throughput.py``. ``chip_smoke.py --chips 4`` checks the
same parity on a 4-chip TPU host.
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

from repro.core import carbon_model
from repro.core.carbon_model import Environment
from repro.serve.forecast import slice_batch
from repro.serve.placement import PlacementState
from repro.serve.policy import CapacityLimiter
from repro.serve.router import FleetRouteResult, pair_counts_and_deferral
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
    """The jitted shard_map routing program for one (router, mesh) pair —
    mirrors ``FleetRouter._fleet_route`` but returns PER-ROW arrays only,
    in sorted and padded order (``_build_aggregate``'s program unsorts and
    sums them on the mesh). Replicated outputs are returned device-tiled
    (leading axis ``D``) because ``check_vma=False`` — required for the
    admission while-loops — forbids unmentioned-axis out_specs."""
    policy = fr.policy
    infra = fr._infra
    interference = fr._interference
    net_slowdown = fr._net_slowdown
    rtt_s = fr.grid.rtt_s
    n_regions = len(fr.regions)
    use_factors = bool(getattr(policy, "wants_factors", False))
    split = fr.grid.ci_forecast is not None

    def _local(w, avail, region, hour, slack, ci_table, ci_fc,
               cap_scale, used0):
        n_loc = region.shape[0]
        # the host pre-sorted the stream into admission-segment order and
        # sharded it contiguously, so the local order hint is the identity
        ident = jnp.arange(n_loc, dtype=jnp.int32)
        state = policy.initial_state(n_regions, n_loc)
        env = Environment(ci=ci_fc[region, hour],
                          interference=interference,
                          net_slowdown=net_slowdown)
        if use_factors:
            factors = carbon_model.energy_factors_batch(
                w, infra, interference, net_slowdown)
            out = carbon_model.route_many_from_factors(
                factors, w, env.ci, avail)
        else:
            factors = None
            out = carbon_model.route_many_envs(w, infra, env, avail)
        take2 = lambda a, t: jnp.take_along_axis(a, t[:, None], axis=1)[:, 0]
        if not split:
            take_act = lambda t: take2(out.total_cf, t)
        elif factors is not None:
            cf_act = carbon_model.total_cf_from_factors(
                factors, ci_table[region, hour])
            take_act = lambda t: take2(cf_act, t)
        else:
            out_act = carbon_model.route_many_envs(
                w, infra,
                Environment(ci=ci_table[region, hour],
                            interference=interference,
                            net_slowdown=net_slowdown), avail)
            take_act = lambda t: take2(out_act.total_cf, t)
        targets, new_state = policy.decide(
            w, env, avail, state, region=region, hour=hour, outputs=out,
            order=ident, inv_order=ident, slack=slack, factors=factors,
            fc_table=ci_fc, cap_scale=cap_scale, used0=used0,
            axis_name=axis)
        with jax.named_scope("account"):
            shed = getattr(new_state, "shed", None)
            exec_region = getattr(new_state, "exec_region", None)
            exec_hour = getattr(new_state, "exec_hour", None)
            if exec_region is None and exec_hour is None:
                exec_region = region
                carbon = take_act(targets)
                feas = take2(out.ok, targets)
            elif factors is not None:
                er = region if exec_region is None else exec_region
                eh = hour if exec_hour is None else exec_hour
                exec_region = er
                ci_exec = jnp.concatenate(
                    [ci_table[region, eh][:, :2],
                     ci_table[er, eh][:, 2:]], axis=1)
                cf_exec = carbon_model.total_cf_from_factors(factors, ci_exec)
                ok_exec = carbon_model.qos_feasible_from_factors(
                    factors, w, rtt_s[region, er]) & avail
                carbon = take2(cf_exec, targets)
                feas = take2(ok_exec, targets)
            else:
                ci_exec = jnp.concatenate(
                    [ci_table[region, hour][:, :2],
                     ci_table[exec_region, hour][:, 2:]], axis=1)
                out_exec = carbon_model.route_many_envs(
                    w, infra,
                    Environment(ci=ci_exec, interference=interference,
                                net_slowdown=net_slowdown), avail)
                moved = exec_region != region
                if shed is not None:
                    moved = moved & ~shed
                carbon = jnp.where(moved, take2(out_exec.total_cf, targets),
                                   take_act(targets))
                feas = jnp.where(moved, take2(out_exec.ok, targets),
                                 take2(out.ok, targets))
            per_row = dict(
                target=targets,
                carbon=carbon,
                feas=feas,
                exec_region=exec_region,
                shed=shed,
                exec_hour=getattr(new_state, "exec_hour", None),
                defer=getattr(new_state, "defer_hours", None),
                ref_latency=take_act(out.target_latency),
                ref_energy=take_act(out.target_energy),
                ref_oracle=take_act(out.target),
            )
        # replicated state pieces, device-tiled for the out_spec (the
        # aggregation reads shard 0; parity across shards is exactly what
        # the reconciliation guarantees and the invariance suite pins)
        tiled = dict(
            counts=getattr(new_state, "counts", None),
            shed_pair=getattr(new_state, "shed_pair", None),
            admit_rounds=getattr(new_state, "admit_rounds", None),
        )
        return per_row, jax.tree.map(lambda x: x[None], tiled)

    row_spec = P(axis)
    in_specs = (row_spec, row_spec, row_spec, row_spec, row_spec,
                P(), P(), P(), P())
    out_specs = (
        dict.fromkeys(("target", "carbon", "feas", "exec_region", "shed",
                       "exec_hour", "defer", "ref_latency", "ref_energy",
                       "ref_oracle"), row_spec),
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
    policy = fr.policy
    n_devices = int(mesh.devices.size)
    n = len(batch)
    n_regions = len(fr.regions)
    region_np = np.asarray(region_np, np.int32)
    hour_np = np.asarray(hour_np, np.int32)

    # --- host pre-sort into admission-segment order -----------------------
    order_key = getattr(policy, "stream_order_key", None)
    if order_key is None:  # per-row policy: no segments, keep stream order
        order_np = np.arange(n, dtype=np.int32)
    else:
        n_win = getattr(policy, "n_windows", None) or fr._horizon_h
        win_np = hour_np % n_win
        key = (win_np * n_regions + region_np
               if order_key == "window_region" else win_np)
        order_np = np.argsort(key, kind="stable").astype(np.int32)
    inv_np = np.empty_like(order_np)
    inv_np[order_np] = np.arange(n, dtype=np.int32)

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
         pad(region_np, n_regions - 1), pad(hour_np, fr._horizon_h - 1),
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
    sum every ``FleetRouteResult`` aggregate on the device with the
    formulas of the single-device program. The permutation-invariant
    reference sums (``refs``) are taken in sorted order, pads masked. The
    per-row columns it unsorts are donated: their outputs reuse their
    memory."""
    policy = fr.policy
    n_regions = len(fr.regions)
    diag = bool(getattr(policy, "_diag_only", False))
    replicated = NamedSharding(mesh, P())

    def _aggregate(per_row, refs, tiled, inv, region):
        n = inv.shape[0]
        rows = _row_sharding(mesh, axis, n)
        on_rows = lambda a: jax.lax.with_sharding_constraint(a, rows)
        one = lambda a: jax.lax.with_sharding_constraint(a, replicated)
        with jax.named_scope("aggregate"):
            row = lambda a: None if a is None else on_rows(a.at[inv].get(
                mode="promise_in_bounds", unique_indices=True))
            target = row(per_row["target"])
            carbon = row(per_row["carbon"])
            feas = row(per_row["feas"])
            exec_region = row(per_row["exec_region"])
            shed = row(per_row["shed"])
            exec_hour = row(per_row["exec_hour"])
            defer = row(per_row["defer"])
            live = (jnp.ones_like(feas) if shed is None else ~shed)
            counts, deferred, mean_defer = pair_counts_and_deferral(
                exec_region, target, shed, defer, n_regions)
            spilled = ((exec_region != region) & live).sum()
            valid = jnp.arange(per_row["target"].shape[0]) < n
            sorted_sum = lambda a: jnp.where(valid, a, 0).sum()
            res = FleetRouteResult(
                target=target,
                carbon_g=carbon,
                feasible=feas,
                counts=one(counts),
                total_carbon_g=one(carbon.sum()),
                routed_carbon_g=one(jnp.where(live, carbon, 0).sum()),
                latency_opt_carbon_g=one(sorted_sum(refs["ref_latency"])),
                energy_opt_carbon_g=one(sorted_sum(refs["ref_energy"])),
                oracle_carbon_g=one(sorted_sum(refs["ref_oracle"])),
                infeasible_count=one((~feas).sum().astype(jnp.int32)),
                shed_count=one((~live).sum().astype(jnp.int32)),
                exec_region=exec_region,
                spilled_count=one(spilled.astype(jnp.int32)),
                deferred_count=one(deferred),
                mean_defer_hours=one(mean_defer),
            )
            if tiled["counts"] is None:  # stateless per-row policy
                return res, ()
            # replicated state pieces: shard 0 of the device-tiled outputs
            first = {k: one(v[0]) for k, v in tiled.items()}
            if exec_hour is not None:
                return res, TemporalState(
                    counts=first["counts"], shed=shed,
                    exec_region=exec_region, shed_pair=first["shed_pair"],
                    exec_hour=exec_hour, defer_hours=defer,
                    admit_rounds=first["admit_rounds"])
            return res, PlacementState(
                counts=first["counts"], shed=shed,
                exec_region=None if diag else exec_region,
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
    policy = fr.policy
    if isinstance(policy, CapacityLimiter):
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
        per_row, tiled = program(
            *rows, fr._ci_table, fr._ci_fc if ci_fc is None else ci_fc,
            cap_scale, used0)
    with TraceAnnotation("gs.route.aggregate"):
        refs = {k: per_row.pop(k)
                for k in ("ref_latency", "ref_energy", "ref_oracle")}
        return aggregate(per_row, refs, tiled, inv, region)
