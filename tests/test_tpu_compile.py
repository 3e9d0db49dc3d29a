"""Ahead-of-time compiles for a described TPU v5e chip (no chip attached):
the routing programs of ``chip_smoke.py`` at their real stream sizes, and
the benchmark's four-chip deferral day plan, must compile and fit one
chip's 16 GiB, and every Pallas kernel must get past
Mosaic at one 128-aligned shape.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. What the compiler refuses here costs no chip time."""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import chip_smoke
from repro.configs import get_config
from repro.kernels import ops
from repro.serve import FleetRouter, OraclePolicy, TemporalPolicy, distributed
from repro.serve.streams import deferrable_stream

#: one v5e chip's HBM
CHIP_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compile cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no chip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits_one_chip(compiled) -> int:
    m = compiled.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes
    assert used < CHIP_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB per chip"
    return used


def _phase_args(phase):
    fr = phase.build()
    batch, region, t_hours = phase.stream
    hour = (np.floor(t_hours) % fr.grid.horizon_h).astype(np.int32)
    return fr, batch, np.asarray(region, np.int32), hour


@pytest.mark.parametrize("make,n", [
    (chip_smoke.place_phase, 1_000_000),
    (chip_smoke.temporal_phase, 200_000),
], ids=["place_1m", "temporal_200k"])
def test_fleet_route_compiles_for_one_chip(make, n, one_chip):
    fr, batch, region, hour = _phase_args(make(n))
    args = _specs(fr._route_args(batch, region, hour), one_chip)
    _fits_one_chip(fr._fleet_route.lower(*args).compile())


def _compiles_sharded(topo, fr, batch, region, t_hours):
    """The router's shard_map program on a 4-chip mesh of the described
    devices (per-row inputs split over the mesh, grid tables replicated)
    fits each chip and reconciles admission with an ``all-gather``."""
    hour = (np.floor(t_hours) % fr.grid.horizon_h).astype(np.int32)
    mesh = Mesh(np.asarray(topo.devices[:4]), (distributed.DATA_AXIS,))
    program = distributed._build_sharded_route(fr, mesh,
                                               distributed.DATA_AXIS)
    rows, _ = distributed.shard_stream(fr, batch, np.asarray(region, np.int32),
                                       hour, distributed.data_mesh(1))
    tables = (fr._ci_table, fr._ci_fc)
    args = (*_specs(rows, NamedSharding(mesh, P(distributed.DATA_AXIS))),
            *_specs(tables, NamedSharding(mesh, P())), None, None)
    compiled = program.lower(*args).compile()
    _fits_one_chip(compiled)
    assert "all-gather" in compiled.as_text()


def test_sharded_route_compiles_for_four_chips(topo):
    """The place_1m stream through the shard_map program on a 4-chip
    mesh."""
    phase = chip_smoke.place_phase(1_000_000)
    _compiles_sharded(topo, phase.build(), *phase.stream)


def test_sharded_deferral_compiles_for_four_chips(topo):
    """A day of 1,000,000 requests, half of them batch work that may wait
    23 hours, on a 48-hour repeated grid through the shard_map program of
    ``TemporalPolicy``: the (N, 24, 4, 3) candidate program fits each chip
    only because the rows are split four ways."""
    n, r = 1_000_000, 4
    cfg = get_config(chip_smoke.ARCH)
    policy = TemporalPolicy(OraclePolicy(FleetRouter(cfg).infra),
                            chip_smoke._dc_caps(r, n / (r * 24)),
                            max_defer_h=23)
    fr = FleetRouter(cfg, grid=chip_smoke._grid4().repeat(2), policy=policy)
    _compiles_sharded(topo, fr, *deferrable_stream(n, r,
                                                   slack_range_h=(23, 23)))


def _kernel_cases():
    bf16, f32 = jnp.bfloat16, jnp.float32
    s = jax.ShapeDtypeStruct
    return {
        "flash_attention": (partial(ops.flash_attention, causal=True),
                            (s((1, 256, 4, 128), bf16),
                             s((1, 256, 2, 128), bf16),
                             s((1, 256, 2, 128), bf16))),
        "ssd_scan": (partial(ops.ssd_scan, chunk=128, block_h=8),
                     (s((1, 256, 8, 128), f32), s((1, 256, 8), f32),
                      s((8,), f32), s((1, 256, 1, 128), f32),
                      s((1, 256, 1, 128), f32), s((8,), f32))),
        "grouped_matmul": (ops.grouped_matmul,
                           (s((2, 256, 256), bf16), s((2, 256, 256), bf16))),
        "fused_rmsnorm": (ops.fused_rmsnorm,
                          (s((512, 1024), bf16), s((1024,), bf16))),
    }


#: kernels Mosaic refuses today, with the compiler's message. No routing
#: path reaches them; fixing them is out of scope until a cell needs them.
MOSAIC_REFUSES = {
    "ssd_scan": "Unimplemented primitive in Pallas TPU lowering for "
                "KernelType.TC: cumsum",
}


@pytest.mark.parametrize("name", [
    pytest.param(k, marks=pytest.mark.xfail(
        strict=True, raises=NotImplementedError, reason=MOSAIC_REFUSES[k]))
    if k in MOSAIC_REFUSES else k
    for k in _kernel_cases()])
def test_pallas_kernel_compiles_with_mosaic(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    args = _specs(shapes, one_chip)
    compiled = jax.jit(partial(fn, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
