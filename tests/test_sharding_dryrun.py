"""Sharding rules + dry-run machinery tests (single device; the 512-device
matrix itself runs via ``python -m repro.launch.dryrun``)."""

import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, param_specs
from repro.launch.dryrun import collective_bytes
from repro.sharding.rules import (
    MeshAxes,
    enforce_divisible,
    logical_param_spec,
    spec_tree,
)

M = MeshAxes(data=("data",), model="model")


class TestRules:
    def test_attention_tp_pattern(self):
        """Megatron pattern: qkv column-parallel, wo row-parallel."""
        assert logical_param_spec("wq", 2, M) == P(None, "model")
        assert logical_param_spec("wo", 2, M) == P("model", None)

    def test_mlp_pattern(self):
        assert logical_param_spec("w_gate", 2, M) == P(None, "model")
        assert logical_param_spec("w_down", 2, M) == P("model", None)

    def test_moe_expert_parallel(self):
        assert logical_param_spec("w_gate", 3, M) == P("model", None, None)
        assert logical_param_spec("router", 2, M) == P()

    def test_mamba_head_parallel(self):
        assert logical_param_spec("x_proj", 2, M) == P(None, "model")
        # replicated (modulo fsdp placeholder Nones)
        assert logical_param_spec("bc_proj", 2, M) in (P(), P(None, None))
        assert logical_param_spec("out_proj", 2, M) == P("model", None)

    def test_embedding_vocab_parallel(self):
        assert logical_param_spec("embed", 2, M) == P("model", None)
        assert logical_param_spec("lm_head", 2, M) == P(None, "model")

    def test_stacked_blocks_get_leading_none(self):
        cfg = get_config("deepseek-7b", smoke=True)
        params = param_specs(cfg)
        specs = spec_tree(params, M)
        wq_spec = specs["blocks"][0]["attn"]["wq"]
        assert wq_spec == P(None, None, "model")

    def test_every_leaf_has_a_spec(self):
        for arch in ("jamba-v0.1-52b", "whisper-base", "qwen2-vl-7b"):
            cfg = get_config(arch, smoke=True)
            params = param_specs(cfg)
            specs = spec_tree(params, M)
            assert len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
                x, P))) == len(jax.tree.leaves(params))


class TestDivisibility:
    def test_divisible_kept(self):
        mesh = jax.make_mesh((1,), ("model",))
        # 1 divides everything
        assert enforce_divisible(mesh, P("model", None), (7, 3)) == \
            P("model", None)

    def test_nondivisible_dropped(self):
        # a fake 1-device mesh can't test >1 axis sizes; simulate via shape
        mesh = jax.make_mesh((1,), ("data",))

        class FakeMesh:
            shape = {"data": 16, "model": 16}

        assert enforce_divisible(FakeMesh(), P("data", None), (1, 8)) == \
            P(None, None)
        assert enforce_divisible(FakeMesh(), P("model", None), (50280, 8)) \
            == P(None, None)
        assert enforce_divisible(FakeMesh(), P("model", None), (50176, 8)) \
            == P("model", None)

    def test_tuple_axes(self):
        class FakeMesh:
            shape = {"pod": 2, "data": 16}

        assert enforce_divisible(FakeMesh(), P(("pod", "data"),), (64,)) == \
            P(("pod", "data"))
        assert enforce_divisible(FakeMesh(), P(("pod", "data"),), (16,)) == \
            P(None)


class TestCollectiveParser:
    HLO = """
HloModule jit_step

%add { ... }

ENTRY %main {
  %p0 = f32[128,64]{1,0} parameter(0)
  %ag = f32[128,256]{1,0} all-gather(%p0), dimensions={1}
  %ar = f32[128,64]{1,0} all-reduce(%p0), to_apply=%add
  %rs = f32[32,64]{1,0} reduce-scatter(%p0), dimensions={0}
  %a2a = f32[128,64]{1,0} all-to-all(%p0), dimensions={0}
  %cp = f32[128,64]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %p1 = bf16[16]{0} parameter(1)
  %ars = bf16[16]{0} all-reduce-start(%p1), to_apply=%add
  %ard = bf16[16]{0} all-reduce-done(%ars)
}
"""

    def test_counts(self):
        out = collective_bytes(self.HLO)
        f32 = 4
        assert out["all-gather"] == 128 * 256 * f32  # result side
        assert out["all-reduce"] == 128 * 64 * f32 + 16 * 2  # + async start
        assert out["reduce-scatter"] == 128 * 64 * f32  # operand side
        assert out["all-to-all"] == 128 * 64 * f32
        assert out["collective-permute"] == 128 * 64 * f32
        assert out["total"] == sum(out[k] for k in
                                   ("all-gather", "all-reduce",
                                    "reduce-scatter", "all-to-all",
                                    "collective-permute"))

    def test_done_not_double_counted(self):
        out = collective_bytes(self.HLO)
        # only the -start contributes the 16x bf16 payload
        assert out["all-reduce"] - 128 * 64 * 4 == 32


@pytest.mark.slow
def test_dryrun_subprocess_smoke():
    """End-to-end: a reduced config lowers + compiles on a 512-device mesh
    in a fresh process (the only place the XLA_FLAGS override may exist)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_production_mesh
mesh = make_production_mesh(multi_pod=True)
r = lower_cell("deepseek-7b", "train_4k", mesh, remat="minimal",
               extra=dict(n_layers=2, d_model=512, n_heads=8, n_kv_heads=4,
                          d_ff=1024, vocab_size=4096, head_dim=64))
assert r.ok, r.error
assert r.flops > 0 and r.collectives["total"] > 0
print("SUBPROCESS_OK", r.mesh)
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=560,
                          env={**__import__("os").environ,
                               "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
                          cwd=__import__("os").path.dirname(
                              __import__("os").path.dirname(__file__)))
    assert "SUBPROCESS_OK 2x16x16" in proc.stdout, proc.stderr[-2000:]
