"""Fast single-process unit tests for ``repro.dist``.

The 8-device correctness tests live in ``tests/test_distribution.py`` and
run in subprocesses; everything here runs on the single CPU device so the
dist logic is covered even where those are skipped:

* ``compressed_psum`` error bounds across dtypes and scales (the axis is
  bound with ``vmap(..., axis_name=...)`` — no devices needed);
* ``param_specs`` divisibility fallbacks (via ``AbstractMesh`` — spec
  derivation never touches devices);
* the LUT-quantized pytree rule: packed codes TP-shard on the output dim,
  scales/bias follow, expert stacks shard the expert dim.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core import LutLinearSpec, QuantizedLinear
from repro.dist import sharding as shd
from repro.dist.collectives import compressed_psum
from repro.models.config import ModelConfig, MoEConfig


def _vpsum(x, **kw):
    """Run compressed_psum over dim 0 of ``x`` on one device via vmap."""
    return jax.vmap(lambda v: compressed_psum(v, "i"), axis_name="i", **kw)(x)


# ---------------------------------------------------------------------------
# compressed_psum
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
# 1e3 keeps the 8-way fp16 sum under fp16's 65504 max (overflow there is a
# property of the output dtype, not of the compression).
@pytest.mark.parametrize("scale", [1e-4, 1.0, 1e3])
def test_compressed_psum_error_bound(dtype, scale):
    n = 8
    x = (jax.random.normal(jax.random.PRNGKey(0), (n, 256), jnp.float32) * scale)
    exact = jnp.sum(x, axis=0)
    out = _vpsum(x.astype(dtype))
    assert out.dtype == dtype
    err = float(
        jnp.max(jnp.abs(out[0].astype(jnp.float32) - exact))
        / jnp.max(jnp.abs(exact))
    )
    # int8 quantization error bound (+ half-precision input rounding slack).
    assert err < 0.02, (dtype, scale, err)
    # All participants see the same reduced value.
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[-1]))


def test_compressed_psum_zero_tensor():
    out = _vpsum(jnp.zeros((4, 16), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_compressed_psum_propagates_nonfinite():
    """A blown-up gradient must stay visible (NaN), not quantize to ~0."""
    x = jnp.ones((4, 8), jnp.float32).at[0, 0].set(jnp.inf)
    out = _vpsum(x)
    assert bool(jnp.all(jnp.isnan(out)))


def test_compressed_psum_worst_case_bound():
    """Absolute error never exceeds n_devices * scale / 2 (+ rounding)."""
    n = 8
    x = jax.random.uniform(jax.random.PRNGKey(1), (n, 512), jnp.float32, -3.0, 3.0)
    exact = jnp.sum(x, axis=0)
    out = _vpsum(x)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    bound = n * scale / 2 * 1.01
    assert float(jnp.max(jnp.abs(out[0] - exact))) <= bound


# ---------------------------------------------------------------------------
# param_specs: divisibility fallbacks
# ---------------------------------------------------------------------------


MESH8 = AbstractMesh((4, 2), ("data", "model"))


def _ctx(**kw):
    kw.setdefault("mesh", MESH8)
    kw.setdefault("dp_axes", ("data",))
    kw.setdefault("tp_axis", "model")
    return shd.ShardCtx(**kw)


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=16, n_heads=2,
                n_kv_heads=2, d_ff=32, vocab_size=64)
    base.update(kw)
    return ModelConfig(**base)


def test_ctx_sizes_from_abstract_mesh():
    ctx = _ctx()
    assert ctx.dp_size() == 4 and ctx.tp_size() == 2
    assert _ctx(dp_axes=("pod", "data")).dp_size() == 4  # missing axis -> 1
    assert shd.ShardCtx(mesh=None).dp_size() == 1


def test_param_specs_tp_shards_col_and_row_projections():
    cfg = _cfg()
    params = {
        "wq": {"w": jnp.zeros((2, 16, 16)), "b": jnp.zeros((2, 16))},
        "wo": {"w": jnp.zeros((2, 16, 16))},
    }
    specs = shd.param_specs(cfg, params, _ctx())
    assert specs["wq"]["w"] == P(None, None, "model")   # output dim
    assert specs["wq"]["b"] == P(None, "model")
    assert specs["wo"]["w"] == P(None, "model", None)   # input dim


def test_param_specs_divisibility_falls_back_to_replication():
    cfg = _cfg()
    # 15 is divisible by neither tp=2 nor dp=4: fully replicated.
    params = {"wq": {"w": jnp.zeros((15, 15))}}
    specs = shd.param_specs(cfg, params, _ctx(fsdp=True))
    assert specs["wq"]["w"] == P(None, None)
    # Odd output dim but even input dim: fsdp still finds the K dim.
    params = {"wq": {"w": jnp.zeros((16, 15))}}
    specs = shd.param_specs(cfg, params, _ctx(fsdp=True))
    assert specs["wq"]["w"] == P("data", None)


def test_param_specs_fsdp_shards_non_tp_dim():
    cfg = _cfg()
    params = {"wq": {"w": jnp.zeros((2, 16, 16))}}
    specs = shd.param_specs(cfg, params, _ctx(fsdp=True))
    assert specs["wq"]["w"] == P(None, "data", "model")
    # Without fsdp the dp axes never touch weights.
    specs = shd.param_specs(cfg, params, _ctx(fsdp=False))
    assert specs["wq"]["w"] == P(None, None, "model")


def test_param_specs_embed_vocab_parallel():
    cfg = _cfg()
    specs = shd.param_specs(cfg, {"embed": jnp.zeros((64, 16))}, _ctx())
    assert specs["embed"] == P("model", None)
    specs = shd.param_specs(cfg, {"embed": jnp.zeros((63, 16))}, _ctx())
    assert specs["embed"] == P(None, None)


def test_param_specs_moe_expert_parallel_and_fallback():
    cfg = _cfg(
        family="moe",
        moe=MoEConfig(n_experts=4, n_shared_experts=0, top_k=2,
                      d_ff_expert=8, capacity_factor=1.0),
    )
    params = {"moe": {
        "router": {"w": jnp.zeros((16, 4))},
        "w_gate": jnp.zeros((2, 4, 16, 8)),   # [units, E, d, f]
        "w_up": jnp.zeros((2, 4, 16, 8)),
        "w_down": jnp.zeros((2, 4, 8, 16)),
    }}
    specs = shd.param_specs(cfg, params, _ctx())
    assert specs["moe"]["w_gate"] == P(None, "model", None, None)
    assert specs["moe"]["w_down"] == P(None, "model", None, None)
    # Odd expert count: replicate instead of sharding the expert dim.
    params["moe"]["w_gate"] = jnp.zeros((2, 3, 16, 8))
    specs = shd.param_specs(cfg, params, _ctx())
    assert specs["moe"]["w_gate"] == P(None, None, None, None)


# ---------------------------------------------------------------------------
# param_specs: LUT-quantized pytrees
# ---------------------------------------------------------------------------


def _qlinear(f, kp, *, lead=(), bias=False):
    shape = tuple(lead) + (f, kp)
    return QuantizedLinear(
        codes=jnp.zeros(shape, jnp.uint8),
        scale=jnp.zeros(tuple(lead) + (f,), jnp.float32),
        bias=jnp.zeros(tuple(lead) + (f,), jnp.float32) if bias else None,
        spec=LutLinearSpec(bw=4, ba=4),
        k=2 * kp,
    )


def test_quantized_codes_tp_shard_output_dim():
    cfg = _cfg()
    params = {"wq": _qlinear(16, 8, lead=(2,), bias=True)}
    specs = shd.param_specs(cfg, params, _ctx(fsdp=True))
    q = specs["wq"]
    assert isinstance(q, QuantizedLinear)
    # Packed codes shard the output (N) dim only — K is bit-packed and the
    # canonical/reordering LUT tables are replicated (static, not in the
    # pytree), so no spec may ever split the packed-K dim.
    assert q.codes == P(None, "model", None)
    assert q.scale == P(None, "model")
    assert q.bias == P(None, "model")
    # Structure round-trips: the spec tree has the parameters' exact treedef
    # (QuantizedLinear static fields included), so device_put/jit line up.
    assert jax.tree.structure(specs) == jax.tree.structure(params)


def test_quantized_odd_output_dim_replicates():
    cfg = _cfg()
    specs = shd.param_specs(cfg, {"wq": _qlinear(15, 8)}, _ctx())
    assert specs["wq"].codes == P(None, None)
    assert specs["wq"].scale == P(None)


def test_quantized_moe_experts_shard_expert_dim():
    cfg = _cfg()
    params = {"moe": {"w_up": _qlinear(8, 4, lead=(2, 4))}}  # [U, E, f, Kp]
    specs = shd.param_specs(cfg, params, _ctx())
    assert specs["moe"]["w_up"].codes == P(None, "model", None, None)
    assert specs["moe"]["w_up"].scale == P(None, "model", None)
    # Odd expert count: fully replicate (moe_apply runs replicated experts
    # then, so output-dim sharding would just be all-gathered every layer).
    odd = {"moe": {"w_up": _qlinear(8, 4, lead=(2, 3))}}
    specs = shd.param_specs(cfg, odd, _ctx())
    assert specs["moe"]["w_up"].codes == P(None, None, None, None)
    assert specs["moe"]["w_up"].scale == P(None, None, None)


def test_quantized_specs_device_put_roundtrip():
    """Spec trees line up leaf-for-leaf for a real device_put on 1 CPU."""
    from jax.sharding import Mesh, NamedSharding

    cfg = _cfg()
    params = {"wq": _qlinear(16, 8, lead=(2,), bias=True),
              "embed": jnp.zeros((64, 16))}
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    ctx = shd.ShardCtx(mesh=mesh)
    shardings = shd.to_shardings(shd.param_specs(cfg, params, ctx), mesh)
    out = jax.device_put(params, shardings)
    assert isinstance(out["wq"], QuantizedLinear)
    assert isinstance(out["wq"].codes.sharding, NamedSharding)


# ---------------------------------------------------------------------------
# param_specs: whole model zoo
# ---------------------------------------------------------------------------


def _iter_spec_leaves(specs, shapes):
    """Pairs of (PartitionSpec, shape) across two structurally equal trees."""
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    a_leaves = jax.tree.leaves(shapes)
    assert len(s_leaves) == len(a_leaves)
    return zip(s_leaves, a_leaves)


@pytest.mark.parametrize("arch", [
    "gemma2-2b", "chatglm3-6b", "stablelm-12b", "command-r-plus-104b",
    "deepseek-v2-lite-16b", "llama4-maverick-400b-a17b", "zamba2-7b",
    "rwkv6-3b", "internvl2-1b", "whisper-large-v3",
])
@pytest.mark.parametrize("fsdp", [False, True])
def test_param_specs_cover_every_family(arch, fsdp):
    """Every smoke config (dense/MoE/SSM/RWKV/hybrid/VLM/enc-dec) gets a
    structurally matching spec tree whose sharded dims all divide."""
    from repro.configs import get_config
    from repro.models.model import build_model

    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    ctx = _ctx(fsdp=fsdp)
    specs = shd.param_specs(cfg, params, ctx)
    assert jax.tree.structure(specs) == jax.tree.structure(params)
    sizes = dict(MESH8.shape)
    n_sharded = 0
    for spec, leaf in _iter_spec_leaves(specs, params):
        assert isinstance(spec, P) and len(spec) <= leaf.ndim, (spec, leaf.shape)
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            n_sharded += 1
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for ax in axes:
                total *= sizes[ax]
            assert leaf.shape[d] % total == 0, (arch, spec, leaf.shape, d)
    assert n_sharded > 0, f"{arch}: no leaf sharded at all"


def test_cache_specs_batch_and_seq_sharding():
    cfg = _cfg()
    caches = [{"s0_D": {"k": jnp.zeros((2, 4, 2048, 2, 8)),
                        "v": jnp.zeros((2, 4, 2048, 2, 8))}}]
    specs = shd.cache_specs(cfg, caches, _ctx(seq_shard=True))
    assert specs[0]["s0_D"]["k"] == P(None, "data", "model", None, None)
    # seq_shard off, or a short dim 2 (SSM feature dims), keeps dim 2 whole.
    specs = shd.cache_specs(cfg, caches, _ctx())
    assert specs[0]["s0_D"]["k"] == P(None, "data", None, None, None)
    short = [{"s0_M": {"conv": jnp.zeros((2, 4, 16, 4))}}]
    specs = shd.cache_specs(cfg, short, _ctx(seq_shard=True))
    assert specs[0]["s0_M"]["conv"] == P(None, "data", None, None)
    # Batch not divisible by dp: replicate.
    odd = [{"s0_D": {"k": jnp.zeros((2, 3, 2048, 2, 8))}}]
    specs = shd.cache_specs(cfg, odd, _ctx())
    assert specs[0]["s0_D"]["k"] == P(None, None, None, None, None)
