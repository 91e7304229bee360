"""The benchmark's weight draw: codes of mean 0, hidden states that stay
apart over RMSNorm layers, and leaf rules that a configuration's reference
brings as a file."""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import reference, run_cell
from bench import weights as bench_weights
from repro.configs import get_config
from repro.core import LutLinearSpec
from repro.models.model import build_model


def abstract_of(model_config):
    model = build_model(model_config)
    return jax.eval_shape(
        lambda k: model.quantize(model.init(k), LutLinearSpec(bw=4, ba=4)),
        jax.random.PRNGKey(0))


@pytest.mark.parametrize("bw", [1, 2, 4])
def test_grid_values_have_mean_zero(bw):
    codes = bench_weights.draw_codes(jax.random.PRNGKey(7), (1024, 128 * bw), bw)
    v = np.asarray(reference.dequant(codes, jnp.ones((1024,)), bw), np.float64)
    assert v.size >= 1 << 20
    m = 2 ** (bw - 1) - 1 if bw > 1 else 1
    assert set(np.unique(v)) == set(range(-m, m + 1, 1 if bw > 1 else 2))
    assert abs(v.mean()) < 0.01 * v.std()
    assert v.std() == pytest.approx(bench_weights.grid_std(bw), rel=0.01)


def test_positions_stay_apart_over_rmsnorm_layers():
    """With a grid of nonzero mean, each projection adds a rank-one part
    along the all-ones direction that RMSNorm keeps: after 4 layers the
    last two positions' final hidden states point the same way."""
    cfg = tiny.config("chatglm3-6b", "dequant", n_layers=4, d_model=1024,
                      n_heads=8, n_kv_heads=2, head_dim=128, d_ff=2816,
                      norm_eps=1e-5)
    full = {**cfg, **reference.block(cfg)}
    assert full["norm_kind"] == "rmsnorm"
    abstract = abstract_of(run_cell.program_config(cfg, reference.block(cfg)))
    w = bench_weights.make_weights(abstract, 2**33 + 5, bw=4, d_model=1024)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 512, 64), jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = np.asarray(reference.forward_hidden(w, full, tokens), np.float64)
    cos = h[-1] @ h[-2] / (np.linalg.norm(h[-1]) * np.linalg.norm(h[-2]))
    assert cos < 0.9


ROUTER_RULE = """
import jax
import numpy as np

LEAF_RULES = {
    "router/w": lambda key, s: jax.random.normal(key, s.shape) / np.sqrt(s.shape[-2]),
}
"""


def test_a_reference_brings_the_rules_its_leaves_need(tmp_path):
    abstract = abstract_of(get_config("deepseek-v2-lite-16b", smoke=True))
    with pytest.raises(ValueError, match="router/w"):
        bench_weights.make_weights(abstract, 3, bw=4, d_model=64)

    (tmp_path / "moe_reference.py").write_text(ROUTER_RULE)
    ref = run_cell.load_module(tmp_path / "moe_reference.py", "moe_reference")
    w = bench_weights.make_weights(abstract, 3, bw=4, d_model=64,
                                   rules=ref.LEAF_RULES)
    layout = bench_weights.layout(abstract)
    assert set(w) == set(layout)
    for path, s in layout.items():
        assert (w[path].shape, w[path].dtype) == (s.shape, s.dtype), path
    router = [p for p in w if p.endswith("router/w")]
    assert router and all(float(jnp.std(w[p])) > 0 for p in router)


@pytest.mark.parametrize("rules", [
    {"router/w": jnp.zeros, "moe/router/w": jnp.zeros},
    {"router/w": jnp.zeros, "g": jnp.ones},
    {"w": jnp.zeros},
], ids=["two-reference-endings", "shared-ending", "shared-leaf"])
def test_an_ending_covered_twice_is_an_error(rules):
    abstract = abstract_of(get_config("deepseek-v2-lite-16b", smoke=True))
    with pytest.raises(ValueError, match="more than one rule|shared rule"):
        bench_weights.make_weights(abstract, 3, bw=4, d_model=64, rules=rules)


def test_a_run_takes_the_rules_of_the_configurations_reference(tmp_path):
    """``run_cell.run`` hands the reference's ``LEAF_RULES`` to the draw:
    here one that repeats a shared ending, which the draw refuses."""
    root = tiny.make_root(tmp_path, [("stablelm-12b", "dequant", "tiny-offline")])
    ref = root / "bench" / "ruled_reference.py"
    shutil.copy(root / "bench" / "reference.py", ref)
    ref.write_text(ref.read_text() + '\nLEAF_RULES = {"g": None}\n')
    cfg_path = root / "bench" / "configs" / "tiny-stablelm-12b-dequant.json"
    cfg = json.loads(cfg_path.read_text())
    cfg_path.write_text(json.dumps(dict(cfg, reference="bench/ruled_reference.py")))
    cell = run_cell.Cell.load(root, "stablelm-12b-dequant-tiny-offline")
    with pytest.raises(ValueError, match="shared rule"):
        run_cell.run(cell, 4, 1.0, trace=False, device_check=False)
