"""A configuration whose block differs from the architecture's, added as a
file alone: the program is built with the block the file states, and the
reference named in the file follows the same fields."""

import json

import jax
import jax.numpy as jnp
import numpy as np

import tiny
from bench import run_cell
from bench import weights as bench_weights
from repro.core import LutLinearSpec
from repro.models.model import build_model


def add_config(root, name: str, **block) -> run_cell.Cell:
    """Write a tiny configuration with ``block`` and a cell over it."""
    c = tiny.config("stablelm-12b", "dequant", name=name, **block)
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(c))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name=name, source="test",
                                 file=f"bench/configs/{name}.json",
                                 reduced=[], why="test"))
    bench["workloads"].append(dict(name=f"{name}-cell", config=name,
                                   traffic="tiny-offline", chips=1, why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        m["workloads"].append(f"{name}-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return run_cell.Cell.load(root, f"{name}-cell")


def test_a_block_the_program_serves_is_correct(tmp_path):
    root = tiny.make_root(tmp_path, [("stablelm-12b", "dequant", "tiny-offline")])
    cell = add_config(root, "tiny-plain-mlp", rope_fraction=1.0, norm_eps=1e-5,
                      gated_ffn=False, ffn_act="gelu")
    mc = run_cell.program_config(cell.cfg, cell.reference.block(cell.cfg))
    assert (mc.rope_kind, mc.norm_eps, mc.gated_ffn, mc.ffn_act) == (
        "full", 1e-5, False, "gelu")
    res = run_cell.run(cell, 2**31 + 11, 1.5, trace=False, device_check=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_compared"]["value"] >= 100


def test_a_parallel_block_reaches_both_sides(tmp_path):
    root = tiny.make_root(tmp_path, [("stablelm-12b", "dequant", "tiny-offline")])
    cell = add_config(root, "tiny-parallel", parallel_block=True)
    ref = cell.reference
    block = ref.block(cell.cfg)
    mc = run_cell.program_config(cell.cfg, block)
    assert mc.parallel_block and block["parallel_block"]

    model = build_model(mc)
    abstract = jax.eval_shape(
        lambda k: model.quantize(model.init(k), LutLinearSpec(bw=4, ba=4)),
        jax.random.PRNGKey(0))
    w = bench_weights.make_weights(abstract, 5, bw=4, d_model=mc.d_model)
    tokens = jnp.arange(12)
    full = {**cell.cfg, **block}
    parallel = ref.forward_hidden(w, full, tokens)
    sequential = ref.forward_hidden(w, dict(full, parallel_block=False), tokens)
    assert not np.allclose(parallel, sequential, atol=1e-2)

    res = run_cell.run(cell, 2**31 + 11, 1.5, trace=False, device_check=False)
    assert res["checks"]["tokens_compared"]["value"] >= 100
    assert {"tokens_per_s", "setup_s"} <= set(res["metrics"])
