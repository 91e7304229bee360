"""The device scopes of ``repro.obs.SCOPES`` survive compilation: the compiled
serving programs carry each one in their HLO ``op_name`` metadata, which is
what a device trace reports per operation."""

import dataclasses as dc
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import LutLinearSpec
from repro.models.model import build_model
from repro.obs import SCOPES
from repro.obs import scopes as sc
from repro.serve import serving

B, MAX_SEQ, PROMPT = 2, 32, 8


@pytest.fixture(scope="module")
def served():
    """A tiny dense decoder, quantized and prepared as the engine serves it,
    with the caches and slot vectors of a two-slot engine."""
    cfg = dc.replace(get_config("stablelm-12b", smoke=True), name="scopes-test",
                     n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                     vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tree = model.prepare(model.quantize(params, LutLinearSpec(bw=4, ba=4,
                                                              mode="dequant")))
    caches = model.init_cache(B, MAX_SEQ, dtype=jnp.float32)
    return model, tree, caches


def scopes_in(hlo_text: str) -> set:
    """Every name of ``SCOPES`` found on an ``op_name`` path of ``hlo_text``."""
    paths = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {part for p in paths for part in p.split("/")} & set(SCOPES)


def compiled_text(model, tree, caches, program: str) -> str:
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    if program == "decode_wave":
        fn = serving.make_decode_wave(model, out_cap=MAX_SEQ)
        args = (tree, i32(B, 1), caches, i32(B), i32(B), jnp.ones((B,), bool),
                jnp.int32(3))
    else:
        fn = jax.jit(serving.make_prefill_step(model))
        args = (tree, i32(B, PROMPT), caches)
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("program,absent", [
    ("decode_wave", set()),
    # The prefill runs the layer scan once, outside any decode loop.
    ("prefill_step", {sc.DECODE_LOOP}),
])
def test_compiled_programs_carry_every_scope(served, program, absent):
    model, tree, caches = served
    found = scopes_in(compiled_text(model, tree, caches, program))
    assert found == set(SCOPES) - absent

