"""Compile-only checks for TPU v5e, plus the CPU-side platform guards.

The compile tests lower the serving path's kernels and one full-width
stablelm-12b decode step for a described (not attached) ``v5e:2x2`` chip:
the TPU compiler refuses what interpret mode accepts (unaligned blocks, too
much VMEM, a program that does not fit the chip).  The topology is described
only inside a fixture, never at import, and the tests skip where it cannot be
described.  They stay in this one file so that one test worker loads the TPU
compiler.
"""

import dataclasses
import importlib.util
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LutLinearSpec
from repro.core.quantize import QuantSpec
from repro.kernels import interpret_mode
from repro.kernels import flash_attention as fa
from repro.kernels import lut_dequant_gemm as dq
from repro.kernels import lut_stream_gemm as ss

V5E_HBM_BYTES = 16e9
D_MODEL, D_FF = 5120, 13824          # stablelm-12b's published widths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else the compiler logs to /tmp
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "bw,batch,k,f",
    [(bw, b, k, f)
     for bw in (2, 4)
     for b in (8, 256)
     for k, f in ((D_MODEL, D_FF), (D_FF, D_MODEL))]
    + [(1, 8, D_MODEL, D_FF)],
)
def test_lut_dequant_gemm_compiles_for_v5e(one_chip, bw, batch, k, f):
    grid = tuple(float(v) for v in np.asarray(QuantSpec(bw, "int").grid()))
    lowered = dq.lut_dequant_gemm.lower(
        _sds((batch, k), jnp.bfloat16, one_chip),
        _sds((f, k * bw // 8), jnp.uint8, one_chip),
        _sds((f,), jnp.float32, one_chip),
        bw=bw, k=k, grid_values=grid, interpret=False,
    )
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("s,t", [(1, 1024), (256, 256)])
def test_flash_attention_compiles_for_v5e(one_chip, s, t):
    hd = D_MODEL // 32                   # stablelm-12b: 32 heads of 160
    lowered = fa.flash_attention.lower(
        _sds((8, s, 32, hd), jnp.bfloat16, one_chip),
        _sds((8, t, 8, hd), jnp.bfloat16, one_chip),
        _sds((8, t, 8, hd), jnp.bfloat16, one_chip),
        causal=s > 1, interpret=False,
    )
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_dequant_decode_step_of_one_stablelm_layer_compiles(one_chip):
    """Prepared W4A4 dequant decode, batch 8, 2048-slot float32 caches."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serve.serving import make_serve_step

    cfg = dataclasses.replace(get_config("stablelm-12b"), n_layers=1)
    model = build_model(cfg)
    spec = LutLinearSpec(bw=4, ba=4, mode="dequant")
    params = jax.eval_shape(
        lambda key: model.prepare(model.quantize(model.init(key), spec)),
        jax.random.PRNGKey(0),
    )
    caches = jax.eval_shape(lambda: model.init_cache(8, 2048, dtype=jnp.float32))
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), tree)
    compiled = jax.jit(make_serve_step(model)).lower(
        place(params),
        _sds((8, 1), jnp.int32, one_chip),
        place(caches),
        _sds((), jnp.int32, one_chip),
        _sds((8,), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


# The stablelm-12b decode wave at 4 layers, batch 8, 2048 positions: one
# layer's float32 K (or V) cache, and the segment's stack of four.
CACHE_SHAPES = ((8, 2048, 8, 160), (4, 8, 2048, 8, 160))
KV_STACK_BYTES = 2 * 4 * 8 * 2048 * 8 * 160 * 4
# Temporaries of that wave when the layer scan took the stacked caches in as
# xs and out as ys (slicing and restacking every layer, every step).
XS_YS_TEMP_BYTES = 5_279_727_104

_HLO_CALLEES = re.compile(r"\b(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")


def _hlo_computations(hlo: str) -> dict:
    """Compiled HLO text by computation: name -> its lines."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = [line]
        elif name is not None:
            comps[name].append(line)
            if line == "}":
                name = None
    return comps


def _while_body_computations(hlo: str) -> dict:
    """Every computation the entry's ``while`` loops run: their bodies and
    conditions, and what those call (nested loops, fusions) in turn."""
    comps = _hlo_computations(hlo)
    entry = next(n for n, lines in comps.items() if lines[0].startswith("ENTRY"))
    todo = [c for line in comps[entry] if " while(" in line
            for c in _HLO_CALLEES.findall(line)]
    seen = {}
    while todo:
        name = todo.pop()
        if name not in seen:
            seen[name] = comps[name]
            todo += [c for line in comps[name] for c in _HLO_CALLEES.findall(line)]
    return seen


def test_decode_wave_of_the_stablelm_cell_writes_kv_rows_in_place(one_chip):
    """The serving cell's decode wave (4 full-width stablelm-12b layers,
    prepared W4 dequant, batch 8, max_seq 2048, float32 caches): inside its
    loops no instruction, fused or not, copies, slices out or updates a whole
    layer's cache or the whole stack; only the rows of the step are written.
    The temporaries fall by at least the stacked K and V that the xs/ys form
    of the layer scan kept besides."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serve.serving import make_decode_wave

    cfg = dataclasses.replace(get_config("stablelm-12b"), n_layers=4)
    model = build_model(cfg)
    spec = LutLinearSpec(bw=4, ba=4, mode="dequant")
    params = jax.eval_shape(
        lambda key: model.prepare(model.quantize(model.init(key), spec)),
        jax.random.PRNGKey(0),
    )
    caches = jax.eval_shape(lambda: model.init_cache(8, 2048, dtype=jnp.float32))
    place = lambda tree: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), tree)
    compiled = make_decode_wave(model, out_cap=2048).lower(
        place(params),
        _sds((8, 1), jnp.int32, one_chip),
        place(caches),
        _sds((8,), jnp.int32, one_chip),
        _sds((8,), jnp.int32, one_chip),
        _sds((8,), jnp.bool_, one_chip),
        _sds((), jnp.int32, one_chip),
    ).compile()

    loop = _while_body_computations(compiled.as_text())
    whole_cache = []
    scatters = 0
    for lines in loop.values():
        for line in lines:
            m = _HLO_INSTR.match(line)
            if not m:
                continue
            name, dtype, dims, op = m.groups()
            shape = tuple(int(d) for d in dims.split(",") if d and d != "1")
            if dtype != "f32" or shape not in CACHE_SHAPES:
                continue
            scatters += op == "scatter"
            if op in ("copy", "dynamic-slice", "dynamic-update-slice"):
                whole_cache.append(f"{op} {name}")
    assert scatters >= 2, "no scatter of K and V rows into the stacked caches"
    assert whole_cache == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= XS_YS_TEMP_BYTES - KV_STACK_BYTES


# ---------------------------------------------------------------------------
# CPU-side guards: no topology needed
# ---------------------------------------------------------------------------


def test_interpret_mode_is_chosen_by_platform():
    assert interpret_mode("cpu") is True
    assert interpret_mode("tpu") is False
    assert interpret_mode() is (jax.default_backend() == "cpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        interpret_mode("gpu")


def test_lut_stream_gemm_refuses_to_compile():
    z = jnp.zeros((4, 2), jnp.int32)
    with pytest.raises(NotImplementedError, match=r"\(M, 1\).*\(R, 1\)"):
        ss.lut_stream_gemm(z, z, z, z, z, r=4, interpret=False)


def _chip_smoke():
    path = pathlib.Path(__file__).resolve().parent.parent / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu():
    cs = _chip_smoke()
    with pytest.raises(SystemExit, match="needs a TPU"):
        cs.check_device(jax.devices("cpu"))


def test_chip_smoke_refuses_too_few_chips():
    cs = _chip_smoke()
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    cs.check_device([tpu])
    with pytest.raises(SystemExit, match="needs 4 devices"):
        cs.check_device([tpu], chips=4)
