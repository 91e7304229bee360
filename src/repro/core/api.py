"""Framework-facing LoCaLUT API: quantized linear layers.

A :class:`QuantizedLinear` stores a weight matrix as **bit-packed low-bit
codes** plus per-output-channel scales; three execution paths share it:

* ``dequant``  — XLA path: value-LUT decode + MXU matmul (dense-equivalent
                 numerics; used inside the large-scale models and the
                 dry-run).  This is the TPU re-instantiation of the paper's
                 capacity↔computation tradeoff: 16/bw× fewer weight bytes
                 from HBM, paid for with decode flops.
* ``lut``      — paper-faithful path: activation quantization → LUT
                 canonicalization → reordering LUT → canonical-LUT lookups
                 (bit-exact integer semantics, :mod:`repro.core.engine`).
* ``stream``   — paper-faithful §IV-C path: tiled, deduplicated LUT slice
                 streaming (:func:`repro.core.engine.streamed_lut_gemm`);
                 same numerics as ``lut``, plus simulated DRAM→buffer
                 traffic stats (:func:`stream_stats_for`).
* ``pallas``   — fused TPU kernel (:mod:`repro.kernels`), same numerics as
                 ``dequant``.

Weight layout: codes are stored transposed ``[F, K]`` and bit-packed along
``K`` (the contraction dim) so the decode in every path streams contiguous
bytes.

Serving is weight-stationary (§V-B): :func:`prepare_linear` freezes every
per-call weight product once (:mod:`repro.core.prepared`), and
:func:`apply_linear` transparently takes either the raw or the prepared
layer — same bits, none of the per-call weight work.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, luts, packing, perfmodel
from repro.core.quantize import QuantSpec, quantize

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LutLinearSpec:
    """Static configuration of a LoCaLUT-quantized linear layer."""

    bw: int = 2
    ba: int = 4
    p: Optional[int] = None        # None -> perf-model auto-selection
    mode: str = "dequant"          # "dequant" | "lut" | "stream" | "pallas"
    w_kind: str = "int"
    a_kind: str = "int"
    tile_n: Optional[int] = None   # stream mode: activation columns per tile
    buffer_bytes: Optional[int] = None  # stream mode: auto tile_n from a
                                        # buffer budget when tile_n is None

    def wspec(self) -> QuantSpec:
        return QuantSpec(self.bw, self.w_kind, axis=1)  # per-output-channel

    def aspec(self) -> QuantSpec:
        return QuantSpec(self.ba, self.a_kind, axis=None)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedLinear:
    """Pytree carrying the packed weight of one linear layer."""

    codes: Array                       # [F, K*bw/8] uint8, bit-packed codes
    scale: Array                       # [F] fp32 per-output-channel scale
    bias: Optional[Array]              # [F] or None
    spec: LutLinearSpec = dataclasses.field(
        metadata=dict(static=True), default=LutLinearSpec()
    )
    k: int = dataclasses.field(metadata=dict(static=True), default=0)
    # Frozen per-tensor activation scale (repro.core.calibrate).  When set,
    # the lut/stream activation quantizer uses it instead of the dynamic
    # per-batch max, making outputs batch-composition invariant — LUT-PIM
    # tables are precomputed against a fixed input grid, so a static scale
    # is the hardware-faithful regime.  None keeps the dynamic seed behavior.
    ascale: Optional[Array] = None

    @property
    def f(self) -> int:
        return self.codes.shape[0]

    @property
    def packed_bytes(self) -> int:
        return int(np.prod(self.codes.shape))


def quantize_linear(
    w: Array, spec: LutLinearSpec, bias: Optional[Array] = None
) -> QuantizedLinear:
    """Quantize a dense ``[K, F]`` weight into a :class:`QuantizedLinear`."""
    k, f = w.shape
    codes, scale = quantize(w, spec.wspec())          # codes [K,F], scale [1,F]
    codes_t = codes.T                                  # [F, K]
    pad = (-k) % packing.codes_per_byte(spec.bw)
    if pad:
        # Pad K with the grid's zero-value code so decode-matmul is exact.
        from repro.core.quantize import zero_code

        zc = zero_code(spec.wspec().grid())
        codes_t = jnp.pad(codes_t, ((0, 0), (0, pad)), constant_values=zc)
    packed = packing.pack_bits(codes_t, spec.bw)       # [F, ceil(K/cpb)]
    return QuantizedLinear(
        codes=packed, scale=scale.reshape(f), bias=bias, spec=spec, k=k
    )


def dequantize_weights(q: QuantizedLinear) -> Array:
    """Value-LUT decode back to a dense ``[K, F]`` float32 weight."""
    spec = q.spec
    grid = jnp.asarray(spec.wspec().grid(), dtype=jnp.float32)
    codes = packing.unpack_bits(q.codes, spec.bw)[:, : q.k]   # [F, K]
    w_t = grid[codes] * q.scale[:, None]
    return w_t.T


def apply_linear(q, x: Array) -> Array:
    """``y = x @ W (+ bias)`` through the path selected by ``q.spec.mode``.

    ``x``: [..., K] activations; returns [..., F].  Accepts either a raw
    :class:`QuantizedLinear` or a :class:`repro.core.prepared.PreparedLinear`
    — the latter routes through the weight-stationary fast path (bit-identical
    results, no per-call weight work).
    """
    from repro.core import prepared as _prepared

    if isinstance(q, _prepared.PreparedLinear):
        return _prepared.apply_prepared(q, x)
    mode = q.spec.mode
    if mode == "dequant":
        y = _dequant_matmul(q, x)
    elif mode == "lut":
        y = _lut_matmul(q, x)
    elif mode == "stream":
        y, _ = _stream_matmul(q, x)
    elif mode == "pallas":
        from repro.kernels import ops  # local import: kernels are optional

        y = ops.lut_dequant_gemm(
            x.reshape(-1, x.shape[-1]),
            q.codes,
            q.scale,
            bw=q.spec.bw,
            k=q.k,
            grid_kind=q.spec.w_kind,
        ).reshape(x.shape[:-1] + (q.f,)).astype(x.dtype)
        # ^ kernel accumulates f32; cast back like every other mode so a
        #   bf16 model's residual stream keeps its dtype through the scan.
    else:
        raise ValueError(f"unknown mode {mode}")
    if q.bias is not None:
        y = y + q.bias.astype(y.dtype)
    return y


def _dequant_matmul(q: QuantizedLinear, x: Array) -> Array:
    spec = q.spec
    grid = jnp.asarray(spec.wspec().grid(), dtype=x.dtype)
    codes = packing.unpack_bits(q.codes, spec.bw)[:, : q.k]        # [F, K]
    w_t = grid[codes] * q.scale[:, None].astype(x.dtype)           # [F, K]
    return jnp.einsum("...k,fk->...f", x, w_t)


def plan_p(f: int, k: int, n: int, spec: LutLinearSpec, device=None) -> int:
    """The packing degree every LUT path agrees on: ``spec.p``, else the
    Eq. 2/4 sweep's ``p*`` for this (M, K, N).

    There is ONE p-selection heuristic in the codebase —
    :func:`repro.core.perfmodel.make_plan` — and this is its single entry
    point: the raw, plan-only and prepared apply paths, and the
    ``repro.tune`` whole-model planner, all route through it so they cannot
    drift.  ``device`` parameterizes the sweep's cost constants; when no
    device model is given the fallback is the paper's profiled UPMEM system
    (the seed behaviour, regression-locked against ``perfmodel.make_plan``
    on the fig13 shapes by ``tests/test_perfmodel.py``)."""
    if spec.p:
        return spec.p
    inp = perfmodel.PlanInputs(m=f, k=k, n=n, bw=spec.bw, ba=spec.ba)
    if device is not None:
        inp = dataclasses.replace(inp, device=device)
    return perfmodel.make_plan(inp).p_star


def quantized_lut_gemm(q, x: Array, run) -> Array:
    """The activation side every LUT path shares — one body, so the raw and
    prepared implementations cannot drift numerically: quantize activations,
    ``o = run(acodes, n)`` (the engine GEMM, [F, B]), rescale, reshape.

    A calibrated layer (``q.ascale`` set) quantizes against its frozen scale,
    so the result for any one row is independent of which other rows share
    the batch — the invariance the bit-exact replay contract needs.  The
    quantizer arithmetic runs in f32 regardless of activation dtype: XLA
    recomputes bf16 fusions with f32 intermediates, so bf16 quantization is
    not bit-stable across graph variants (frozen-vs-dynamic scale, jit
    boundaries) — f32 ops are."""
    xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)             # [B, K]
    frozen = getattr(q, "ascale", None)
    acodes, ascale = quantize(xf.T, q.spec.aspec(), scale=frozen)   # [K, B]
    o = run(acodes, xf.shape[0])
    y = o.astype(jnp.float32) * q.scale[:, None] * ascale
    return y.T.reshape(x.shape[:-1] + (q.f,)).astype(x.dtype)


def _lut_matmul(q: QuantizedLinear, x: Array) -> Array:
    """Paper-faithful path: canonical + reordering LUT engine (bit-exact)."""
    spec = q.spec

    def run(acodes, n):
        wcodes = packing.unpack_bits(q.codes, spec.bw)[:, : q.k]    # [F, K]
        p = plan_p(q.f, q.k, n, spec)
        pack = _lut_pack_cache(spec.bw, spec.ba, p, spec.w_kind, spec.a_kind)
        return engine.canonical_lut_gemm(wcodes, acodes, pack)      # [F,B] i32

    return quantized_lut_gemm(q, x, run)


def _stream_matmul(q: QuantizedLinear, x: Array) -> tuple[Array, engine.StreamStats]:
    """§IV-C path: tiled, deduplicated slice streaming (bit-exact vs ``lut``)."""
    spec = q.spec
    stats_box = []

    def run(acodes, n):
        wcodes = packing.unpack_bits(q.codes, spec.bw)[:, : q.k]    # [F, K]
        p = plan_p(q.f, q.k, n, spec)
        pack = _lut_pack_cache(spec.bw, spec.ba, p, spec.w_kind, spec.a_kind)
        o, stats = engine.streamed_lut_gemm(
            wcodes, acodes, pack,
            tile_n=spec.tile_n, buffer_bytes=spec.buffer_bytes,
        )
        stats_box.append(stats)
        return o

    return quantized_lut_gemm(q, x, run), stats_box[0]


def stream_stats_for(q, x: Array, *, plan_only: bool = False) -> engine.StreamStats:
    """Simulated DRAM→buffer traffic of serving ``x`` through ``q`` with the
    slice-streaming dataflow (regardless of ``q.spec.mode``).

    ``plan_only=True`` skips the GEMM entirely: quantize the activations,
    run the stream planner, and derive every stat by counter arithmetic
    (:func:`repro.core.engine.stream_plan_stats`) — same numbers, no compute.
    Accepts a raw :class:`QuantizedLinear` or a prepared layer.
    """
    from repro.core import prepared as _prepared

    if plan_only:
        spec = q.spec
        xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        acodes, _ = quantize(xf.T, spec.aspec(),
                             scale=getattr(q, "ascale", None))
        if isinstance(q, _prepared.PreparedLinear):
            p = q.p
        else:
            p = plan_p(q.f, q.k, xf.shape[0], spec)
        pack = _lut_pack_cache(spec.bw, spec.ba, p, spec.w_kind, spec.a_kind)
        return engine.stream_plan_stats(
            q.f, np.asarray(acodes), pack,
            tile_n=spec.tile_n, buffer_bytes=spec.buffer_bytes,
        )
    if isinstance(q, _prepared.PreparedLinear):
        _, stats = _prepared.stream_matmul(q, x)
        return stats
    _, stats = _stream_matmul(q, x)
    return stats


def prepare_linear(q: QuantizedLinear, **kw):
    """Freeze ``q``'s weight-side serve products into a weight-stationary
    :class:`repro.core.prepared.PreparedLinear` (see that module's docstring
    for the cached-product → paper-step map)."""
    from repro.core import prepared as _prepared

    return _prepared.prepare_linear(q, **kw)


@functools.lru_cache(maxsize=64)
def _lut_pack_cache(bw: int, ba: int, p: int, w_kind: str, a_kind: str):
    return luts.build_lut_pack(bw, ba, p, w_kind=w_kind, a_kind=a_kind)
