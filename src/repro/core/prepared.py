"""Weight-stationary prepared layers: the prepare/apply split.

The paper's deployment regime (§IV-A step 1, §V-B) ships quantized, packed
weights to the PIM banks **once**; only activations move at serve time.  The
seed engine redid every weight-side step per ``apply_linear`` call.  A
:class:`PreparedLinear` caches each of those products once, trading a small
amount of memory for *all* per-call weight work — the reordering-LUT idea
(§IV-B: spend ``2^(bw p) * p!`` table bytes to avoid runtime permutation
work) applied one level up.  Cached product → paper step it replaces:

Each product is cached only for the execution mode(s) whose apply path
consumes it (pallas already feeds on the packed codes directly):

===================  =====================================================
cached product       paper step it replaces at serve time
===================  =====================================================
``wcodes [F, K]``    unpacking the bit-packed DRAM weight words back into
                     codes (§V-A layout step; ``packing.unpack_bits``) —
                     ``mode="dequant"``
``wpk [F, G]``       grouping K into packs of p and packing each group's
                     codes into a LUT row index (§III-A operation packing;
                     ``packing.pack_index``) — ``mode="lut"``/``"stream"``
``p`` (+ LUT key)    the host-side Eq. 2/4 sweep picking ``p*`` and the
                     canonical/reordering LUT build (§IV-D, §V-A;
                     ``perfmodel.make_plan`` + ``luts.build_lut_pack``)
``wcanon [F,G,p!]``  the reordering-LUT lookup itself (§IV-B Fig. 5 step 3):
                     ``wcanon[m, g, pid] == reorder[wpk[m, g], pid]`` for
                     every permutation id, so serve time is pure canonical
                     gathers — a weight-static reordering LUT (built only
                     for ``mode="lut"``, its sole consumer, and capped)
``onehot [F, G*R]``  rebuilding the exact one-hot contraction matrix the
                     streamed engine's BLAS path uses (§IV-C Fig. 7 reuse;
                     ``mode="stream"`` only)
===================  =====================================================

``prepare_linear`` freezes the products; :func:`apply_prepared` is the
serve-time fast path for all four execution modes and is bit-identical to
``apply_linear`` on the raw :class:`~repro.core.api.QuantizedLinear`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, packing
from repro.core.api import LutLinearSpec, QuantizedLinear, _lut_pack_cache
from repro.core.quantize import quantize

Array = jax.Array

# Entry cap for the weight-static canonical table [F, G, p!]: above this the
# capacity side of the tradeoff stops paying (p=8 would need 40320 cols/group)
# and apply falls back to the shared reordering LUT.
WCANON_MAX_ENTRIES = 32_000_000


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PreparedLinear:
    """Pytree carrying one linear layer's weight-stationary serve products.

    ``onehot`` stays a host (numpy) array — it feeds the streamed engine's
    host-simulated dataflow and never crosses a jit boundary.
    """

    codes: Array                       # [F, K*bw/8] uint8 packed (pallas path)
    scale: Array                       # [F] fp32 per-output-channel scale
    bias: Optional[Array]              # [F] or None
    wcodes: Optional[Array]            # [F, K] uint8 codes (dequant mode)
    wpk: Optional[Array]               # [F, G] int32 indices (lut/stream)
    wcanon: Optional[Array]            # [F, G, p!] int32 reorder table (lut)
    onehot: Optional[np.ndarray]       # [F, G*R] f32 (stream mode only)
    spec: LutLinearSpec = dataclasses.field(
        metadata=dict(static=True), default=LutLinearSpec()
    )
    k: int = dataclasses.field(metadata=dict(static=True), default=0)
    p: int = dataclasses.field(metadata=dict(static=True), default=1)
    # Frozen per-tensor activation scale (scalar; [stack] on scanned leaves,
    # sliced to a scalar per unit).  When set, the lut/stream activation
    # quantizer uses it instead of the dynamic per-batch max — outputs become
    # batch-composition invariant, the precondition for bit-exact replay
    # across a restart's re-bucketed batches (repro.core.calibrate).
    # dequant/pallas are float matmuls and ignore it.
    ascale: Optional[Array] = None

    @property
    def f(self) -> int:
        return self.codes.shape[0]

    @property
    def g(self) -> int:
        return (self.k + (-self.k) % self.p) // self.p

    @property
    def prepared_bytes(self) -> int:
        """Extra bytes the prepare/apply tradeoff spends on this layer."""
        total = 0
        for a in (self.wcodes, self.wpk, self.wcanon, self.onehot):
            if a is not None:
                total += int(np.prod(a.shape)) * a.dtype.itemsize
        return total


def _pack_for(pl: PreparedLinear):
    return _lut_pack_cache(
        pl.spec.bw, pl.spec.ba, pl.p, pl.spec.w_kind, pl.spec.a_kind
    )


def prepare_linear(
    q: QuantizedLinear,
    *,
    n_hint: int = 128,
    wcanon_max_entries: int = WCANON_MAX_ENTRIES,
    host_products: bool = True,
    calibration: Optional[Array] = None,
    ascale: Optional[Array] = None,
) -> PreparedLinear:
    """Freeze every weight-side product of ``q`` into a :class:`PreparedLinear`.

    ``n_hint`` is the activation-column count the Eq. 2/4 sweep plans ``p*``
    for when ``q.spec.p`` is ``None`` (weights are stationary, so the batch
    width must be assumed up front; any value is bit-exact — it only steers
    performance).  ``host_products=False`` skips the numpy-side one-hot build
    — required when this function runs under ``vmap`` over stacked layers
    (:func:`repro.models.model.prepare_params`), where tracers cannot leave
    the device.

    ``calibration`` freezes the activation scale from a representative batch
    ``[..., K]`` — the exact scale the dynamic quantizer would pick for that
    batch, so prepared apply on the calibration batch stays bit-identical to
    dynamic apply while becoming batch-composition invariant everywhere.
    ``ascale`` installs an already-captured frozen scale (e.g. from
    :mod:`repro.core.calibrate`); mutually exclusive with ``calibration``.
    """
    spec = q.spec
    if calibration is not None and ascale is not None:
        raise ValueError("pass calibration or ascale, not both")
    if calibration is not None:
        cf = calibration.reshape(-1, calibration.shape[-1]).astype(jnp.float32)
        _, ascale = quantize(cf.T, spec.aspec())
    if ascale is None:
        ascale = getattr(q, "ascale", None)
    if ascale is not None:
        ascale = jnp.asarray(ascale, jnp.float32)
    if q.codes.ndim != 2:
        raise ValueError(
            f"prepare_linear handles single layers ([F, KB] codes); got "
            f"{q.codes.ndim}-d codes — vmap it over the stack "
            f"(see repro.models.model.prepare_params)"
        )
    from repro.core.api import plan_p

    # p* is planned for every mode (pure Python, microseconds) so serve-time
    # stats/plan queries on any prepared layer agree with the raw path; the
    # expensive products below are gated on the mode that consumes them —
    # pallas keeps just the packed codes the kernel already eats.
    p = plan_p(q.f, q.k, n_hint, spec)
    wcodes = wpk = onehot = wcanon = None
    if spec.mode in ("dequant", "lut", "stream"):
        wcodes = packing.unpack_bits(q.codes, spec.bw)[:, : q.k]      # [F, K]
    if spec.mode in ("lut", "stream"):
        pack = _lut_pack_cache(spec.bw, spec.ba, p, spec.w_kind, spec.a_kind)
        if spec.mode == "stream" and host_products:
            # Host path: one prepare_stream_weights call yields both the
            # packed group indices and the one-hot contraction matrix.  Both
            # stay numpy — the streamed engine only ever consumes host
            # arrays, so apply-time np.asarray(wpk) is a zero-copy view.
            sw = engine.prepare_stream_weights(np.asarray(wcodes), pack)
            wpk = sw.wpk                                              # [F, G]
            onehot = sw.onehot
        else:
            pad, cw, _, _ = engine.pad_info(q.k, p, pack.wgrid, pack.agrid)
            wc_pad = wcodes
            if pad:
                wc_pad = jnp.pad(
                    wcodes, ((0, 0), (0, pad)), constant_values=cw
                )
            g = wc_pad.shape[1] // p
            wpk = packing.pack_index(wc_pad.reshape(q.f, g, p), spec.bw)
        if (
            spec.mode == "lut"
            and q.f * wpk.shape[1] * math.factorial(p) <= wcanon_max_entries
        ):
            # Weight-static reordering table, stored in the int32 the
            # canonical gather wants so apply pays no per-call cast; above
            # the cap the lut path falls back to the shared LUT via wpk.
            wcanon = jnp.asarray(pack.reordering.astype(np.int32))[wpk]
    return PreparedLinear(
        codes=q.codes,
        scale=q.scale,
        bias=q.bias,
        wcodes=wcodes.astype(jnp.uint8) if spec.mode == "dequant" else None,
        wpk=wpk,
        wcanon=wcanon,
        onehot=onehot,
        spec=spec,
        k=q.k,
        p=p,
        ascale=ascale,
    )


def stream_weights(pl: PreparedLinear) -> engine.StreamWeights:
    """Rehydrate the streamed engine's :class:`~repro.core.engine.StreamWeights`
    from the cached products (no unpack/pack/one-hot recompute).

    Prepared layers of other modes don't carry ``wpk`` — for those (e.g.
    traffic queries via ``stream_stats_for`` on a dequant-mode layer) the
    stream products are built from the packed codes on the fly.
    """
    pack = _pack_for(pl)
    if pl.wpk is None:
        wcodes = np.asarray(packing.unpack_bits(pl.codes, pl.spec.bw))[:, : pl.k]
        return engine.prepare_stream_weights(wcodes, pack)
    pad, _, _, corr = engine.pad_info(pl.k, pl.p, pack.wgrid, pack.agrid)
    return engine.StreamWeights(
        wpk=np.asarray(pl.wpk),
        onehot=pl.onehot,
        m=pl.f,
        g=pl.g,
        r=pack.n_rows,
        pad=pad,
        corr=corr,
    )


def apply_prepared(pl: PreparedLinear, x: Array) -> Array:
    """``y = x @ W (+ bias)`` through the cached weight-stationary products.

    Bit-identical to ``apply_linear`` on the raw layer in every mode — only
    the per-call weight work disappears.
    """
    mode = pl.spec.mode
    if mode == "dequant":
        y = _dequant_matmul(pl, x)
    elif mode == "lut":
        y = _lut_matmul(pl, x)
    elif mode == "stream":
        y, _ = stream_matmul(pl, x)
    elif mode == "pallas":
        from repro.kernels import ops  # local import: kernels are optional

        y = ops.lut_dequant_gemm(
            x.reshape(-1, x.shape[-1]),
            pl.codes,
            pl.scale,
            bw=pl.spec.bw,
            k=pl.k,
            grid_kind=pl.spec.w_kind,
        ).reshape(x.shape[:-1] + (pl.f,)).astype(x.dtype)
        # ^ kernel accumulates f32; cast back like every other mode so a
        #   bf16 model's residual stream keeps its dtype through the scan.
    else:
        raise ValueError(f"unknown mode {mode}")
    if pl.bias is not None:
        y = y + pl.bias.astype(y.dtype)
    return y


def _dequant_matmul(pl: PreparedLinear, x: Array) -> Array:
    grid = jnp.asarray(pl.spec.wspec().grid(), dtype=x.dtype)
    w_t = grid[pl.wcodes.astype(jnp.int32)] * pl.scale[:, None].astype(x.dtype)
    return jnp.einsum("...k,fk->...f", x, w_t)


def _lut_matmul(pl: PreparedLinear, x: Array) -> Array:
    from repro.core.api import quantized_lut_gemm

    pack = _pack_for(pl)
    return quantized_lut_gemm(
        pl, x,
        lambda acodes, n: engine.canonical_lut_gemm(
            None, acodes, pack, wpacked=pl.wpk, wcanon_table=pl.wcanon
        ),
    )


def stream_matmul(
    pl: PreparedLinear, x: Array
) -> tuple[Array, engine.StreamStats]:
    from repro.core.api import quantized_lut_gemm

    spec = pl.spec
    pack = _pack_for(pl)
    stats_box = []

    def run(acodes, n):
        o, stats = engine.streamed_lut_gemm(
            None, acodes, pack,
            tile_n=spec.tile_n, buffer_bytes=spec.buffer_bytes,
            prep=stream_weights(pl),
        )
        stats_box.append(stats)
        return o

    return quantized_lut_gemm(pl, x, run), stats_box[0]
