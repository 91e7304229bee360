"""Jitted wrappers around the Pallas kernels.

These are the entry points the rest of the framework uses; each dispatches to
the Pallas kernel (compiled on TPU, interpreted on CPU — the choice is
:func:`repro.kernels.interpret_mode`'s) and owns the host-side preparation the
paper assigns to the host CPU (activation quantization, canonicalization, LUT
construction).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, luts, packing
from repro.core.quantize import QuantSpec, quantize
from repro.kernels import lut_dequant_gemm as _dq
from repro.kernels import lut_stream_gemm as _ss

Array = jax.Array


def lut_dequant_gemm(
    x: Array,
    codes: Array,
    scale: Array,
    *,
    bw: int,
    k: int,
    grid_kind: str = "int",
    **block_kw,
) -> Array:
    """Packed-code GEMM (TPU-optimized path).  x [B,K] -> y [B,F]."""
    grid = QuantSpec(bw, grid_kind).grid()
    return _dq.lut_dequant_gemm(
        x,
        codes,
        scale,
        bw=bw,
        k=k,
        grid_values=tuple(float(v) for v in np.asarray(grid)),
        **block_kw,
    )


def lut_stream_gemm_full(
    wcodes: Array,
    acodes: Array,
    pack: luts.LutPack,
    *,
    nt: int = 8,
) -> Array:
    """Paper-faithful slice-streaming GEMM from raw codes (Pallas kernel v2).

    Performs the host-side steps (§IV-A step 1: canonicalize + index), then
    launches the tiled streaming kernel (``nt`` output columns and streamed
    slice pairs per grid step, int32 accumulation).  Returns the int-exact
    GEMM as float32.
    """
    if pack.canonical.dtype.kind not in "iu":
        raise ValueError(
            "lut_stream_gemm_full accumulates in int32; float-grid packs run "
            "through engine.streamed_lut_gemm instead"
        )
    p = pack.p
    wcodes, acodes, corr = engine._pad_groups(
        wcodes, acodes, p, pack.wgrid, pack.agrid
    )
    idx = engine.canonicalize_activations(acodes, pack)
    m, k = wcodes.shape
    g = k // p
    wpacked = packing.pack_index(wcodes.reshape(m, g, p), pack.bw)
    out = _ss.lut_stream_gemm(
        wpacked,
        idx.msrank,
        idx.permid,
        jnp.asarray(pack.canonical.astype(np.int32)),
        jnp.asarray(pack.reordering.astype(np.int32)),
        r=pack.n_rows,
        nt=nt,
    )
    return (out - corr).astype(jnp.float32)
