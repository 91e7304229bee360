"""TPU Pallas kernel: packed low-bit-code GEMM with in-kernel value-LUT decode.

This is LoCaLUT's capacity↔computation tradeoff re-instantiated for the TPU
memory hierarchy (DESIGN.md §2.1): weights live in HBM as bit-packed ``bw``-bit
codes (16/bw× fewer bytes than bf16) and are decoded *inside* the kernel
through a tiny value LUT — the code→value table that defines the numeric
format, exactly the paper's format-flexibility argument.  The MXU supplies the
"free" arithmetic that the DRAM-PIM design had to buy with LUT capacity.

Dataflow per grid step (i, j, kk):

    HBM ──codes tile [bF, bKc] (uint8)──▶ VMEM      (Pallas double-buffers)
    per bit plane q < 8/bw (byte bits [q·bw, (q+1)·bw) hold codes k = j·cpb + q):
      VMEM: decode = Σ_c grid[c]·(plane==c)  — a 2^bw-term one-hot
            contraction, i.e. the *lookup performed as compute* (VPU), no gather
      MXU : acc[bB, bF] += x_q[bB, bKc] @ w_q[bF, bKc]^T
    last kk: out = acc * scale[bF]

Each bit plane decodes as its own ``[bF, bKc]`` tile, so the kernel never
reshapes a vector (Mosaic refuses the ``[bF, bKc, cpb] -> [bF, bK]`` unpack).
The wrapper reorders x's columns per K block so that plane q's columns are
the contiguous slice ``x_blk[:, q·bKc:(q+1)·bKc]``.  ``bKc`` is a multiple of
128 lanes and ``bF``/``bB`` of 8 sublanes, the TPU block rule.

The K (contraction) axis is the innermost grid dimension; the f32 accumulator
lives in the revisited output block."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

Array = jax.Array

# Default tile sizes (MXU-aligned; VMEM footprint per step ≈
# bB*bK*4 + bF*bK/cpb*(1+4) + bB*bF*4 ≈ 1 MB at 128/256/512 — far below VMEM).
# ``block_k`` is a hint: the kernel rounds ``block_k // cpb`` (the packed
# columns per step) to a multiple of 128 lanes.
DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_F = 256
DEFAULT_BLOCK_K = 512
_LANES = 128


def _decode_kernel_body(
    x_ref, codes_ref, scale_ref, out_ref, *, bw: int, grid_values: tuple, nk: int
):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...].astype(jnp.int32)       # [bF, bKc]
    bkc = codes.shape[1]
    mask = (1 << bw) - 1
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for q in range(8 // bw):
        plane = (codes >> (q * bw)) & mask          # codes k = j*cpb + q
        # Value-LUT decode as a one-hot contraction (lookup-as-compute).
        w_t = jnp.zeros(plane.shape, dtype=jnp.float32)
        for c, v in enumerate(grid_values):
            w_t += jnp.float32(v) * (plane == c).astype(jnp.float32)
        x = x_ref[:, q * bkc:(q + 1) * bkc].astype(jnp.float32)   # [bB, bKc]
        acc += jax.lax.dot_general(
            x,
            w_t,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [bB, bF]
    out_ref[...] += acc

    @pl.when(kk == nk - 1)
    def _scale():
        out_ref[...] = out_ref[...] * scale_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bw", "k", "grid_values", "block_b", "block_f", "block_k", "interpret"),
)
def lut_dequant_gemm(
    x: Array,
    codes: Array,
    scale: Array,
    *,
    bw: int,
    k: int,
    grid_values: tuple,
    block_b: int = DEFAULT_BLOCK_B,
    block_f: int = DEFAULT_BLOCK_F,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> Array:
    """``y[B,F] = x[B,K] @ (grid[codes] * scale)[F,K]^T``.

    ``codes`` is the bit-packed ``[F, ceil(K/cpb)]`` uint8 weight storage of a
    :class:`repro.core.api.QuantizedLinear`.  Padding to block multiples is
    handled here; the caller passes logical sizes.  ``interpret=None`` takes
    the platform's choice (:func:`repro.kernels.interpret_mode`).
    """
    if interpret is None:
        interpret = interpret_mode()
    b, k_in = x.shape
    f = codes.shape[0]
    cpb = 8 // bw
    assert k_in == k

    kc = -(-k // cpb)                                # packed columns
    block_kc = max(_LANES, block_k // cpb // _LANES * _LANES)
    block_kc = min(block_kc, -(-kc // _LANES) * _LANES)
    block_k = block_kc * cpb
    block_b = min(block_b, max(8, 1 << (b - 1).bit_length()))
    block_f = min(block_f, max(8, 1 << (f - 1).bit_length()))

    pb, pf, pk = (-b) % block_b, (-f) % block_f, (-k) % block_k
    if pb or pk:
        x = jnp.pad(x, ((0, pb), (0, pk)))
    if pf or pk:
        codes = jnp.pad(codes, ((0, pf), (0, pk // cpb)))
        scale = jnp.pad(scale, (0, pf))
    bb, ff, kk = b + pb, f + pf, k + pk
    nk = kk // block_k
    # Within each K block, put bit plane q's columns (k = j*cpb + q) together.
    x = x.reshape(bb, nk, block_kc, cpb).transpose(0, 1, 3, 2).reshape(bb, kk)

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel_body, bw=bw, grid_values=grid_values, nk=nk
        ),
        grid=(bb // block_b, ff // block_f, nk),
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, j, kk_: (i, kk_)),
            pl.BlockSpec((block_f, block_kc), lambda i, j, kk_: (j, kk_)),
            pl.BlockSpec((1, block_f), lambda i, j, kk_: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_f), lambda i, j, kk_: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bb, ff), jnp.float32),
        interpret=interpret,
    )(x, codes, scale.reshape(1, ff))
    return out[:b, :f]
