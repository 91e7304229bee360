"""TPU Pallas kernel v2: tiled canonical-LUT **slice streaming** GEMM.

This kernel maps the paper's §IV-C dataflow natively onto the TPU memory
hierarchy:

* the canonical LUT and the reordering LUT live in **HBM** (the "DRAM bank"),
* the grid runs over ``(N-tiles, G)``; each step streams the ``NT``
  canonical-LUT columns and ``NT`` reordering-LUT columns addressed by the
  tile's activation columns at K-group ``g`` into **VMEM** (the "local
  buffer") via **scalar-prefetched, data-dependent BlockSpec index maps** —
  Pallas's pipelined block fetch plays the role of the paper's slice
  streaming, with double-buffering as the overlap the paper gets from its
  3-stage pipelined bank access,
* the streamed slices are reused across **all M weight rows** before the
  grid advances (LUT-stationary reuse, paper Fig. 7).

v2 replaces v1's per-lookup ``[R, R]`` one-hot permutation matmul with
**index composition**: the reordering lookup is folded into the canonical
gather at the slice level,

    composed[r, t] = canon_cols[reorder_cols[r, t], t]        # [R, NT] gather

so only one ``[M, R]·[R, NT]`` one-hot contraction remains per grid step,
accumulated in **int32** (bit-exact for integer LUT packs):

    out[:, tile] += onehot(w_codes) @ composed                # [M, NT]

v1 streamed one column pair per step and burned an ``[R, R]`` matmul plus an
f32 accumulator per lookup; v2 amortizes the weight one-hot over NT columns
and does no permutation matmul at all.

The kernel runs in interpret mode only.  Its ``(M, 1)`` weight-column and
``(R, 1)`` LUT-slice blocks are one lane wide, which breaks the TPU rule that
a block's last two dims be multiples of (8, 128); on TPU it raises
``NotImplementedError``.  No serving path uses it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

Array = jax.Array


def _stream_kernel_body(
    ms_ref,          # scalar-prefetch [T*G*NT] int32 (unused in body; drives specs)
    pid_ref,         # scalar-prefetch [T*G*NT] int32 (unused in body; drives specs)
    wpacked_ref,     # [M, 1] int32 (block: weight column g)
    *refs,           # NT canonical [R,1] + NT reordering [R,1] slices + out
    r: int,
    nt: int,
):
    canon_refs = refs[:nt]
    reorder_refs = refs[nt : 2 * nt]
    out_ref = refs[2 * nt]
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    ccols = jnp.concatenate([c[...] for c in canon_refs], axis=1)      # [R, NT]
    rcols = jnp.concatenate([c[...] for c in reorder_refs], axis=1)    # [R, NT]
    # Index composition (no [R, R] one-hot): fold the reordering lookup into
    # the canonical gather — composed[r, t] = ccols[rcols[r, t], t].
    composed = jnp.take_along_axis(ccols, rcols, axis=0)               # [R, NT]
    wcol = wpacked_ref[...][:, 0]                                      # [M]
    iota_mr = jax.lax.broadcasted_iota(jnp.int32, (wcol.shape[0], r), 1)
    onehot_w = (wcol[:, None] == iota_mr).astype(jnp.int32)            # [M, R]
    vals = jax.lax.dot_general(
        onehot_w, composed, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                                                  # [M, NT]
    out_ref[...] += vals


def _slice_index_map(j: int, gdim: int, nt: int):
    """Index map streaming the j-th slice of the (tile, group) step."""

    def index_map(ti, gi, ms, pid):
        del pid
        return (0, ms[(ti * gdim + gi) * nt + j])

    return index_map


def _reorder_index_map(j: int, gdim: int, nt: int):
    def index_map(ti, gi, ms, pid):
        del ms
        return (0, pid[(ti * gdim + gi) * nt + j])

    return index_map


@functools.partial(jax.jit, static_argnames=("r", "nt", "interpret"))
def lut_stream_gemm(
    wpacked: Array,     # [M, G] int32 packed weight codes
    msrank: Array,      # [G, N] int32 canonical-LUT column ids
    permid: Array,      # [G, N] int32 reordering-LUT column ids
    canonical: Array,   # [R, C] int32 LUT (stays in HBM; columns streamed)
    reordering: Array,  # [R, P!] int32 LUT (stays in HBM; columns streamed)
    *,
    r: int,
    nt: int = 8,
    interpret: bool | None = None,
) -> Array:
    """Tiled slice-streaming canonical-LUT GEMM; returns int32 [M, N].

    Semantics match :func:`repro.kernels.ref.lut_stream_gemm_ref` exactly
    (int32 partial-product accumulation).  ``nt`` is the N-tile width: slices
    streamed (and output columns produced) per grid step.  ``interpret=None``
    takes the platform's choice (:func:`repro.kernels.interpret_mode`).
    """
    if interpret is None:
        interpret = interpret_mode()
    if not interpret:
        raise NotImplementedError(
            "lut_stream_gemm does not compile for TPU: its (M, 1) weight-column "
            "and (R, 1) LUT-slice blocks break the (8, 128) block rule; it runs "
            "in interpret mode on CPU only"
        )
    m, gdim = wpacked.shape
    n = msrank.shape[1]
    nt = max(1, min(nt, n))
    ntiles = -(-n // nt)
    npad = ntiles * nt - n
    if npad:
        # Pad with column-0 ids: valid addresses, padded outputs sliced away.
        msrank = jnp.pad(msrank, ((0, 0), (0, npad)))
        permid = jnp.pad(permid, ((0, 0), (0, npad)))
    # Scalar prefetch wants flat int32 vectors indexed by (tile, g, j).
    ms_flat = msrank.reshape(gdim, ntiles, nt).transpose(1, 0, 2).reshape(-1)
    pid_flat = permid.reshape(gdim, ntiles, nt).transpose(1, 0, 2).reshape(-1)

    in_specs = [
        # weight column g: [M, 1]
        pl.BlockSpec((m, 1), lambda ti, gi, ms, pid: (0, gi)),
    ]
    in_specs += [
        pl.BlockSpec((r, 1), _slice_index_map(j, gdim, nt)) for j in range(nt)
    ]
    in_specs += [
        pl.BlockSpec((r, 1), _reorder_index_map(j, gdim, nt)) for j in range(nt)
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ntiles, gdim),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, nt), lambda ti, gi, ms, pid: (0, ti)),
    )
    lut_args = [canonical] * nt + [reordering] * nt
    out = pl.pallas_call(
        functools.partial(_stream_kernel_body, r=r, nt=nt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, ntiles * nt), jnp.int32),
        interpret=interpret,
    )(ms_flat, pid_flat, wpacked, *lut_args)
    return out[:, :n]
