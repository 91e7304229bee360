"""TPU Pallas kernel: flash attention (online-softmax, scores never in HBM).

The §Roofline analysis shows every prefill cell is dominated by attention
score traffic — the XLA path materializes [chunk, S] score tensors to HBM.
This kernel is the structural fix: Q/K/V stream through VMEM in MXU-aligned
blocks, the running max/sum/accumulator live in VMEM scratch, and only the
[S, hd] output returns to HBM.  Per-chip attention HBM traffic drops from
O(S²·H·B) to O(S·H·B·hd).

Supports causal masking, sliding windows (gemma2 local layers) and logit
softcap.  GQA is handled by the K/V BlockSpec index maps (q-head → kv-head).

Grid: (B·H, S/blk_q, T/blk_k), k-blocks innermost; the classic two-pass-free
online softmax:

    m' = max(m, rowmax(s))        l' = l·e^{m-m'} + rowsum(e^{s-m'})
    acc' = acc·e^{m-m'} + e^{s-m'} @ V

Validated in interpret mode against the pure-jnp oracle across
shape/window/softcap sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

Array = jax.Array

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_body(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, blk_q: int, blk_k: int, nk: int, causal: bool,
    window, softcap, scale: float,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * blk_q
    k_start = ik * blk_k
    # Fully-masked block? (causal: keys strictly after the last query)
    run = True
    if causal:
        run = k_start <= q_start + blk_q - 1

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # [blk_q, hd]
        k = k_ref[0].astype(jnp.float32)                    # [blk_k, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                            # [blk_q, blk_k]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = jnp.ones((blk_q, blk_k), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                  # [blk_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "blk_q", "blk_k", "interpret"),
)
def flash_attention(
    q: Array,   # [B, S, H, hd]
    k: Array,   # [B, T, Hkv, hd]
    v: Array,   # [B, T, Hkv, hd]
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    blk_q: int = DEFAULT_BLOCK_Q,
    blk_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> Array:
    """Attention over ``q [B, S, H, hd]`` and ``k``/``v [B, T, Hkv, hd]``.

    ``interpret=None`` takes the platform's choice
    (:func:`repro.kernels.interpret_mode`).
    """
    if interpret is None:
        interpret = interpret_mode()
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, t)
    pq, pk = (-s) % blk_q, (-t) % blk_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        # padded keys sit at positions >= t; causal/window masks never reach
        # them for real queries, and padded queries are sliced away below.
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sq, st = s + pq, t + pk
    nq, nk = sq // blk_q, st // blk_k

    # [B, S, H, hd] -> [B*H, S, hd] with h-major so kv-head mapping is h//rep
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, st, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, st, hd)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        return ((bh // h) * hkv + (bh % h) // rep, ik, 0)

    scratch = [
        pltpu.VMEM((blk_q, 1), jnp.float32),
        pltpu.VMEM((blk_q, 1), jnp.float32),
        pltpu.VMEM((blk_q, hd), jnp.float32),
    ]
    out = pl.pallas_call(
        functools.partial(
            _flash_body, blk_q=blk_q, blk_k=blk_k, nk=nk, causal=causal,
            window=window, softcap=softcap, scale=1.0 / float(np.sqrt(hd)),
        ),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), q_map),
            pl.BlockSpec((1, blk_k, hd), kv_map),
            pl.BlockSpec((1, blk_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, blk_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
    return out[:, :s]
