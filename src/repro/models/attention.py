"""Attention variants: GQA (w/ sliding window + logit softcap), MLA, cross.

All functions are cache-aware: ``cache=None`` runs full-sequence (train /
prefill-style) attention; otherwise ``cache`` is a dict of :class:`LayerRows`,
the layer's rows inside its segment's stacked buffers, written in place at
``pos`` (decode).  MLA caches the *compressed* latent (DeepSeek-style
absorbed formulation), which is what makes the 32k decode cells of
deepseek-v2-lite cheap on HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, linear
from repro.obs import scopes

Array = jax.Array


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(cfg: ModelConfig, key, *, cross: bool = False) -> dict:
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model),
    }
    return p


def _split_heads(x: Array, n: int) -> Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1)


@dataclasses.dataclass(frozen=True)
class LayerRows:
    """One layer's cache leaf where it lives: ``buf [n_units, B, T, ...]`` is
    the segment's stacked buffer and ``layer`` the (traced) unit index.

    Writes scatter the new rows straight into ``buf`` and reads slice the
    layer out where it is consumed, so no whole-layer copy of the cache is
    made per step (the layer scan carries ``buf``).  ``shape`` and ``dtype``
    are the layer's own.
    """

    buf: Array
    layer: Array

    @property
    def shape(self) -> tuple:
        return self.buf.shape[1:]

    @property
    def dtype(self):
        return self.buf.dtype

    def read(self) -> Array:
        return jax.lax.dynamic_index_in_dim(self.buf, self.layer, 0, keepdims=False)


@scopes.scoped(scopes.KV_WRITE)
def _cache_write(rows: LayerRows, new: Array, pos) -> LayerRows:
    """Write ``new [B, S, ...]`` into the layer's ``rows`` at sequence offset
    ``pos``.

    ``pos`` may be a scalar (all rows share the offset — prefill and chunked
    decode) or a ``[B]`` vector of per-slot offsets (continuous-batching
    decode, where ``S == 1`` and every slot sits at its own depth).
    """
    p = jnp.asarray(pos)
    new = new.astype(rows.dtype)
    if p.ndim:
        b = new.shape[0]
        buf = rows.buf.at[rows.layer, jnp.arange(b), p].set(new[:, 0])
    else:
        starts = (rows.layer, 0, p) + (0,) * (rows.buf.ndim - 3)
        buf = jax.lax.dynamic_update_slice(rows.buf, new[None], starts)
    return dataclasses.replace(rows, buf=buf)


def _key_mask(kpos: Array, qpos: Array, pad_len, window) -> Array:
    """Causal key-validity mask in *logical* coordinates.

    ``kpos`` are buffer key positions ``[1, T]``; ``qpos`` logical query
    positions ``[B, S, 1]``.  With left-padding, ``pad_len [B]`` shifts keys
    into logical coordinates (buffer - pad) and masks the pad positions out
    entirely (logical < 0) — don't-care positions, like ReducedLUT's
    don't-care LUT entries: present in the buffer, never attended.
    """
    if pad_len is not None:
        kpos = kpos - pad_len[:, None]
    k = kpos[:, None, :]                                   # [B|1, 1, T]
    m = k <= qpos
    if pad_len is not None:
        m &= k >= 0
    if window is not None:
        m &= k > qpos - window
    return m


def _attend(
    q: Array,            # [B, S, H, hd]
    k: Array,            # [B, T, Hkv, hd]
    v: Array,            # [B, T, Hkv, hd]
    *,
    mask: Array,         # [B, 1, S, T] or broadcastable boolean
    softcap_val: Optional[float],
    bf16_operands: bool = False,
) -> Array:
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    qg = q.reshape(b, s, hkv, rep, hd)
    if bf16_operands:
        # Mixed-precision attend (§Perf): keep Q/K/V + probabilities in bf16
        # with f32 MXU accumulation — no f32 copy of the (cache-sized) K/V
        # ever materializes.  This is the TPU-canonical formulation.
        scores = jnp.einsum(
            "bsgrd,btgd->bgrst", qg.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ) / jnp.sqrt(hd).astype(jnp.float32)
        scores = layers.softcap(scores, softcap_val)
        scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(jnp.bfloat16)
        out = jnp.einsum(
            "bgrst,btgd->bsgrd", w, v.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, s, h, hd).astype(q.dtype)
    scores = jnp.einsum(
        "bsgrd,btgd->bgrst", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / jnp.sqrt(hd).astype(jnp.float32)
    scores = layers.softcap(scores, softcap_val)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", w, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def causal_mask(s: int, t: int, *, offset: int = 0, window: Optional[int] = None):
    """[1, 1, s, t] boolean; query i (global pos offset+i) sees keys <= it."""
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m[None, None]


# Above this many query positions, full-sequence attention runs in query
# chunks (lax.scan) so scores never materialize at [S, S] — required for the
# 32k prefill cells (an [B,H,32k,32k] f32 score tensor is terabytes).
CHUNK_THRESHOLD = 4096
CHUNK_SIZE = 512


def _attend_chunked(
    q: Array,            # [B, S, H, hd]
    k: Array,            # [B, T, Hkv, hd]
    v: Array,
    positions: Array,    # [B, S] query positions (logical)
    *,
    window: Optional[int],
    softcap_val: Optional[float],
    causal: bool,
    bf16_operands: bool = False,
    pad_len: Optional[Array] = None,   # [B] left-pad lengths (key don't-cares)
) -> Array:
    b, s, h, hd = q.shape
    nc = s // CHUNK_SIZE
    qc = q.reshape(b, nc, CHUNK_SIZE, h, hd)
    pc = positions.reshape(b, nc, CHUNK_SIZE)

    def body(_, inp):
        q_i, pos_i = inp                                   # [B, C, H, hd], [B, C]
        kpos = jnp.arange(k.shape[1])[None, :]
        if causal:
            m = _key_mask(kpos, pos_i[:, :, None], pad_len, window)
        else:
            m = jnp.ones((b, CHUNK_SIZE, k.shape[1]), bool)
            if window is not None:
                m &= kpos[:, None, :] > pos_i[:, :, None] - window
        o = _attend(q_i, k, v, mask=m[:, None], softcap_val=softcap_val,
                    bf16_operands=bf16_operands)
        return None, o

    from repro import flags

    _, outs = jax.lax.scan(
        body, None, (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(pc, 1, 0)),
        unroll=flags.scan_unroll(),
    )
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, hd)


def _quant_rows(x: Array) -> tuple[Array, Array]:
    """Symmetric int8 per-(token, head) row quantization: [B,S,H,hd] ->
    (int8 codes, f32 scales [B,S,H])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    codes = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return codes, scale


def _ring_update(rows: LayerRows, new: Array, global_start, tail: int) -> LayerRows:
    """Write the last ``tail`` tokens of ``new`` into the layer's ring buffer
    at their ``global_position % W`` slots.  ``global_start`` may be a
    per-slot ``[B]`` vector (continuous-batching decode)."""
    w = rows.shape[1]
    gs = jnp.reshape(jnp.asarray(global_start), (-1, 1))            # [B|1, 1]
    idx = (gs + jnp.arange(tail)[None, :]) % w                      # [B|1, tail]
    b = new.shape[0]
    buf = rows.buf.at[rows.layer, jnp.arange(b)[:, None], idx].set(
        new[:, -tail:].astype(rows.dtype)
    )
    return dataclasses.replace(rows, buf=buf)


@scopes.scoped(scopes.ATTENTION)
def gqa_attention(
    p: dict,
    x: Array,
    *,
    cfg: ModelConfig,
    positions: Array,                  # [B, S] logical positions (RoPE + mask)
    cache: Optional[dict] = None,      # {"k": LayerRows [B, Smax, Hkv, hd], "v": ...}
    pos: Optional[Array] = None,       # cache write offset: scalar or [B]
    window: Optional[int] = None,
    causal: bool = True,
    ctx=None,                          # ShardCtx (prefill head-sharding hint)
    pad_len: Optional[Array] = None,   # [B] left-pad lengths: pad keys masked
) -> tuple[Array, Optional[dict]]:
    b, s, _ = x.shape
    hd = cfg.hd
    q = _split_heads(linear(p["wq"], x), cfg.n_heads)
    k = _split_heads(linear(p["wk"], x), cfg.n_kv_heads)
    v = _split_heads(linear(p["wv"], x), cfg.n_kv_heads)
    q = layers.apply_rope(q, positions, cfg.rope_theta, cfg.rope_kind)
    k = layers.apply_rope(k, positions, cfg.rope_theta, cfg.rope_kind)

    if (
        cfg.gqa_prefill_headshard
        and ctx is not None
        and ctx.mesh is not None
        and s > 1
        and cfg.n_heads % ctx.tp_size() == 0
    ):
        # Prefill: put query heads on the TP axis, replicate the small K/V —
        # scores/softmax become chip-local instead of model-axis-replicated
        # (§Perf; the GQA analogue of the MLA head-sharding fix).
        from jax.sharding import PartitionSpec as P

        dp = ctx.dp_axes if b % ctx.dp_size() == 0 else None
        q = ctx.constrain(q, P(dp, None, ctx.tp_axis, None))
        k = ctx.constrain(k, P(dp, None, None, None))
        v = ctx.constrain(v, P(dp, None, None, None))

    # Sliding-window layers may carry a ring-buffer cache of exactly `window`
    # slots (Mistral-style): decode reads W entries instead of the full
    # context — §Perf iteration (gemma2 local layers: 8x fewer cache bytes).
    if cache is not None and window is not None and cache["k"].shape[1] <= window:
        w = cache["k"].shape[1]
        if s == 1:  # decode: write slot pos % W, attend over the ring
            new_cache = {"k": _ring_update(cache["k"], k, pos, 1),
                         "v": _ring_update(cache["v"], v, pos, 1)}
            kc, vc = new_cache["k"].read(), new_cache["v"].read()
            slots = jnp.arange(w)
            pos2 = jnp.reshape(jnp.asarray(pos), (-1, 1))  # [1|B, 1]
            kpos_global = pos2 - ((pos2 - slots[None]) % w)  # in (pos-W, pos]
            start = 0 if pad_len is None else pad_len[:, None]
            m = jnp.broadcast_to((kpos_global >= start)[:, None, :], (b, 1, w))
            out = _attend(q, kc, vc, mask=m[:, None],
                          softcap_val=cfg.attn_logit_softcap,
                          bf16_operands=cfg.attend_bf16)
        else:       # prefill: in-sequence attention; store the last W tokens
            if s > CHUNK_THRESHOLD and s % CHUNK_SIZE == 0:
                out = _attend_chunked(
                    q, k, v, positions, window=window,
                    softcap_val=cfg.attn_logit_softcap, causal=True,
                    bf16_operands=cfg.attend_bf16, pad_len=pad_len,
                )
            else:
                if pad_len is None:
                    m = causal_mask(s, s, window=window)
                else:
                    m = _key_mask(jnp.arange(s)[None, :],
                                  positions[:, :, None], pad_len, window)[:, None]
                out = _attend(q, k, v, mask=m, softcap_val=cfg.attn_logit_softcap,
                              bf16_operands=cfg.attend_bf16)
            tail = min(s, w)
            new_cache = {"k": _ring_update(cache["k"], k, pos + s - tail, tail),
                         "v": _ring_update(cache["v"], v, pos + s - tail, tail)}
        y = linear(p["wo"], out.reshape(b, s, cfg.n_heads * hd))
        return y, new_cache

    # int8 KV cache (§Perf): store codes + per-row scales; attention reads
    # half the bytes.  Reuses the paper's symmetric-quantization machinery.
    if cache is not None and "k_s" in cache:
        k8, ks = _quant_rows(k)
        v8, vs = _quant_rows(v)
        new_cache = {"k": _cache_write(cache["k"], k8, pos),
                     "k_s": _cache_write(cache["k_s"], ks, pos),
                     "v": _cache_write(cache["v"], v8, pos),
                     "v_s": _cache_write(cache["v_s"], vs, pos)}
        r = {name: rows.read() for name, rows in new_cache.items()}
        kc = r["k"].astype(jnp.float32) * r["k_s"][..., None]
        vc = r["v"].astype(jnp.float32) * r["v_s"][..., None]
        t = kc.shape[1]
        if s > CHUNK_THRESHOLD and s % CHUNK_SIZE == 0:
            out = _attend_chunked(
                q, kc, vc, positions, window=window,
                softcap_val=cfg.attn_logit_softcap, causal=True,
                bf16_operands=cfg.attend_bf16, pad_len=pad_len,
            )
        else:
            m = _key_mask(jnp.arange(t)[None, :], positions[:, :, None],
                          pad_len, window)
            out = _attend(q, kc, vc, mask=m[:, None], softcap_val=cfg.attn_logit_softcap,
                          bf16_operands=cfg.attend_bf16)
        y = linear(p["wo"], out.reshape(b, s, cfg.n_heads * hd))
        return y, new_cache

    if cache is not None:
        new_cache = {"k": _cache_write(cache["k"], k, pos),
                     "v": _cache_write(cache["v"], v, pos)}
        kc, vc = new_cache["k"].read(), new_cache["v"].read()
        if s > CHUNK_THRESHOLD and s % CHUNK_SIZE == 0:
            out = _attend_chunked(
                q, kc, vc, positions, window=window,
                softcap_val=cfg.attn_logit_softcap, causal=True,
                bf16_operands=cfg.attend_bf16, pad_len=pad_len,
            )
        else:
            t = kc.shape[1]
            m = _key_mask(jnp.arange(t)[None, :], positions[:, :, None],
                          pad_len, window)                  # [B, S, T]
            out = _attend(q, kc, vc, mask=m[:, None], softcap_val=cfg.attn_logit_softcap,
                          bf16_operands=cfg.attend_bf16)
    else:
        new_cache = None
        if cfg.attn_impl == "flash":
            from repro.kernels.flash_attention import flash_attention

            out = flash_attention(
                q, k, v, causal=causal, window=window,
                softcap=cfg.attn_logit_softcap,
            )
        elif s > CHUNK_THRESHOLD and s % CHUNK_SIZE == 0:
            out = _attend_chunked(
                q, k, v, positions, window=window,
                softcap_val=cfg.attn_logit_softcap, causal=causal,
                bf16_operands=cfg.attend_bf16,
            )
        else:
            m = causal_mask(s, s, window=window) if causal else jnp.ones((1, 1, s, s), bool)
            out = _attend(q, k, v, mask=m, softcap_val=cfg.attn_logit_softcap,
                          bf16_operands=cfg.attend_bf16)
    y = linear(p["wo"], out.reshape(b, s, cfg.n_heads * hd))
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross attention (enc-dec)
# ---------------------------------------------------------------------------


def cross_attention(
    p: dict,
    x: Array,
    *,
    cfg: ModelConfig,
    enc_k: Array,     # [B, T, Hkv, hd]  (precomputed from encoder output)
    enc_v: Array,
) -> Array:
    b, s, _ = x.shape
    q = _split_heads(linear(p["wq"], x), cfg.n_heads)
    m = jnp.ones((1, 1, s, enc_k.shape[1]), bool)
    out = _attend(q, enc_k, enc_v, mask=m, softcap_val=None)
    return linear(p["wo"], out.reshape(b, s, -1))


def cross_kv(p: dict, enc_out: Array, *, cfg: ModelConfig) -> tuple[Array, Array]:
    k = _split_heads(linear(p["wk"], enc_out), cfg.n_kv_heads)
    v = _split_heads(linear(p["wv"], enc_out), cfg.n_kv_heads)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention, absorbed formulation)
# ---------------------------------------------------------------------------


def _dense_weight(p) -> Array:
    """Raw [K, F] weight of a dense dict or a QuantizedLinear (MLA absorbs
    W_kup/W_vup into the query/output paths, so it needs the matrix itself)."""
    from repro.core import QuantizedLinear
    from repro.core.api import dequantize_weights
    from repro.core.calibrate import unwrap

    p = unwrap(p)   # absorbed matrices never consume an activation scale
    if isinstance(p, QuantizedLinear):
        return dequantize_weights(p)
    return p["w"]


def mla_init(cfg: ModelConfig, key) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)
    return {
        "w_dkv": dense_init(ks[0], d, m.kv_lora_rank + m.qk_rope_dim),
        "w_kup": dense_init(ks[1], m.kv_lora_rank, h * m.qk_nope_dim),
        "w_vup": dense_init(ks[2], m.kv_lora_rank, h * m.v_head_dim),
        "wq": dense_init(ks[3], d, h * (m.qk_nope_dim + m.qk_rope_dim)),
        "wo": dense_init(ks[4], h * m.v_head_dim, d),
        "kv_norm": layers.rmsnorm_init(m.kv_lora_rank),
    }


@scopes.scoped(scopes.ATTENTION)
def mla_attention(
    p: dict,
    x: Array,
    *,
    cfg: ModelConfig,
    positions: Array,
    cache: Optional[dict] = None,   # {"ckv": LayerRows [B, Smax, lora], "krope": ...}
    pos: Optional[Array] = None,    # cache write offset: scalar or [B]
    ctx=None,                       # ShardCtx (prefill head-sharding hint)
    pad_len: Optional[Array] = None,  # [B] left-pad lengths: pad keys masked
) -> tuple[Array, Optional[dict]]:
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    dkv = linear(p["w_dkv"], x)
    ckv, krope = dkv[..., : m.kv_lora_rank], dkv[..., m.kv_lora_rank :]
    ckv = layers.norm(p["kv_norm"], ckv, "rmsnorm", cfg.norm_eps)
    krope = layers.apply_rope(
        krope[:, :, None, :], positions, cfg.rope_theta, "full"
    )[:, :, 0, :]                                                   # [B,S,rope]

    q = linear(p["wq"], x).reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta, "full")

    # Absorb W_kup into the query: q_lat[b,s,h,lora] = q_nope · W_kup^T
    wkup = _dense_weight(p["w_kup"]).reshape(m.kv_lora_rank, h, m.qk_nope_dim)
    q_lat = jnp.einsum("bshd,lhd->bshl", q_nope.astype(jnp.float32), wkup)

    if cache is not None:
        new_cache = {"ckv": _cache_write(cache["ckv"], ckv, pos),
                     "krope": _cache_write(cache["krope"], krope, pos)}
        ckv_c, krope_c = new_cache["ckv"].read(), new_cache["krope"].read()
    else:
        ckv_c, krope_c = ckv, krope
        new_cache = None

    scale = 1.0 / jnp.sqrt(m.qk_nope_dim + m.qk_rope_dim).astype(jnp.float32)
    ckv_f = ckv_c.astype(jnp.float32)
    krope_f = krope_c.astype(jnp.float32)
    qr_f = q_rope.astype(jnp.float32)

    if (
        cfg.mla_prefill_headshard
        and ctx is not None
        and ctx.mesh is not None
        and s > 1
    ):
        # Prefill: replicate the small latent across TP and shard the absorbed
        # query's HEAD dim instead — scores stay chip-local (no [B,H,S,T]
        # all-reduce, one latent all-gather per layer instead).  §Perf.
        from jax.sharding import PartitionSpec as P

        dp = ctx.dp_axes if b % ctx.dp_size() == 0 else None
        h_ax = ctx.tp_axis if h % ctx.tp_size() == 0 else None
        ckv_f = ctx.constrain(ckv_f, P(dp, None, None))
        krope_f = ctx.constrain(krope_f, P(dp, None, None))
        q_lat = ctx.constrain(q_lat, P(dp, None, h_ax, None))
        qr_f = ctx.constrain(qr_f, P(dp, None, h_ax, None))

    if cfg.attend_bf16:
        ckv_f = ckv_c.astype(jnp.bfloat16)
        krope_f = krope_c.astype(jnp.bfloat16)
        qr_f = q_rope.astype(jnp.bfloat16)
        q_lat = q_lat.astype(jnp.bfloat16)

    def latent_attend(q_lat_i, q_rope_i, pos_i):
        sc = (
            jnp.einsum("bshl,btl->bhst", q_lat_i, ckv_f,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bshr,btr->bhst", q_rope_i, krope_f,
                         preferred_element_type=jnp.float32)
        ) * scale
        mk = _key_mask(jnp.arange(ckv_f.shape[1])[None, :],
                       pos_i[:, :, None], pad_len, None)
        sc = jnp.where(mk[:, None], sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1)
        if cfg.attend_bf16:
            w = w.astype(jnp.bfloat16)
        return jnp.einsum("bhst,btl->bshl", w, ckv_f,
                          preferred_element_type=jnp.float32)

    if s > 4096 and s % 512 == 0:
        # chunked prefill: scores never materialize at [S, S]
        nc = s // 512
        def body(_, inp):
            ql_i, qr_i, pos_i = inp
            return None, latent_attend(ql_i, qr_i, pos_i)
        from repro import flags

        _, outs = jax.lax.scan(
            body, None,
            (jnp.moveaxis(q_lat.reshape(b, nc, 512, h, -1), 1, 0),
             jnp.moveaxis(qr_f.reshape(b, nc, 512, h, -1), 1, 0),
             jnp.moveaxis(positions.reshape(b, nc, 512), 1, 0)),
            unroll=flags.scan_unroll(),
        )
        out_lat = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, m.kv_lora_rank)
    else:
        out_lat = latent_attend(q_lat, qr_f, positions)
    wvup = _dense_weight(p["w_vup"]).reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bshl,lhv->bshv", out_lat, wvup).astype(x.dtype)
    y = linear(p["wo"], out.reshape(b, s, h * m.v_head_dim))
    return y, new_cache
