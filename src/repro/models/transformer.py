"""Transformer assembly: pattern-segmented, scanned layer stacks.

Every architecture is a sequence of *segments*; each segment is a stack of
identical *units* scanned with ``lax.scan`` (so an 81-layer model compiles a
single unit).  A unit is described by a pattern string:

    D  attention + FFN (or MoE)         L  sliding-window attention + FFN
    G  global attention + FFN           M  Mamba2 block
    S  Mamba2 + *shared* attention      R  RWKV6 time-mix + channel-mix
    C  self-attn + cross-attn + FFN     E  bidirectional attention + FFN

Examples: gemma2 = [("LG", 13)], zamba2 = [("MMMMMS", 13), ("M", 3)],
deepseek-v2-lite = [("F", 1), ("D", 26)] (F = dense-FFN first layer).

Caches follow the same segmentation: each segment's stacked per-unit cache
pytree rides in its layer scan's carry, and every unit reads and writes its
own layer of it in place.  One ``forward`` serves train (no cache), prefill
(cache + pos=0) and decode (cache + pos=t).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention, ffn, layers, moe, rwkv, ssm
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, linear, norm
from repro.obs import scopes

Array = jax.Array


# ---------------------------------------------------------------------------
# Segmentation
# ---------------------------------------------------------------------------


def segments(cfg: ModelConfig) -> list[tuple[str, int]]:
    if cfg.layer_pattern:
        period = len(cfg.layer_pattern)
        n_units, rem = divmod(cfg.n_layers, period)
        segs = [(cfg.layer_pattern, n_units)]
        if rem:
            segs.append((cfg.layer_pattern[0] * rem, 1))
        return segs
    if cfg.rwkv is not None:
        return [("R", cfg.n_layers)]
    if cfg.is_encdec:
        return [("C", cfg.n_layers)]
    if cfg.moe is not None and cfg.first_dense_layers:
        return [("F", cfg.first_dense_layers), ("D", cfg.n_layers - cfg.first_dense_layers)]
    return [("D", cfg.n_layers)]


def _needs_shared_attn(cfg: ModelConfig) -> bool:
    return any("S" in pat for pat, _ in segments(cfg))


# ---------------------------------------------------------------------------
# Unit init
# ---------------------------------------------------------------------------


def _sublayer_init(cfg: ModelConfig, ch: str, key) -> dict:
    d = cfg.d_model
    nrm = layers.rmsnorm_init if cfg.norm_kind == "rmsnorm" else layers.layernorm_init
    ks = jax.random.split(key, 6)
    if ch in ("D", "L", "G", "F"):
        p = {"attn_norm": nrm(d), "ffn_norm": nrm(d)}
        if cfg.attn_kind == "mla":
            p["attn"] = attention.mla_init(cfg, ks[0])
        else:
            p["attn"] = attention.gqa_init(cfg, ks[0])
        if cfg.moe is not None and ch == "D":
            p["moe"] = moe.moe_init(cfg, ks[1])
        else:
            p["ffn"] = ffn.ffn_init(cfg, ks[1])
        return p
    if ch in ("M", "S"):
        return {"norm": nrm(d), "ssm": ssm.ssm_init(cfg, ks[0])}
    if ch == "R":
        return {
            "tm_norm": nrm(d),
            "time_mix": rwkv.rwkv_time_init(cfg, ks[0]),
            "cm_norm": nrm(d),
            "channel_mix": rwkv.rwkv_channel_init(cfg, ks[1]),
        }
    if ch == "C":
        return {
            "attn_norm": nrm(d),
            "attn": attention.gqa_init(cfg, ks[0]),
            "cross_norm": nrm(d),
            "cross": attention.gqa_init(cfg, ks[1]),
            "ffn_norm": nrm(d),
            "ffn": ffn.ffn_init(cfg, ks[2]),
        }
    if ch == "E":
        return {
            "attn_norm": nrm(d),
            "attn": attention.gqa_init(cfg, ks[0]),
            "ffn_norm": nrm(d),
            "ffn": ffn.ffn_init(cfg, ks[1]),
        }
    raise ValueError(ch)


def unit_init(cfg: ModelConfig, pattern: str, key) -> dict:
    ks = jax.random.split(key, len(pattern))
    return {f"s{i}_{ch}": _sublayer_init(cfg, ch, ks[i]) for i, ch in enumerate(pattern)}


def _stack(trees: list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def _sublayer_cache(cfg: ModelConfig, ch: str, batch: int, max_seq: int, dtype):
    hd = cfg.hd
    if ch in ("D", "L", "G", "F"):
        if cfg.attn_kind == "mla":
            m = cfg.mla
            return {
                "ckv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
                "krope": jnp.zeros((batch, max_seq, m.qk_rope_dim), dtype),
            }
        seq = max_seq
        if ch == "L" and cfg.ring_window_cache and cfg.window:
            seq = min(max_seq, cfg.window)   # ring buffer (§Perf)
        if cfg.kv_cache_int8 and seq == max_seq:
            return {
                "k": jnp.zeros((batch, seq, cfg.n_kv_heads, hd), jnp.int8),
                "k_s": jnp.zeros((batch, seq, cfg.n_kv_heads), jnp.float32),
                "v": jnp.zeros((batch, seq, cfg.n_kv_heads, hd), jnp.int8),
                "v_s": jnp.zeros((batch, seq, cfg.n_kv_heads), jnp.float32),
            }
        return {
            "k": jnp.zeros((batch, seq, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, seq, cfg.n_kv_heads, hd), dtype),
        }
    if ch == "M":
        return ssm.init_ssm_state(cfg, batch, dtype)
    if ch == "S":
        return {
            "mamba": ssm.init_ssm_state(cfg, batch, dtype),
            "attn": {
                "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, hd), dtype),
            },
        }
    if ch == "R":
        return rwkv.init_rwkv_state(cfg, batch, dtype)
    if ch == "C":
        enc_seq = cfg.frontend_seq
        return {
            "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, hd), dtype),
            "ck": jnp.zeros((batch, enc_seq, cfg.n_kv_heads, hd), dtype),
            "cv": jnp.zeros((batch, enc_seq, cfg.n_kv_heads, hd), dtype),
        }
    if ch == "E":
        return None
    raise ValueError(ch)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """Stacked cache pytrees mirroring the parameter segmentation."""
    out = []
    for pattern, n_units in segments(cfg):
        unit = {
            f"s{i}_{ch}": _sublayer_cache(cfg, ch, batch, max_seq, dtype)
            for i, ch in enumerate(pattern)
        }
        out.append(_stack([unit] * n_units) if n_units > 1 else _stack([unit]))
    return out


# ---------------------------------------------------------------------------
# Unit apply
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunState:
    """Closure-carried context for one forward pass."""

    cfg: ModelConfig
    positions: Array                     # [B, S] logical positions
    pos: Optional[Array]                 # cache write offset (None = no cache;
                                         # scalar, or [B] per-slot offsets)
    shared_attn: Optional[dict] = None   # zamba2 shared block params
    enc_out: Optional[Array] = None      # whisper encoder output
    is_prefill: bool = False
    ctx: Any = None                      # ShardCtx
    remat: bool = False                  # activation-checkpoint each unit
    pad_len: Optional[Array] = None      # [B] left-pad lengths (key don't-cares)


def _apply_sublayer(
    rs: RunState, ch: str, p: dict, x: Array, cache, aux: Array
):
    cfg = rs.cfg
    nk, eps = cfg.norm_kind, cfg.norm_eps
    if ch in ("D", "L", "G", "F"):
        h = norm(p["attn_norm"], x, nk, eps)
        window = cfg.window if ch == "L" else None
        if cfg.attn_kind == "mla":
            a, new_attn_cache = attention.mla_attention(
                p["attn"], h, cfg=cfg, positions=rs.positions, cache=cache,
                pos=rs.pos, ctx=rs.ctx, pad_len=rs.pad_len,
            )
        else:
            a, new_attn_cache = attention.gqa_attention(
                p["attn"], h, cfg=cfg, positions=rs.positions, cache=cache,
                pos=rs.pos, window=window, ctx=rs.ctx, pad_len=rs.pad_len,
            )
        x = x + a
        h = norm(p["ffn_norm"], x, nk, eps)
        if "moe" in p:
            f, aux_l = moe.moe_apply(p["moe"], h, cfg, rs.ctx)
            aux = aux + aux_l
        else:
            f = ffn.ffn_apply(p["ffn"], h, cfg)
        if cfg.parallel_block:
            # stablelm: attn and FFN read the same pre-norm input in parallel
            x = x + f
        else:
            x = x + f
        return x, new_attn_cache, aux
    if ch == "M":
        h = norm(p["norm"], x, nk, eps)
        y, new_state = ssm.ssm_apply(p["ssm"], h, cfg, _read(cache))
        return x + y, new_state, aux
    if ch == "S":
        h = norm(p["norm"], x, nk, eps)
        y, new_m = ssm.ssm_apply(p["ssm"], h, cfg, _read(cache["mamba"]) if cache else None)
        x = x + y
        sp = rs.shared_attn
        h = norm(sp["attn_norm"], x, nk, eps)
        a, new_a = attention.gqa_attention(
            sp["attn"], h, cfg=cfg, positions=rs.positions,
            cache=cache["attn"] if cache else None, pos=rs.pos,
            pad_len=rs.pad_len,
        )
        x = x + a
        h = norm(sp["ffn_norm"], x, nk, eps)
        x = x + ffn.ffn_apply(sp["ffn"], h, cfg)
        new_cache = {"mamba": new_m, "attn": new_a} if cache is not None else None
        return x, new_cache, aux
    if ch == "R":
        h = norm(p["tm_norm"], x, nk, eps)
        y, new_state = rwkv.rwkv_time_mix(p["time_mix"], h, cfg, _read(cache))
        x = x + y
        h = norm(p["cm_norm"], x, nk, eps)
        y, new_state = rwkv.rwkv_channel_mix(p["channel_mix"], h, cfg, new_state)
        return x + y, new_state, aux
    if ch == "C":
        h = norm(p["attn_norm"], x, nk, eps)
        self_cache = {"k": cache["k"], "v": cache["v"]} if cache is not None else None
        a, new_self = attention.gqa_attention(
            p["attn"], h, cfg=cfg, positions=rs.positions, cache=self_cache,
            pos=rs.pos, pad_len=rs.pad_len,
        )
        x = x + a
        h = norm(p["cross_norm"], x, nk, eps)
        if rs.enc_out is not None:
            ck, cv = attention.cross_kv(p["cross"], rs.enc_out, cfg=cfg)
            if cache is not None:
                ck = ck.astype(cache["ck"].dtype)
                cv = cv.astype(cache["cv"].dtype)
        else:
            ck, cv = cache["ck"].read(), cache["cv"].read()
        x = x + attention.cross_attention(p["cross"], h, cfg=cfg, enc_k=ck, enc_v=cv)
        h = norm(p["ffn_norm"], x, nk, eps)
        x = x + ffn.ffn_apply(p["ffn"], h, cfg)
        new_cache = None
        if cache is not None:
            new_cache = {"k": new_self["k"], "v": new_self["v"], "ck": ck, "cv": cv}
        return x, new_cache, aux
    if ch == "E":
        h = norm(p["attn_norm"], x, nk, eps)
        a, _ = attention.gqa_attention(
            p["attn"], h, cfg=cfg, positions=rs.positions, causal=False
        )
        x = x + a
        h = norm(p["ffn_norm"], x, nk, eps)
        return x + ffn.ffn_apply(p["ffn"], h, cfg), None, aux
    raise ValueError(ch)


def _read(cache):
    """The whole state of a sublayer that replaces its state every step (SSM,
    RWKV), as arrays; :func:`_write_back` stores what it returns."""
    return jax.tree.map(attention.LayerRows.read, cache)


def _write_back(stack: Array, new, layer: Array) -> Array:
    """One leaf of a unit's new cache into its segment's stack: rows written
    in place come back as :class:`attention.LayerRows` holding the updated
    stack; a state returned whole replaces the unit's slice."""
    if isinstance(new, attention.LayerRows):
        return new.buf
    return jax.lax.dynamic_update_index_in_dim(stack, new.astype(stack.dtype), layer, 0)


def unit_apply(rs: RunState, pattern: str, unit_p: dict, x: Array, unit_cache, aux):
    new_cache = {} if unit_cache is not None else None
    for i, ch in enumerate(pattern):
        key = f"s{i}_{ch}"
        c = unit_cache[key] if unit_cache is not None else None
        x, nc, aux = _apply_sublayer(rs, ch, unit_p[key], x, c, aux)
        if unit_cache is not None:
            new_cache[key] = nc
    return x, new_cache, aux


def run_segments(
    rs: RunState,
    seg_params: list,
    x: Array,
    caches: Optional[list],
):
    """Scan every segment; returns (x, new_caches, aux)."""
    cfg = rs.cfg
    aux = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    for si, (pattern, n_units) in enumerate(segments(cfg)):
        p_stack = seg_params[si]
        if rs.ctx is not None:
            x = rs.ctx.constrain_acts(x)

        from repro import flags

        if caches is None:
            def body(carry, unit_p):
                x_c, aux_c = carry
                x_c, _, aux_c = unit_apply(rs, pattern, unit_p, x_c, None, aux_c)
                return (x_c, aux_c), None

            body_fn = jax.checkpoint(body) if rs.remat else body
            with jax.named_scope(scopes.LAYERS):
                (x, aux), _ = jax.lax.scan(
                    body_fn, (x, aux), p_stack, unroll=flags.scan_unroll()
                )
            continue

        # The stacked cache rides in the carry, not in xs/ys: each unit writes
        # its new rows into it in place, so the scan never slices out or
        # stacks back a whole layer of cache.
        def cached_body(carry, xs):
            x_c, aux_c, stack = carry
            unit_p, layer = xs
            rows = jax.tree.map(lambda a: attention.LayerRows(a, layer), stack)
            x_c, nc, aux_c = unit_apply(rs, pattern, unit_p, x_c, rows, aux_c)
            stack = jax.tree.map(lambda a, n: _write_back(a, n, layer), stack, nc)
            return (x_c, aux_c, stack), None

        body_fn = jax.checkpoint(cached_body) if rs.remat else cached_body
        with jax.named_scope(scopes.LAYERS):
            (x, aux, stack), _ = jax.lax.scan(
                body_fn, (x, aux, caches[si]),
                (p_stack, jnp.arange(n_units, dtype=jnp.int32)),
                unroll=flags.scan_unroll(),
            )
        new_caches.append(stack)
    return x, new_caches, aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 8)

    seg_list = []
    for si, (pattern, n_units) in enumerate(segments(cfg)):
        seg_key = jax.random.fold_in(ks[1], si)
        units = [unit_init(cfg, pattern, k) for k in jax.random.split(seg_key, n_units)]
        seg_list.append(_stack(units))

    params: dict = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
        * 0.02,
        "final_norm": (
            layers.rmsnorm_init(cfg.d_model)
            if cfg.norm_kind == "rmsnorm"
            else layers.layernorm_init(cfg.d_model)
        ),
        "segments": seg_list,
    }

    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size)
    if _needs_shared_attn(cfg):
        params["shared_attn"] = {
            "attn_norm": layers.rmsnorm_init(cfg.d_model),
            "attn": attention.gqa_init(cfg, ks[3]),
            "ffn_norm": layers.rmsnorm_init(cfg.d_model),
            "ffn": ffn.ffn_init(cfg, ks[4]),
        }
    if cfg.is_encdec:
        enc_units = [
            unit_init(cfg, "E", k) for k in jax.random.split(ks[5], cfg.encoder_layers)
        ]
        params["encoder"] = _stack(enc_units)
        params["enc_final_norm"] = (
            layers.rmsnorm_init(cfg.d_model)
            if cfg.norm_kind == "rmsnorm"
            else layers.layernorm_init(cfg.d_model)
        )
    if cfg.frontend is not None:
        params["frontend_proj"] = dense_init(ks[6], cfg.frontend_dim, cfg.d_model)
    return params


def encode(params: dict, cfg: ModelConfig, frames: Array, ctx=None) -> Array:
    """Whisper-style encoder over stub frontend embeddings [B, T, frontend_dim]."""
    x = linear(params["frontend_proj"], frames)
    x = x + layers.sinusoidal_positions(x.shape[1], cfg.d_model)[None].astype(x.dtype)
    rs = RunState(
        cfg=cfg,
        positions=jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], (x.shape[0], x.shape[1])
        ),
        pos=None,
        ctx=ctx,
    )

    def body(carry, unit_p):
        y, _, _ = unit_apply(rs, "E", unit_p, carry, None, jnp.zeros((), jnp.float32))
        return y, None

    from repro import flags

    x, _ = jax.lax.scan(body, x, params["encoder"], unroll=flags.scan_unroll())
    return norm(params["enc_final_norm"], x, cfg.norm_kind, cfg.norm_eps)


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: Array,                     # [B, S] int32
    *,
    caches: Optional[list] = None,
    pos: Optional[Array] = None,       # cache write offset: scalar or [B]
    prefix_embeds: Optional[Array] = None,  # [B, P, frontend_dim] stub frontend
    is_prefill: bool = False,
    ctx=None,
    remat: bool = False,
    return_hidden: bool = False,       # skip the LM head (chunked-loss path)
    last_token_only: bool = False,     # head over the final position only
    pad_len: Optional[Array] = None,   # [B] left-pad lengths; pad positions
                                       # become attention don't-cares and
                                       # logical positions shift by -pad_len
) -> tuple[Array, Optional[list], Array]:
    """Returns (logits [B, S', V] — or hidden [B, S', D], new_caches, aux)."""
    b, s = tokens.shape
    x = params["embed"][tokens].astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)

    enc_out = None
    if cfg.is_encdec and prefix_embeds is not None:
        enc_out = encode(params, cfg, prefix_embeds, ctx=ctx)
    elif cfg.frontend is not None and prefix_embeds is not None and not cfg.is_encdec:
        # VLM: project patch embeddings and prepend to the token sequence.
        pe = linear(params["frontend_proj"], prefix_embeds.astype(x.dtype))
        x = jnp.concatenate([pe, x], axis=1)
        s = x.shape[1]

    if pos is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    else:
        pos_a = jnp.asarray(pos)
        off = pos_a[:, None] if pos_a.ndim else pos_a      # [B,1] | scalar
        positions = off + jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    if pad_len is not None:
        # Left-padded rows: real token i sits at buffer index pad+i but
        # logical position i.  RoPE/sinusoid and all causal comparisons use
        # logical positions; cache writes keep using buffer offsets (rs.pos).
        positions = positions - pad_len[:, None]

    if cfg.rope_kind == "none":
        # Absolute sinusoidal positions for rope-less decoders (whisper/OPT).
        x = x + layers.sinusoid_at(positions, cfg.d_model).astype(x.dtype)

    rs = RunState(
        cfg=cfg,
        positions=positions,
        pos=pos,
        shared_attn=params.get("shared_attn"),
        enc_out=enc_out,
        is_prefill=is_prefill,
        ctx=ctx,
        remat=remat,
        pad_len=pad_len,
    )
    x, new_caches, aux = run_segments(rs, params["segments"], x, caches)
    x = norm(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    if return_hidden:
        return x, new_caches, aux
    if last_token_only:
        x = x[:, -1:, :]
    logits = lm_head(params, cfg, x)
    return logits, new_caches, aux


@scopes.scoped(scopes.LM_HEAD)
def lm_head(params: dict, cfg: ModelConfig, x: Array) -> Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype))
    else:
        logits = linear(params["lm_head"], x)
    return layers.softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
