"""Plain float32 reference of the dense grouped-query decoders.

Straightforward ``jax.numpy``, independent of the program: it imports
nothing of ``repro`` and reads only the benchmark's own weights
(:mod:`bench.weights`), rebuilding each layer's dense float32 weights from
the packed codes and scales inside a scan over layers, so that one layer's
dense weights exist at a time.  Matrix products run at ``highest``
precision.  The block follows the configuration file's fields in
:data:`BLOCK` (a field the file leaves out takes the value given there):

    x = embed[token]
    per layer, sequential:  h = norm(x); x += attn(h) @ wo; h = norm(x);
                            x += ffn(h)
    per layer, parallel:    h = norm(x); x += attn(h) @ wo + ffn(h)
    ffn(h) = (act(h @ w_gate) * (h @ w_up)) @ w_down, or act(h @ w_up) @ w_down
             where the FFN is not gated
    logits = norm(x) @ lm_head

with rotary embedding on the first ``rope_fraction`` of each head's
dimensions, as interleaved pairs, and grouped-query attention (query head
``h`` reads key/value group ``h // (n_heads / n_kv_heads)``).  The parallel
block has one input norm, as in GPT-J and StableLM 2.

``control=True`` computes the same in the lower precision a later change
might be tempted to serve in: every matrix-product operand is rounded to
float8 (e4m3) first.  It stands in for the program in the check that the
comparison can fail (``bench/calibrate.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYERS = "segments/0/s0_D/"

# The block fields this reference reads, each with its value where a
# configuration file states none.  The benchmark serves the program with the
# same values, so both sides run the block the file states.
BLOCK = dict(norm_kind="rmsnorm", norm_eps=1e-6, rope_theta=10000.0,
             rope_fraction=1.0, qkv_bias=False, parallel_block=False,
             gated_ffn=True, ffn_act="silu")
SHAPE = ("n_heads", "n_kv_heads", "head_dim", "bw")


def block(cfg: dict) -> dict:
    """The block fields of ``cfg``, with :data:`BLOCK`'s values where it
    states none."""
    return {k: cfg.get(k, v) for k, v in BLOCK.items()}


def dequant(codes, scale, bw: int):
    """Packed ``[F, K*bw/8]`` uint8 codes and ``[F]`` scales -> ``[K, F]``.

    Byte ``j`` holds codes ``j*cpb .. j*cpb+cpb-1``, code ``q`` in bits
    ``[q*bw, (q+1)*bw)``.  Code ``c`` has value ``clip(c - 2^(bw-1), -m, m)``
    with ``m = 2^(bw-1) - 1`` (the symmetric int grid) for ``bw >= 2``, and
    ``2c - 1`` for ``bw == 1``.
    """
    cpb = 8 // bw
    shifts = jnp.arange(cpb, dtype=jnp.int32) * bw
    c = (codes.astype(jnp.int32)[..., None] >> shifts) & ((1 << bw) - 1)
    c = c.reshape(codes.shape[0], -1)
    if bw == 1:
        v = 2 * c - 1
    else:
        m = 2 ** (bw - 1) - 1
        v = jnp.clip(c - 2 ** (bw - 1), -m, m)
    return (v.astype(jnp.float32) * scale[:, None].astype(jnp.float32)).T


def _norm(x, g, b, kind: str, eps: float):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _act(x, kind: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[kind](x)


def _rope(x, pos, theta: float, frac: float):
    """Rotate the first ``frac`` of ``x [S, H, hd]``'s last dim, pairs
    ``(2i, 2i+1)`` by angle ``pos * theta^(-2i/rot)``."""
    hd = x.shape[-1]
    rot = int(hd * frac) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv            # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([y.reshape(x[..., :rot].shape), x[..., rot:]], -1)


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def forward_hidden(w: dict, cfg: dict, tokens, *, control: bool = False):
    """Final-normed hidden states ``[S, d_model]`` of one token sequence."""
    cast = _fp8 if control else (lambda a: a)
    mm = lambda a, b: jnp.matmul(cast(a), cast(b))
    bw, kind, eps = cfg["bw"], cfg["norm_kind"], cfg["norm_eps"]
    h_q, h_kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]                       # [S(q), T(k)]

    def proj(lw, name, h):
        y = mm(h, dequant(lw[name + "/codes"], lw[name + "/scale"], bw))
        if name in ("attn/wq", "attn/wk", "attn/wv") and cfg["qkv_bias"]:
            y = y + lw[name + "/bias"]
        return y

    def ffn(lw, h):
        if cfg["gated_ffn"]:
            f = _act(proj(lw, "ffn/w_gate", h), cfg["ffn_act"]) * proj(lw, "ffn/w_up", h)
        else:
            f = _act(proj(lw, "ffn/w_up", h), cfg["ffn_act"])
        return proj(lw, "ffn/w_down", f)

    def layer(x, lw):
        nb = lambda n: lw.get(n + "/b")
        h = _norm(x, lw["attn_norm/g"], nb("attn_norm"), kind, eps)
        q = proj(lw, "attn/wq", h).reshape(s, h_q, hd)
        k = proj(lw, "attn/wk", h).reshape(s, h_kv, hd)
        v = proj(lw, "attn/wv", h).reshape(s, h_kv, hd)
        q = _rope(q, pos, cfg["rope_theta"], cfg["rope_fraction"])
        k = _rope(k, pos, cfg["rope_theta"], cfg["rope_fraction"])
        rep = h_q // h_kv
        kh = jnp.repeat(k, rep, axis=1)                         # [T, H, hd]
        vh = jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("shd,thd->hst", cast(q), cast(kh)) / np.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hst,thd->shd", cast(p), cast(vh)).reshape(s, h_q * hd)
        a = proj(lw, "attn/wo", o)
        if cfg["parallel_block"]:
            return x + a + ffn(lw, h), None
        x = x + a
        return x + ffn(lw, _norm(x, lw["ffn_norm/g"], nb("ffn_norm"), kind, eps)), None

    stacked = {k[len(LAYERS):]: v for k, v in w.items() if k.startswith(LAYERS)}
    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, stacked)
    return _norm(x, w["final_norm/g"], w.get("final_norm/b"), kind, eps)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _gaps(w, tokens, at, want, *, cfg_items, control: bool):
    """For each position ``at[i]``: the reference's best logit minus its
    logit of token ``want[i]`` (``control``: of the control's first token)."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        hid = forward_hidden(w, cfg, tokens)[at]                # [M, D]
        ref = hid @ w["lm_head/w"].astype(jnp.float32)          # [M, V]
        if control:
            chid = forward_hidden(w, cfg, tokens, control=True)[at]
            want = jnp.argmax(_fp8(chid) @ _fp8(w["lm_head/w"]), axis=-1)
    picked = jnp.take_along_axis(ref, want[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - picked


def served_gaps(w: dict, cfg: dict, prompt, served, *, seq_len: int,
                control: bool = False) -> np.ndarray:
    """Gap of every served token of one request under the reference.

    ``prompt`` and ``served`` are int token lists; the sequence
    ``prompt + served[:-1]`` is right-padded to ``seq_len`` (causal, so the
    padding changes nothing before it) and served token ``j`` is read at
    position ``len(prompt) - 1 + j``.  Returns one gap per served token:
    how far the reference's logit of that token lies below its best logit
    (with ``control``, the same for the token the control ranks first).
    """
    seq = np.zeros((seq_len,), np.int32)
    full = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    seq[: len(full)] = full
    n = len(served)
    at = np.zeros((seq_len,), np.int32)          # fixed shape: one compile
    want = np.zeros((seq_len,), np.int32)
    at[:n] = len(prompt) - 1 + np.arange(n)
    want[:n] = served
    cfg_items = tuple(sorted({**block(cfg), **{k: cfg[k] for k in SHAPE}}.items()))
    g = _gaps(w, jnp.asarray(seq), jnp.asarray(at), jnp.asarray(want),
              cfg_items=cfg_items, control=control)
    return np.asarray(g)[:n]
