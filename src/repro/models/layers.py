"""Shared primitive layers: norms, RoPE, activations, linears.

A "linear" parameter is either a dense dict ``{"w": [K,F], ("b": [F])}``, a
:class:`repro.core.QuantizedLinear`, or a weight-stationary
:class:`repro.core.PreparedLinear` — :func:`linear` dispatches, which is what
makes LoCaLUT quantization (and the serve-time prepare/apply split) a drop-in
transform over any model in the zoo.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PreparedLinear, QuantizedLinear, apply_linear
from repro.core.calibrate import CalibrationProbe, probe_apply
from repro.obs import scopes

Array = jax.Array


def dense_init(key, k: int, f: int, *, bias: bool = False, scale: float | None = None):
    std = scale if scale is not None else (1.0 / np.sqrt(k))
    p = {"w": jax.random.normal(key, (k, f), dtype=jnp.float32) * std}
    if bias:
        p["b"] = jnp.zeros((f,), dtype=jnp.float32)
    return p


def linear(p, x: Array) -> Array:
    if isinstance(p, (QuantizedLinear, PreparedLinear)):
        with jax.named_scope(scopes.QLINEAR):
            return apply_linear(p, x)
    if isinstance(p, CalibrationProbe):   # one-shot scale-capture forward
        return probe_apply(p, x)
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def rmsnorm_init(d: int):
    return {"g": jnp.ones((d,), dtype=jnp.float32)}


def layernorm_init(d: int):
    return {"g": jnp.ones((d,), dtype=jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def norm(p, x: Array, kind: str = "rmsnorm", eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * p["g"]).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"] + p["b"]).astype(x.dtype)


def activation(x: Array, kind: str) -> Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(kind)


def softcap(x: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(hd: int, theta: float, *, frac: float = 1.0) -> Array:
    """Inverse frequencies for the rotated ``frac`` of the head dim."""
    rot = int(hd * frac) // 2 * 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: Array, positions: Array, theta: float, kind: str = "full") -> Array:
    """Rotate ``x [B, S, H, hd]`` by position.  ``kind='half'`` rotates only
    the first half of the head dim (ChatGLM's 2D/partial RoPE)."""
    if kind == "none":
        return x
    hd = x.shape[-1]
    frac = 0.5 if kind == "half" else 1.0
    inv = rope_freqs(hd, theta, frac=frac)                    # [R/2]
    ang = positions[..., None].astype(jnp.float32) * inv      # [B, S, R/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    r = inv.shape[0] * 2
    xr, xp = x[..., :r], x[..., r:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr.astype(x.dtype), xp], axis=-1)


def sinusoidal_positions(seq: int, d: int) -> Array:
    """Whisper-style sinusoidal absolute embeddings [seq, d]."""
    pos = np.arange(seq)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d)
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, dtype=jnp.float32)


def chunked_scan(step, s0, xs_seqfirst, *, chunk: int = 128):
    """``lax.scan`` over the sequence with per-chunk activation checkpointing.

    A recurrent scan's VJP stores one carry per step; for 32k-token SSD/RWKV
    prefill that is tens of GB.  Scanning chunk-wise with a checkpointed
    chunk body stores one carry per *chunk* and recomputes the inner steps in
    backward — the standard O(sqrt)-memory recurrence trick.
    """
    import jax

    leaves = jax.tree.leaves(xs_seqfirst)
    s = leaves[0].shape[0]
    if s <= chunk or s % chunk:
        return jax.lax.scan(step, s0, xs_seqfirst)
    nc = s // chunk
    xs_c = jax.tree.map(lambda t: t.reshape(nc, chunk, *t.shape[1:]), xs_seqfirst)

    @jax.checkpoint
    def outer(carry, xc):
        return jax.lax.scan(step, carry, xc)

    carry, ys = jax.lax.scan(outer, s0, xs_c)
    ys = jax.tree.map(lambda t: t.reshape(s, *t.shape[2:]), ys)
    return carry, ys


def sinusoid_at(positions: Array, d: int) -> Array:
    """Sinusoidal embeddings evaluated at dynamic positions [B, S] -> [B, S, d].

    Used for rope_kind="none" decoders (whisper, OPT-style): works at any
    decode offset without a precomputed table.
    """
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, None, :]
    ang = positions[..., None].astype(jnp.float32) / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
