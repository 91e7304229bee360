"""Work of a dense grouped-query decoder, counted from its shapes.

The counts are the algorithm's, at the configuration's stated formats, and
the same whatever implements them:

- FLOPs: the matrix products and attention of the prompt tokens actually
  admitted and of the output tokens; padded rows, padded prompt positions
  and inactive slots are not counted.  A multiply-add is 2 FLOPs.
- Least bytes of one decode step: the weights once (``bw``-bit codes plus a
  bfloat16 scale per output channel; biases, norms and the LM head at the
  configuration's dtype, bfloat16), one embedding row per active slot, the
  keys and values of every live context at bfloat16, and bfloat16
  activations in and out of every product.

What today's implementation reads beyond that (uint8 codes, a float32 LM
head, float32 caches) is not counted: it is what a later change can remove.
"""

from __future__ import annotations

DTYPE_BYTES = 2          # bfloat16


def linears(cfg: dict) -> list[tuple[int, int]]:
    """``(K, F)`` of each quantized product of one layer: q, k, v, o, then
    the FFN's gate (where it is gated), up and down."""
    d, hd, ff = cfg["d_model"], cfg["head_dim"], cfg["d_ff"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    gate = [(d, ff)] if cfg.get("gated_ffn", True) else []
    return [(d, q), (d, kv), (d, kv), (q, d)] + gate + [(d, ff), (ff, d)]


def _mm_flops_per_token(cfg: dict) -> float:
    return cfg["n_layers"] * sum(2.0 * k * f for k, f in linears(cfg))


def _attn_flops(cfg: dict, keys: float) -> float:
    """Scores and weighted values of one query over ``keys`` keys, all layers."""
    return cfg["n_layers"] * 4.0 * cfg["n_heads"] * cfg["head_dim"] * keys


def _head_flops(cfg: dict) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab_size"]


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One request's prefill: every prompt position through every layer
    (position ``i`` attends ``i + 1`` keys) and the LM head once."""
    p = prompt_len
    return (p * _mm_flops_per_token(cfg) + _attn_flops(cfg, p * (p + 1) / 2)
            + _head_flops(cfg))


def decode_flops(cfg: dict, keys: int) -> float:
    """One output token from a decode step that attends ``keys`` keys."""
    return _mm_flops_per_token(cfg) + _attn_flops(cfg, keys) + _head_flops(cfg)


def weight_bytes(cfg: dict) -> float:
    """Every weight a decode step reads once, at the stated formats."""
    d, L, bw = cfg["d_model"], cfg["n_layers"], cfg["bw"]
    per_layer = sum(k * f * bw / 8 + f * DTYPE_BYTES for k, f in linears(cfg))
    if cfg["qkv_bias"]:
        per_layer += sum(f for _, f in linears(cfg)[:3]) * DTYPE_BYTES
    norm_vecs = 2 if cfg["norm_kind"] == "layernorm" else 1
    per_layer += 2 * norm_vecs * d * DTYPE_BYTES
    return (L * per_layer + norm_vecs * d * DTYPE_BYTES
            + d * cfg["vocab_size"] * DTYPE_BYTES)


def kv_bytes_per_key(cfg: dict) -> float:
    return cfg["n_layers"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"] * DTYPE_BYTES


def act_bytes_per_token(cfg: dict) -> float:
    per_layer = sum(k + f for k, f in linears(cfg))
    return (cfg["n_layers"] * per_layer + cfg["d_model"] + cfg["vocab_size"]) \
        * DTYPE_BYTES


def decode_step(cfg: dict, keys: list[int]) -> tuple[float, float]:
    """``(FLOPs, least bytes)`` of one decode step whose active slots attend
    ``keys[i]`` keys each (the new token's own key included)."""
    flops = sum(decode_flops(cfg, k) for k in keys)
    nbytes = (weight_bytes(cfg)
              + len(keys) * (cfg["d_model"] * DTYPE_BYTES + act_bytes_per_token(cfg))
              + sum(keys) * kv_bytes_per_key(cfg))
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The larger of compute time at the bfloat16 peak and memory time at
    HBM bandwidth, and which of the two bounds it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
