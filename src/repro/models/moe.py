"""Mixture-of-Experts block: top-k routing, capacity-based dispatch, EP.

Dispatch is gather + batched-matmul (linear in token count — no quadratic
GShard dispatch einsum): tokens are scattered into per-expert capacity slots,
experts run as one batched GEMM over ``[E, C, d]``, and results scatter-add
back weighted by the gate.  Overflow beyond ``capacity_factor`` is dropped
(standard Switch semantics).

Expert parallelism: :func:`moe_apply` optionally runs inside ``shard_map``
over the TP/EP mesh axis — each shard computes *its local experts* for the
tokens of its data shard (tokens are already replicated across the model
axis), then one ``psum`` over the EP axis combines expert outputs.  That is
the whole EP communication: no all-to-all is needed because token activations
never leave their data shard.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, linear

from jax import shard_map

if TYPE_CHECKING:  # import only for annotations: models must not require dist
    from repro.dist.sharding import ShardCtx

Array = jax.Array


def moe_init(cfg: ModelConfig, key) -> dict:
    e = cfg.moe
    d, f = cfg.d_model, e.d_ff_expert
    ks = jax.random.split(key, 5)
    import numpy as np

    std = 1.0 / np.sqrt(d)
    p = {
        "router": {"w": jax.random.normal(ks[0], (d, e.n_experts), jnp.float32) * std},
        "w_gate": jax.random.normal(ks[1], (e.n_experts, d, f), jnp.float32) * std,
        "w_up": jax.random.normal(ks[2], (e.n_experts, d, f), jnp.float32) * std,
        "w_down": jax.random.normal(ks[3], (e.n_experts, f, d), jnp.float32) * (1.0 / np.sqrt(f)),
    }
    if e.n_shared_experts:
        from repro.models.ffn import ffn_init

        p["shared"] = ffn_init(cfg, ks[4], d_ff=e.n_shared_experts * f)
    return p


def _dispatch_compute(
    xt: Array,            # [T, d] tokens
    gates: Array,         # [T, k] combine weights (already normalized)
    eidx: Array,          # [T, k] global expert ids
    w_gate: Array,        # [El, d, f] local experts
    w_up: Array,
    w_down: Array,
    *,
    e_first: Array | int, # first global id of the local expert range
    e_total: int,
    capacity_factor: float,
    act_kind: str,
) -> Array:
    """Capacity-slot dispatch for the local expert slice; returns [T, d]."""
    t, k = gates.shape
    el = w_gate.shape[0]
    # Per-shard capacity: slots per *local* expert given the local token count.
    cap = max(int((t * k / e_total) * capacity_factor), 4)
    slot_e = eidx.reshape(-1)                           # [T*k] global ids
    slot_g = gates.reshape(-1)
    slot_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    local_e = slot_e - e_first                           # [T*k]
    is_local = (local_e >= 0) & (local_e < el)
    oh = jax.nn.one_hot(jnp.where(is_local, local_e, el), el + 1, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - 1                     # position within expert
    slot_pos = jnp.take_along_axis(
        pos, jnp.where(is_local, local_e, el)[:, None], axis=1
    )[:, 0]
    keep = is_local & (slot_pos < cap)

    # Scatter token ids and gates into [El, cap] buffers (T = padding row).
    buf_tok = jnp.full((el, cap), t, dtype=jnp.int32)
    buf_gate = jnp.zeros((el, cap), dtype=gates.dtype)
    se = jnp.where(keep, local_e, el)                    # overflow -> dropped
    sp = jnp.where(keep, slot_pos, 0)
    buf_tok = buf_tok.at[(se, sp)].set(
        jnp.where(keep, slot_tok, t), mode="drop"
    )
    buf_gate = buf_gate.at[(se, sp)].set(
        jnp.where(keep, slot_g, 0.0), mode="drop"
    )

    x_pad = jnp.concatenate([xt, jnp.zeros((1, xt.shape[1]), xt.dtype)], axis=0)
    xg = x_pad[buf_tok]                                   # [El, cap, d]
    h = layers.activation(
        jnp.einsum("ecd,edf->ecf", xg, w_gate.astype(xg.dtype)), act_kind
    ) * jnp.einsum("ecd,edf->ecf", xg, w_up.astype(xg.dtype))
    out_e = jnp.einsum("ecf,efd->ecd", h, w_down.astype(xg.dtype))
    out_e = out_e * buf_gate[..., None].astype(xg.dtype)

    y = jnp.zeros((t + 1, xt.shape[1]), xt.dtype)
    y = y.at[buf_tok.reshape(-1)].add(out_e.reshape(-1, xt.shape[1]), mode="drop")
    return y[:t]


def _route(xt: Array, router_w: Array, cfg: ModelConfig):
    e = cfg.moe
    logits = (xt.astype(jnp.float32)) @ router_w          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, e.top_k)           # [T, k]
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss.
    dense_frac = jnp.mean(probs, axis=0)
    hard_frac = jnp.mean(
        jax.nn.one_hot(eidx[:, 0], e.n_experts, dtype=jnp.float32), axis=0
    )
    aux = e.n_experts * jnp.sum(dense_frac * hard_frac)
    return gates.astype(xt.dtype), eidx.astype(jnp.int32), aux


def moe_apply(
    p: dict,
    x: Array,
    cfg: ModelConfig,
    ctx: Optional["ShardCtx"] = None,
) -> tuple[Array, Array]:
    """Returns (y, aux_loss).  ``ctx`` enables expert parallelism."""
    e = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    gates, eidx, aux = _route(xt, p["router"]["w"], cfg)
    # LoCaLUT-quantized experts arrive as stacked QuantizedLinear; decode to
    # dense for the batched einsum (the fused Pallas kernel is the TPU path).
    from repro.models.model import maybe_dequant

    w_gate = maybe_dequant(p["w_gate"], x.dtype)
    w_up = maybe_dequant(p["w_up"], x.dtype)
    w_down = maybe_dequant(p["w_down"], x.dtype)

    tp_size = 1 if ctx is None or ctx.mesh is None else ctx.tp_size()
    if tp_size > 1 and e.n_experts % tp_size != 0:
        # Uneven expert split: integer division would give shards 0 experts
        # (or drop the remainder).  Fall back to replicated experts — still
        # correct, just without expert parallelism for this layer.
        tp_size = 1
    if tp_size == 1:
        y = _dispatch_compute(
            xt, gates, eidx, w_gate, w_up, w_down,
            e_first=0, e_total=e.n_experts,
            capacity_factor=e.capacity_factor, act_kind=cfg.ffn_act,
        )
    else:
        tp = ctx.tp_axis
        el = e.n_experts // tp_size
        dp = ctx.dp_axes

        def shard_fn(xt_l, gates_l, eidx_l, wg_l, wu_l, wd_l):
            rank = jax.lax.axis_index(tp)
            y_l = _dispatch_compute(
                xt_l, gates_l, eidx_l, wg_l, wu_l, wd_l,
                e_first=rank * el, e_total=e.n_experts,
                capacity_factor=e.capacity_factor, act_kind=cfg.ffn_act,
            )
            return jax.lax.psum(y_l, tp)

        tok_spec = P(dp, None)
        y = shard_map(
            shard_fn,
            mesh=ctx.mesh,
            in_specs=(tok_spec, tok_spec, tok_spec, P(tp, None, None),
                      P(tp, None, None), P(tp, None, None)),
            out_specs=tok_spec,
        )(xt, gates, eidx, w_gate, w_up, w_down)

    if "shared" in p:
        from repro.models.ffn import ffn_apply

        y = y + ffn_apply(p["shared"], x, cfg).reshape(b * s, d)
    return y.reshape(b, s, d), aux
