"""Seeded random weights, made by the benchmark in the format the program serves.

The program declares the layout: which leaves its parameter tree has, their
shapes and dtypes (``jax.eval_shape`` of ``Model.quantize(Model.init(...))``,
so it computes nothing).  The values are the benchmark's own, drawn on the
device from ``--seed`` in one jitted call.  Each leaf takes the one rule
whose path ending it has (``g`` covers ``.../attn_norm/g``).  The shared
rules (:data:`SHARED_ENDINGS`):

- quantized linears (``codes``): every ``bw``-bit field of a code byte drawn
  uniformly over the codes with distinct grid values, so the values are
  uniform over ``-m..m`` and have mean 0 (for ``bw == 1``, both codes);
  ``scale``: per-output-channel scales that give the weight a standard
  deviation near ``1/sqrt(K)``;
- projection biases and norm shifts (``bias``, ``b``): normal around 0; norm
  gains (``g``) around 1;
- the embedding and the LM head (``embed``, ``lm_head/w``): normal, of
  standard deviation 1 and ``1/sqrt(d_model)``.

A configuration's reference may add rules for leaves its block has and the
shared rules do not cover, as a map ``LEAF_RULES`` from path ending to
``rule(key, s)`` (``s`` the leaf's ``ShapeDtypeStruct``; the result is cast
to its dtype).  A leaf that no rule covers, or that two cover, is an error.

Codes of mean 0 matter: a grid whose values have a mean adds to every
projection a rank-one part along the all-ones direction, which a LayerNorm
removes and an RMSNorm does not, so that over many RMSNorm layers every
position's hidden state turns to that one direction.

The reference (:mod:`bench.reference`) reads the same flat ``{path: array}``
dict, so it takes nothing that the program made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def grid_std(bw: int) -> float:
    """Standard deviation of the grid's values under the codes
    :func:`draw_codes` makes: uniform over ``-m..m``, ``m = 2^(bw-1) - 1``,
    for ``bw >= 2`` (``sqrt(m (m + 1) / 3)``, 4.32 at ``bw`` 4); 1 for the
    binary grid."""
    if bw == 1:
        return 1.0
    m = 2 ** (bw - 1) - 1
    return float(np.sqrt(m * (m + 1) / 3))


def draw_codes(key, shape, bw: int):
    """Packed code bytes of ``shape`` (uint8), one draw per byte.

    Byte field ``q`` (bits ``[q*bw, (q+1)*bw)``) holds code ``1 + d_q``, where
    the ``d_q`` are the base-``(2^bw - 1)`` digits of one uniform draw over
    ``[0, (2^bw - 1)^cpb)``: each field is uniform over ``1..2^bw-1`` and
    independent of the others.  Code 0 would repeat code 1's value ``-m``.
    For ``bw == 1`` the byte is uniform over ``0..255``.
    """
    cpb = 8 // bw
    if bw == 1:
        return jax.random.randint(key, shape, 0, 256, jnp.int32).astype(jnp.uint8)
    base = 2 ** bw - 1
    r = jax.random.randint(key, shape, 0, base ** cpb, jnp.int32)
    byte = jnp.zeros(shape, jnp.int32)
    for q in range(cpb):
        byte = byte | ((r // base ** q % base + 1) << (q * bw))
    return byte.astype(jnp.uint8)


def path_str(path) -> str:
    """A tree path such as ``segments/0/s0_D/attn/wq/codes``."""
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return "/".join(parts)


def seed_key(seed: int):
    """A PRNG key from any whole number (a seed may exceed 32 bits)."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def layout(abstract) -> dict:
    """``{path: ShapeDtypeStruct}`` of the program's parameter tree."""
    return {
        path_str(p): jax.ShapeDtypeStruct(x.shape, x.dtype)
        for p, x in jax.tree_util.tree_flatten_with_path(abstract)[0]
    }


SHARED_ENDINGS = ("codes", "scale", "embed", "lm_head/w", "g", "b", "bias")


def _ending(path: str, rules) -> str:
    """The one ending of ``path`` among the shared rules and ``rules``;
    raises where none or several match."""
    hits = [e for e in (*SHARED_ENDINGS, *rules)
            if path == e or path.endswith("/" + e)]
    if not hits:
        raise ValueError(f"no rule to make weight leaf {path!r}: give the "
                         f"configuration's reference a LEAF_RULES entry for it")
    if len(hits) > 1:
        raise ValueError(f"weight leaf {path!r} is covered by more than one "
                         f"rule: {hits}")
    return hits[0]


def _leaf(key, s, end: str, *, bw: int, d_model: int, k_in: int | None,
          rules):
    """A leaf of shape and dtype ``s`` by the rule of its ending ``end``:
    ``rules[end](key, s)`` where the reference gives it, else a shared rule,
    as the module says.  ``k_in`` is the input width of the linear a
    ``scale`` belongs to."""
    if end in rules:
        v = rules[end](key, s)
    elif end == "codes":
        v = draw_codes(key, s.shape, bw)
    elif end == "scale":
        u = jax.random.uniform(key, s.shape, jnp.float32, 0.5, 1.5)
        v = u / (grid_std(bw) * np.sqrt(k_in))
    elif end == "embed":
        v = jax.random.normal(key, s.shape, jnp.float32)
    elif end == "lm_head/w":
        v = jax.random.normal(key, s.shape, jnp.float32) / np.sqrt(d_model)
    elif end == "g":
        v = 1.0 + 0.1 * jax.random.normal(key, s.shape, jnp.float32)
    else:
        v = 0.1 * jax.random.normal(key, s.shape, jnp.float32)
    return v.astype(s.dtype)


def make_weights(abstract, seed: int, *, bw: int, d_model: int,
                 rules=None) -> dict:
    """Fill every leaf of ``abstract`` from ``seed``; returns ``{path: array}``.

    ``rules`` maps path endings to draw functions for leaves the shared rules
    do not cover (a reference's ``LEAF_RULES``).
    """
    flat = layout(abstract)
    rules = dict(rules or {})
    if both := sorted(set(rules) & set(SHARED_ENDINGS)):
        raise ValueError(f"endings {both} have a shared rule already")
    ends = {path: _ending(path, rules) for path in flat}
    cpb = 8 // bw

    def k_of(path):
        if not path.endswith("/scale"):
            return None
        return flat[path[: -len("scale")] + "codes"].shape[-1] * cpb

    def build(key):
        return {
            path: _leaf(
                jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF),
                s, ends[path], bw=bw, d_model=d_model, k_in=k_of(path),
                rules=rules,
            )
            for path, s in flat.items()
        }

    return jax.jit(build)(seed_key(seed))


def program_tree(abstract, flat: dict):
    """The program's parameter tree, holding the arrays of ``flat``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: flat[path_str(path)], abstract
    )
