"""Seeded random weights, made by the benchmark in the format the program serves.

The program declares the layout: which leaves its parameter tree has, their
shapes and dtypes (``jax.eval_shape`` of ``Model.quantize(Model.init(...))``,
so it computes nothing).  The values are the benchmark's own, drawn on the
device from ``--seed`` in one jitted call:

- quantized linears: uniform ``bw``-bit codes packed into bytes, and
  per-output-channel scales that give the weight a standard deviation near
  ``1/sqrt(K)``;
- projection biases and norm shifts: normal around 0; norm gains around 1;
- the embedding and the LM head: normal, of standard deviation 1 and
  ``1/sqrt(d_model)``.

The reference (:mod:`bench.reference`) reads the same flat ``{path: array}``
dict, so it takes nothing that the program made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def grid_std(bw: int) -> float:
    """Standard deviation of the symmetric int grid's values under uniform
    codes (code 0 duplicates -max)."""
    lim = 2 ** (bw - 1) - 1
    vals = np.clip(np.arange(2 ** bw) - 2 ** (bw - 1), -lim, lim)
    return float(np.sqrt(np.mean(vals.astype(np.float64) ** 2)))


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


def _leaf(key, path: str, s, *, bw: int, d_model: int, k_in: int | None):
    name = path.rsplit("/", 1)[-1]
    if name == "codes":
        return jax.random.randint(key, s.shape, 0, 256, jnp.int32).astype(s.dtype)
    if name == "scale":
        u = jax.random.uniform(key, s.shape, jnp.float32, 0.5, 1.5)
        return (u / (grid_std(bw) * np.sqrt(k_in))).astype(s.dtype)
    if path == "embed":
        return jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype)
    if path == "lm_head/w":
        w = jax.random.normal(key, s.shape, jnp.float32) / np.sqrt(d_model)
        return w.astype(s.dtype)
    if name == "g":
        g = 1.0 + 0.1 * jax.random.normal(key, s.shape, jnp.float32)
        return g.astype(s.dtype)
    if name in ("b", "bias"):
        return (0.1 * jax.random.normal(key, s.shape, jnp.float32)).astype(s.dtype)
    raise ValueError(f"no rule to make weight leaf {path!r}")


def make_weights(abstract, seed: int, *, bw: int, d_model: int) -> dict:
    """Fill every leaf of ``abstract`` from ``seed``; returns ``{path: array}``."""
    flat = layout(abstract)
    cpb = 8 // bw

    def k_of(path):
        if not path.endswith("/scale"):
            return None
        return flat[path[: -len("scale")] + "codes"].shape[-1] * cpb

    def build(key):
        return {
            path: _leaf(
                jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF),
                path, s, bw=bw, d_model=d_model, k_in=k_of(path),
            )
            for path, s in flat.items()
        }

    return jax.jit(build)(seed_key(seed))


def program_tree(abstract, flat: dict):
    """The program's parameter tree, holding the arrays of ``flat``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: flat[path_str(path)], abstract
    )
