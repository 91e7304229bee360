"""Pipeline parallelism: a GPipe schedule as ``shard_map`` + ``ppermute``.

:func:`pipeline_apply` spreads a stack of stage parameters over the mesh's
``stage`` axis and streams microbatches through the ring.  Step ``t`` has
stage ``s`` working on microbatch ``t - s`` (the classic GPipe diagonal);
activations rotate one hop per step via ``ppermute``, so the whole schedule
is ``n_micro + n_stages - 1`` steps with every chip busy in the steady
state.

Stages must be shape-preserving (``stage_fn(w, x)`` returns an activation
shaped like ``x``) — true for the residual-block stacks this repo pipelines.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

Array = jax.Array


def pipeline_apply(
    stage_fn: Callable[[Any, Array], Array],
    stage_params: Any,
    microbatches: Array,
    mesh,
    *,
    axis: str = "stage",
) -> Array:
    """Apply ``n_stages`` stages to every microbatch; returns ``[n_micro, ...]``.

    ``stage_params`` is a pytree whose leaves lead with the stage dim
    (``[n_stages, ...]``); ``microbatches`` is ``[n_micro, *mb_shape]`` and
    is replicated (each stage only ever reads the activation handed to it).
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
    n_stages = int(dict(mesh.shape)[axis])
    n_micro = int(microbatches.shape[0])
    lead = {int(leaf.shape[0]) for leaf in jax.tree.leaves(stage_params)}
    if lead != {n_stages}:
        raise ValueError(
            f"stage_params leading dims {sorted(lead)} != mesh {axis} size {n_stages}"
        )

    p_specs = jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stage_params
    )
    n_steps = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_prog(p_local, xs):
        # p_local leaves are [1, ...] (this stage's slice); xs is the full
        # replicated [n_micro, *mb] stack.
        w = jax.tree.map(lambda a: a[0], p_local)
        sid = jax.lax.axis_index(axis)

        def step(carry, t):
            buf, outs = carry
            # Stage 0 injects microbatch t; later stages consume the
            # activation rotated in from their predecessor.
            inp = jnp.where(sid == 0, xs[jnp.clip(t, 0, n_micro - 1)], buf)
            y = stage_fn(w, inp)
            nxt = jax.lax.ppermute(y, axis, perm)
            mb = t - (n_stages - 1)
            done = (sid == n_stages - 1) & (mb >= 0)
            idx = jnp.clip(mb, 0, n_micro - 1)
            outs = outs.at[idx].set(jnp.where(done, y, outs[idx]))
            return (nxt, outs), None

        # The carry varies over the stage axis (each stage holds its own
        # activation and outputs), so its initial value must be marked so.
        init = jax.lax.pcast(
            (jnp.zeros_like(xs[0]), jnp.zeros_like(xs)), axis, to="varying"
        )
        (_, outs), _ = jax.lax.scan(step, init, jnp.arange(n_steps))
        # Only the last stage wrote results; the psum replicates them so the
        # output is unsharded on the stage axis.
        return jax.lax.psum(outs, axis)

    return shard_map(
        stage_prog,
        mesh=mesh,
        in_specs=(p_specs, P()),
        out_specs=P(),
    )(stage_params, microbatches)
