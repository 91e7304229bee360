"""Training driver: fault-tolerant supervised loop with checkpoint/restart.

Runs at whatever scale the process sees: one CPU device in a smoke run; on a
real fleet the same driver runs under ``jax.distributed`` with the production
mesh (``--mesh``), FSDP+TP shardings, async checkpoints, and the restart
supervisor.

Example (CPU smoke):
    PYTHONPATH=src python -m repro.launch.train --arch chatglm3-6b --smoke \
        --steps 20 --batch 4 --seq 64
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist import sharding as shd
from repro.ft import supervisor as sup
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.train import optimizer as opt
from repro.train import train_step as ts


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="runs/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", choices=["none", "single", "multi"], default="none")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject failures at these steps (FT demo)")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    ctx = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        dp = tuple(a for a in mesh.axis_names if a != "model")
        ctx = shd.ShardCtx(mesh=mesh, dp_axes=dp, fsdp=True)

    data = SyntheticLM(
        DataConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
            prefix_seq=cfg.frontend_seq if cfg.frontend else 0,
            prefix_dim=cfg.frontend_dim if cfg.frontend else 0,
        )
    )
    step_fn = jax.jit(
        ts.make_train_step(model, opt.AdamWConfig(lr=args.lr), ctx=ctx, remat=True)
    )

    t0 = time.time()
    losses = []

    def on_metrics(step, m):
        losses.append(float(m["loss"]))
        if step % 10 == 0 or step == args.steps:
            dt = time.time() - t0
            print(
                f"step {step:5d} loss {losses[-1]:.4f} "
                f"gnorm {float(m['grad_norm']):.3f} ({dt:.1f}s)", flush=True
            )

    state, restarts = sup.run_supervised(
        cfg=sup.SupervisorConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
        init_state_fn=lambda: ts.init_train_state(model, jax.random.PRNGKey(0)),
        train_step_fn=step_fn,
        batch_at=lambda i: jax.tree.map(jnp.asarray, data.batch_at(i)),
        n_steps=args.steps,
        injector=sup.FailureInjector(fail_at_steps=tuple(args.fail_at)),
        on_metrics=on_metrics,
    )
    print(f"done: {args.steps} steps, {restarts} restarts, final loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
