"""Serving driver: LoCaLUT-quantized batched inference.

Quantizes the model with the paper's technique (packed low-bit weight codes)
and serves batched requests through prefill + greedy decode.

Example (CPU smoke):
    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-12b --smoke \
        --requests 4 --prompt-len 8 --max-new 12 --bw 2 --ba 4
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core import LutLinearSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.serve.serving import Request, ServeEngine


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--bw", type=int, default=4)
    ap.add_argument("--ba", type=int, default=4)
    ap.add_argument("--dense", action="store_true", help="skip quantization")
    ap.add_argument("--no-prepare", dest="prepare", action="store_false",
                    help="serve raw QuantizedLinear params (skip the "
                         "weight-stationary prepare step)")
    ap.add_argument("--decode", default="scan",
                    choices=["scan", "chunked", "loop"],
                    help="continuous in-flight batching (1 host sync per "
                         "admission wave), the fixed-chunk fused-scan "
                         "baseline, or the seed per-token loop")
    ap.add_argument("--prompt-bucket", type=int, default=8,
                    help="power-of-two prompt-length bucketing floor (1 "
                         "disables bucketing; pad-masked prefill makes the "
                         "bucket padding output-invariant either way)")
    ap.add_argument("--profile", default="baseline", choices=["baseline", "serve"],
                    help="apply the EXPERIMENTS.md §4-validated perf profile")
    ap.add_argument("--mode", default="dequant",
                    # no "stream": the slice-streaming dataflow is
                    # host-simulated and cannot run inside the jitted serve
                    # programs (plans exclude it for the same reason)
                    choices=["dequant", "lut", "pallas"],
                    help="base execution mode of the quantized projections")
    ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                    help="serve through a repro.tune ModelPlan artifact "
                         "(per-layer autotuned configs; fingerprint-checked)")
    ap.add_argument("--autotune", type=float, default=None, metavar="BUDGET_MB",
                    help="run the repro.tune planner inline under this "
                         "LUT-capacity budget (MB) and serve the result")
    ap.add_argument("--prepared-ckpt", default=None, metavar="DIR",
                    help="prepared-pytree checkpoint dir: restore the "
                         "weight-stationary serve tree from it when present "
                         "(fast cold start, skipping quantize+prepare "
                         "entirely), else save one after preparing")
    ap.add_argument("--calibrate", type=int, default=None, metavar="TOKENS",
                    help="freeze per-layer activation scales from a seeded "
                         "synthetic calibration batch of this many tokens "
                         "at prepare time: the int-lut engines become "
                         "batch-composition invariant, putting them in the "
                         "bit-exact replay domain that --request-log "
                         "kill+replay and hot-swap token-identity rely on")
    ap.add_argument("--request-log", default=None, metavar="PATH",
                    help="serve under repro.serve.ops.LiveServer with a "
                         "durable request log at PATH: every admission "
                         "wave's tokens are fsynced, and a crashed engine "
                         "restarts + replays in-flight slots "
                         "token-identically (requires --decode scan)")
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="record the zero-sync repro.obs trace and write it "
                         "as Chrome/Perfetto trace_event JSON (load in "
                         "chrome://tracing or ui.perfetto.dev); recording "
                         "happens only at existing host syncs, so tokens "
                         "and sync counts are identical with or without it")
    ap.add_argument("--metrics", nargs="?", const="-", default=None,
                    metavar="OUT_JSONL",
                    help="print the repro.obs metrics + SLO snapshot after "
                         "serving; with a PATH, also write the full metrics "
                         "surface (snapshot, SLO stats, per-request "
                         "lifecycle records) as JSONL")
    args = ap.parse_args()
    if args.plan and args.autotune is not None:
        ap.error("--plan and --autotune are mutually exclusive")
    if (args.plan or args.autotune is not None) and args.dense:
        ap.error("--plan/--autotune require a quantized model")
    if args.prepared_ckpt and args.dense:
        ap.error("--prepared-ckpt requires a quantized model")
    if args.request_log and args.decode != "scan":
        ap.error("--request-log needs the continuous driver (--decode scan): "
                 "wave-level token logging is its host-sync hook")
    if args.calibrate is not None and (
        args.dense or not args.prepare
        or args.plan or args.autotune is not None
    ):
        ap.error("--calibrate freezes activation scales during the plain "
                 "prepare step: it requires a quantized model with "
                 "--prepare (no --dense/--no-prepare/--plan/--autotune)")

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.profile != "baseline":
        from repro.models.profiles import apply_perf_profile

        cfg = apply_perf_profile(cfg, args.profile)
        print(f"perf profile: {args.profile}")
    model = build_model(cfg)
    plan = None
    restored = False
    if args.prepared_ckpt:
        from repro.ckpt import checkpoint as ckpt

        latest = ckpt.latest_step(args.prepared_ckpt)
        if latest is not None:
            t0 = time.time()
            params = ckpt.restore_prepared(args.prepared_ckpt, latest)
            print(f"restored prepared checkpoint step {latest} from "
                  f"{args.prepared_ckpt} in {time.time()-t0:.2f}s "
                  f"(skipped quantize + prepare)")
            restored = True
    if not restored:
        params = model.init(jax.random.PRNGKey(0))
    if not restored and not args.dense:
        t0 = time.time()
        params = model.quantize(
            params, LutLinearSpec(bw=args.bw, ba=args.ba, mode=args.mode)
        )
        print(f"quantized W{args.bw}A{args.ba} ({args.mode}) in {time.time()-t0:.1f}s")
        nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
        print(f"packed parameter bytes: {nbytes:,}")
        if args.plan:
            from repro.tune import ModelPlan

            plan = ModelPlan.load(args.plan)
            print(f"loaded plan {args.plan}: {len(plan.layers)} layers, "
                  f"{plan.total_bytes:,} B under a {plan.budget_bytes:,} B budget")
        elif args.autotune is not None:
            from repro.tune import plan_model

            t0 = time.time()
            plan = plan_model(
                params,
                lut_budget_bytes=int(args.autotune * 1024 * 1024),
                n_hint=args.batch,
            )
            print(f"autotuned {len(plan.layers)} layers in {time.time()-t0:.1f}s: "
                  f"{plan.total_bytes:,} B spent of "
                  f"{plan.budget_bytes:,} B budget")
        elif args.prepare:
            t0 = time.time()
            if args.calibrate is not None:
                import jax.numpy as jnp

                crng = np.random.default_rng(1)
                cal = jnp.asarray(
                    crng.integers(1, cfg.vocab_size,
                                  (2, max(1, args.calibrate // 2))),
                    jnp.int32,
                )
                params = model.prepare(params, calibrate=cal)
                print(f"prepared + froze activation scales on {cal.size} "
                      f"synthetic calibration tokens in {time.time()-t0:.1f}s "
                      f"(int-lut serving is now batch-composition invariant)")
            else:
                params = model.prepare(params)
                print(f"prepared weight-stationary serve products in "
                      f"{time.time()-t0:.1f}s")

    obs = None
    if args.trace or args.metrics:
        from repro.obs import Observer

        obs = Observer()
    # ``plan`` routes through ServeEngine's autotuned path (spec rewrite +
    # prepare happen inside, fingerprint-checked).
    eng = ServeEngine(model, params, batch=args.batch, max_seq=args.max_seq,
                      decode=args.decode, prompt_bucket=args.prompt_bucket,
                      plan=plan, obs=obs)
    if args.prepared_ckpt and not restored and (args.prepare or plan is not None):
        from repro.ckpt import checkpoint as ckpt

        t0 = time.time()
        ckpt.save_prepared(args.prepared_ckpt, 0, eng.params)
        print(f"saved prepared checkpoint to {args.prepared_ckpt} in "
              f"{time.time()-t0:.2f}s (next cold start restores it)")
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for _ in range(args.requests)
    ]
    t0 = time.time()
    if args.request_log:
        from repro.serve.ops import LiveServer

        eng_params = eng.params   # already prepared / plan-applied
        server = LiveServer(
            lambda: ServeEngine(model, eng_params, batch=args.batch,
                                max_seq=args.max_seq, decode="scan",
                                prompt_bucket=args.prompt_bucket),
            log_path=args.request_log,
            obs=obs, trace_path=args.trace,
        )
        outs = server.serve(reqs)
        eng = server.engine
        print(f"live serve: {server.restarts} restarts, log at "
              f"{args.request_log}")
    else:
        outs = eng.generate(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(o) for o in outs)
    print(f"served {len(reqs)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s incl. compile), "
          f"{eng.host_syncs} host syncs")
    if args.decode == "scan":
        print(f"admission order (request -> slot): {eng.admissions}")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: {o}")
    if obs is not None:
        from repro.obs import snapshot_text, write_metrics_jsonl, write_perfetto

        if args.trace:
            path = write_perfetto(obs, args.trace)
            print(f"perfetto trace: {path} ({len(obs.tracer)} events, "
                  f"{obs.tracer.dropped} dropped)")
        if args.metrics:
            print(snapshot_text(obs, title=f"repro.serve {args.arch}"))
            if args.metrics != "-":
                path = write_metrics_jsonl(obs, args.metrics)
                print(f"metrics jsonl: {path}")


if __name__ == "__main__":
    main()
