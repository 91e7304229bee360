#!/usr/bin/env python3
"""Bring-up smoke run of the LoCaLUT serving path on TPU.

With no arguments it needs one TPU chip.  It serves stablelm-12b at its
published widths, cut to 4 layers, through the objects a user calls
(``Model.quantize`` -> ``Model.prepare`` -> ``ServeEngine.generate``), once
with ``mode="dequant"`` and once with ``mode="pallas"``.  For each mode it
checks the prefill logits against a float32 reference forward and the
continuous scheduler's greedy tokens against the per-token loop oracle.

With ``--chips 4`` it runs only the tensor-parallel path: the raw quantized
parameters sharded over a ``(data=1, model=4)`` mesh, prefill plus decode
steps, compared with the same model on one chip.

Everything runs in this one process.  The last line of standard output is
``{"ok": true, "device": {...}}``; any failure raises and exits non-zero
before it is printed.  The timings printed are one smoke run, not benchmark
figures.

    python3 chip_smoke.py
    python3 chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

SEED = 0
ARCH = "stablelm-12b"
# stablelm-12b's 40 layers would sit on 10 chips as pipeline stages of 4
# layers each; this chip holds one stage.  No width is changed.
N_LAYERS = 4
BW, BA = 4, 4                  # W4A4, the launch/serve.py defaults
BATCH = 8
MAX_SEQ = 2048
N_REQUESTS = 16
PROMPT_LEN = (64, 512)         # inclusive range, drawn per request
NEW_TOKENS = (32, 128)
ORACLE_REQUESTS = 4            # requests also served by the loop oracle
TP_DECODE_STEPS = 8
TP_CHIPS = 4

# Logit tolerance, as RMS(served - reference) / RMS(reference) over the
# last-position logits of the batch.  The reference is the same model's
# dequantized weights run in float32 at "highest" matmul precision, so
# quantization error is not in the gap.  What is in it is bfloat16 rounding:
# the served model rounds matmul inputs, norm outputs and the residual stream
# to 8 significant bits (unit roundoff 2^-9 ~ 2e-3) some 30 times in series
# across 4 layers.  Those errors are independent, so they add in quadrature
# to about 2% of the logits' RMS (1.7-1.9% measured on CPU at d_model 64 and
# 512 with 4 layers).  The bound leaves 2.5x room for that.  A wiring fault
# (a wrong or transposed weight, a missed layer, a misplaced position or pad)
# puts the gap near 1.4, the ratio for unrelated logits.  The tensor-parallel
# comparison uses the same bound: both sides are bfloat16 and differ in how
# partial sums are split and reduced.
LOGIT_TOL = 5e-2


def check_device(devices, chips: int = 1) -> None:
    """Refuse to run anywhere but on ``chips`` TPU devices."""
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX's default device is {d.platform!r}"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke --chips {chips} needs {chips} devices; JAX sees "
            f"{len(devices)}"
        )


def smoke_config():
    from repro.configs import get_config

    return dataclasses.replace(get_config(ARCH), n_layers=N_LAYERS)


def quantized_params(model, seed: int = SEED):
    """Seeded random weights, quantized W4A4 on the device in one program
    (the float32 layer weights never leave it)."""
    from repro.core import LutLinearSpec

    spec = LutLinearSpec(bw=BW, ba=BA)
    init_q = jax.jit(lambda key: model.quantize(model.init(key), spec))
    return init_q(jax.random.PRNGKey(seed))


def make_requests(cfg, seed: int = SEED):
    from repro.serve.serving import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt=rng.integers(
                0, cfg.vocab_size, int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
            ).astype(np.int32),
            max_new_tokens=int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1)),
        )
        for _ in range(N_REQUESTS)
    ]


def padded_prompts(reqs):
    """Left-pad the prompts of ``reqs`` into one [len(reqs), max len] matrix."""
    plen = max(len(r.prompt) for r in reqs)
    toks = np.zeros((len(reqs), plen), np.int32)
    pad = np.zeros((len(reqs),), np.int32)
    for i, r in enumerate(reqs):
        toks[i, plen - len(r.prompt):] = r.prompt
        pad[i] = plen - len(r.prompt)
    return jnp.asarray(toks), jnp.asarray(pad)


def is_quantized(x) -> bool:
    from repro.core import QuantizedLinear

    return isinstance(x, QuantizedLinear)


def with_mode(params, mode: str):
    """The same quantized weights, served through execution mode ``mode``."""
    return jax.tree.map(
        lambda q: dataclasses.replace(q, spec=dataclasses.replace(q.spec, mode=mode))
        if is_quantized(q) else q,
        params, is_leaf=is_quantized,
    )


def prefill_logits(model, params, toks, pad, ctx=None):
    """Last-position prefill logits [B, V] through ``Model.prefill``."""
    from repro.serve.serving import make_prefill_step

    caches = model.init_cache(toks.shape[0], MAX_SEQ, dtype=jnp.float32)
    logits, _ = jax.jit(make_prefill_step(model, ctx=ctx))(
        params, toks, caches, pad_len=pad
    )
    return np.asarray(logits[:, -1], np.float32)


def reference_logits(model, qparams, toks, pad):
    """Float32 forward of the same model over its dequantized dense weights."""
    from repro.models.model import Model, maybe_dequant

    def dense(q):
        leaf = {"w": maybe_dequant(q, jnp.float32)}
        if q.bias is not None:
            leaf["b"] = q.bias
        return leaf

    ref_params = jax.tree.map(
        lambda x: dense(x) if is_quantized(x) else x, qparams, is_leaf=is_quantized
    )
    ref_model = Model(dataclasses.replace(model.cfg, dtype="float32"))
    with jax.default_matmul_precision("highest"):
        return prefill_logits(ref_model, ref_params, toks, pad)


def logit_gap(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want * want)))


def check_gap(what: str, gap: float) -> None:
    print(f"{what}: RMS logit gap / ref RMS = {gap!r} (tolerance {LOGIT_TOL})")
    if not gap <= LOGIT_TOL:
        raise RuntimeError(f"{what}: logit gap {gap!r} exceeds {LOGIT_TOL}")


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def adjudicate(model, params, req, loop_toks, scan_toks, margin: float) -> str:
    """Accept a scan/loop divergence only at a near-tie of the two tokens.

    Both drivers run the same model on differently padded batches, so their
    logits differ by rounding; at a step where the top two logits are closer
    than that, greedy decoding may pick either.  ``margin`` is in logits: if
    every served logit is within e of the exact one, the two drivers' picks
    put the exact gap of the two tokens within 2e, and this third served run
    measures it within 4e.  A faulty scheduler picks a token far below the
    top logit.
    """
    t = next(i for i, (a, b) in enumerate(zip(loop_toks, scan_toks)) if a != b)
    seq = np.concatenate([req.prompt, np.asarray(loop_toks[:t], np.int32)])
    lg = prefill_logits(
        model, params, jnp.asarray(seq[None]), jnp.zeros((1,), jnp.int32)
    )[0]
    a, b = loop_toks[t], scan_toks[t]
    tie = abs(float(lg[a]) - float(lg[b]))
    if not tie <= margin:
        raise RuntimeError(
            f"scan and loop diverge at step {t} ({a} vs {b}) with a logit gap "
            f"of {tie!r}, above the rounding margin {margin!r}"
        )
    return f"diverges at step {t}, a near-tie ({tie!r} <= {margin!r} logits)"


def serve_mode(model, qparams, mode, reqs, toks, pad, ref) -> None:
    from repro.serve.serving import ServeEngine

    t0 = time.perf_counter()
    params = model.prepare(with_mode(qparams, mode))
    jax.block_until_ready(params)
    print(f"[{mode}] prepared in {time.perf_counter() - t0!r} s")

    logits = prefill_logits(model, params, toks, pad)
    check_gap(f"[{mode}] prefill vs float32 reference", logit_gap(logits, ref))
    margin = 4 * float(np.max(np.abs(logits - ref)))

    eng = ServeEngine(model, params, batch=BATCH, max_seq=MAX_SEQ, decode="scan")
    t0 = time.perf_counter()
    outs = eng.generate(reqs)
    t_first = time.perf_counter() - t0
    syncs = eng.host_syncs
    t0 = time.perf_counter()
    again = eng.generate(reqs)
    t_warm = time.perf_counter() - t0
    if again != outs:
        raise RuntimeError(f"[{mode}] a second generate gave different tokens")
    n_tok = sum(len(o) for o in outs)
    print(f"[{mode}] first generate (compiles included): {t_first!r} s")
    print(f"[{mode}] warm generate: {t_warm!r} s, {n_tok} tokens, "
          f"{n_tok / t_warm!r} tok/s, {eng.host_syncs - syncs} host syncs "
          f"(smoke run, not a benchmark)")
    print(f"[{mode}] peak_bytes_in_use so far: {peak_bytes()}")

    oracle = ServeEngine(model, params, batch=BATCH, max_seq=MAX_SEQ, decode="loop")
    loop_outs = oracle.generate(reqs[:ORACLE_REQUESTS])
    same = total = 0
    for i, (lo, sc) in enumerate(zip(loop_outs, outs)):
        if len(lo) != len(sc):
            raise RuntimeError(f"[{mode}] request {i}: scan gave {len(sc)} "
                               f"tokens, loop {len(lo)}")
        match = sum(a == b for a, b in zip(lo, sc))
        same, total = same + match, total + len(lo)
        verdict = "identical" if lo == sc else adjudicate(
            model, params, reqs[i], lo, sc, margin)
        print(f"[{mode}] request {i}: scan vs loop {match}/{len(lo)} tokens, "
              f"{verdict}")
    print(f"[{mode}] scan vs loop oracle agreement: {same}/{total} tokens")


def run_one_chip() -> None:
    from repro.models.model import build_model

    cfg = smoke_config()
    model = build_model(cfg)
    print(f"config: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} W{BW}A{BA} batch={BATCH} max_seq={MAX_SEQ}")
    t0 = time.perf_counter()
    qparams = quantized_params(model)
    jax.block_until_ready(qparams)
    print(f"init + quantize: {time.perf_counter() - t0!r} s")
    reqs = make_requests(cfg)
    toks, pad = padded_prompts(reqs[:BATCH])
    t0 = time.perf_counter()
    ref = reference_logits(model, qparams, toks, pad)
    print(f"float32 reference prefill: {time.perf_counter() - t0!r} s")
    for mode in ("dequant", "pallas"):
        serve_mode(model, qparams, mode, reqs, toks, pad, ref)


def decode_logits(model, params, toks, pad, ctx=None, feed=None):
    """Prefill plus ``TP_DECODE_STEPS`` decode steps; per-step logits [B, V].

    ``feed`` gives the token fed at each decode step (greedy when None), so
    two runs can be compared on identical inputs.  Returns the logits and
    the fed tokens.
    """
    from repro.dist import sharding as shd
    from repro.serve.serving import make_prefill_step

    caches = model.init_cache(toks.shape[0], MAX_SEQ, dtype=jnp.float32)
    if ctx is not None:
        caches = jax.device_put(caches, shd.to_shardings(
            shd.cache_specs(model.cfg, caches, ctx), ctx.mesh))
    prefill = jax.jit(make_prefill_step(model, ctx=ctx))
    step = jax.jit(
        lambda p, t, c, pos, pad_: model.decode_step(
            p, t, c, pos, ctx=ctx, pad_len=pad_),
        donate_argnums=(2,),
    )
    lg, caches = prefill(params, toks, caches, pad_len=pad)
    out = [lg[:, -1]]
    fed = []
    plen = toks.shape[1]
    for i in range(TP_DECODE_STEPS):
        tok = feed[i] if feed is not None else jnp.argmax(out[-1], -1)[:, None]
        fed.append(tok)
        lg, caches = step(params, tok.astype(jnp.int32), caches,
                          jnp.int32(plen + i), pad)
        out.append(lg[:, -1])
    if ctx is not None:
        mesh_devs = set(ctx.mesh.devices.flat)
        for x in jax.tree.leaves((out, caches)):
            if x.sharding.device_set != mesh_devs:
                raise RuntimeError("a tensor-parallel output left the mesh")
    return [np.asarray(x, np.float32) for x in out], fed


def run_tensor_parallel() -> None:
    """Raw dequant params TP-sharded over 4 chips vs the same model on one."""
    from jax.sharding import Mesh

    from repro.dist import sharding as shd
    from repro.models.model import build_model

    cfg = smoke_config()
    model = build_model(cfg)
    devices = jax.devices()[:TP_CHIPS]
    mesh = Mesh(np.array(devices).reshape(1, TP_CHIPS), ("data", "model"))
    ctx = shd.ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    qparams = quantized_params(model)
    reqs = make_requests(cfg)
    toks, pad = padded_prompts(reqs[:BATCH])

    one, fed = decode_logits(model, qparams, toks, pad)
    specs = shd.param_specs(cfg, qparams, ctx)
    params_tp = jax.device_put(qparams, shd.to_shardings(specs, mesh))
    tp, _ = decode_logits(model, params_tp, toks, pad, ctx=ctx, feed=fed)

    leaves = jax.tree.leaves(params_tp)
    off_mesh = [x for x in leaves if x.sharding.device_set != set(devices)]
    if off_mesh:
        raise RuntimeError(f"{len(off_mesh)} parameter leaves are not on all "
                           f"{TP_CHIPS} chips")
    sharded = sum(not x.sharding.is_fully_replicated for x in leaves)
    print(f"tensor parallel: {sharded} of {len(leaves)} parameter leaves "
          f"sharded over model={TP_CHIPS}, the rest replicated on all "
          f"{TP_CHIPS} chips")
    for step in range(TP_DECODE_STEPS + 1):
        what = "prefill" if step == 0 else f"decode step {step}"
        check_gap(f"[tp={TP_CHIPS}] {what} vs one chip",
                  logit_gap(tp[step], one[step]))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, TP_CHIPS), default=1,
                    help=f"1: serve on one chip; {TP_CHIPS}: only the "
                         f"tensor-parallel path and its one-chip comparison")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}")
    check_device(devices, args.chips)
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip()
    else:
        run_tensor_parallel()
    print(f"peak_bytes_in_use (device 0): {peak_bytes()}")
    print(f"total: {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
