"""Serving: prefill+decode equals full forward; continuous batching;
pad-masked bucketing invariance; scheduler contract (admission order, slot
reuse, O(1) host syncs per admission wave)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.config import ModelConfig, MoEConfig
from repro.models.model import build_model
from repro.serve.serving import Request, ServeEngine

DECODE_ARCHS = [
    "gemma2-2b", "command-r-plus-104b", "stablelm-12b", "chatglm3-6b",
    "zamba2-7b", "deepseek-v2-lite-16b", "llama4-maverick-400b-a17b",
    "rwkv6-3b", "whisper-large-v3",
]


def _dropless(cfg: ModelConfig) -> ModelConfig:
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0)
    )


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = _dropless(get_config(arch, smoke=True))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, PRE = 2, 10, 5
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32))
    pe = None
    if cfg.frontend is not None:
        pe = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_seq, cfg.frontend_dim)).astype(np.float32)
        )
    full_logits, _, _ = model.forward(params, toks, prefix_embeds=pe)
    caches = model.init_cache(B, 16, dtype=jnp.float32)
    pf, caches = model.prefill(params, toks[:, :PRE], caches, prefix_embeds=pe)
    assert pf.shape == (B, 1, cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(pf[:, 0]), np.asarray(full_logits[:, PRE - 1]), rtol=3e-2, atol=3e-2
    )
    outs = []
    for t in range(PRE, S):
        lg, caches = model.decode_step(params, toks[:, t : t + 1], caches, jnp.int32(t))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec[:, :-1]), np.asarray(full_logits[:, PRE : S - 1]),
        rtol=3e-2, atol=3e-2,
    )


@pytest.mark.parametrize("arch,profile", [
    ("stablelm-12b", None),              # GQA
    ("deepseek-v2-lite-16b", None),      # MLA
    ("gemma2-2b", "serve"),              # int8 KV on global layers, ring on local
])
def test_decode_wave_matches_step_loop_bit_for_bit(arch, profile):
    """The continuous driver's ``while_loop`` program writes each step's rows
    where the per-step program does: with slots at different depths and one
    slot inactive, the tokens it emits and the caches it leaves are those of
    ``make_serve_step`` run step by step, leaf by leaf, bit for bit."""
    from repro.models.profiles import apply_perf_profile
    from repro.serve.serving import make_decode_wave, make_serve_step

    cfg = _dropless(get_config(arch, smoke=True))
    if profile:
        cfg = apply_perf_profile(cfg, profile, tp=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, PROMPT, MAX_SEQ, STEPS = 3, 8, 32, 5
    rng = np.random.default_rng(10)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, PROMPT), dtype=np.int32))
    pad = jnp.asarray([0, 2, 1], jnp.int32)
    logits, caches = model.prefill(
        params, toks, model.init_cache(B, MAX_SEQ, dtype=jnp.float32), pad_len=pad
    )
    token = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
    pos = jnp.asarray([PROMPT, PROMPT - 3, PROMPT - 1], jnp.int32)   # three depths
    active = jnp.asarray([True, False, True])

    wave = make_decode_wave(model, out_cap=STEPS + 1)
    w_token, w_caches, w_pos, w_out = wave(
        params, token, jax.tree.map(jnp.copy, caches), pos, pad, active,
        jnp.int32(STEPS),
    )

    step = jax.jit(make_serve_step(model))
    out = [jnp.where(active, token[:, 0], -1)]
    for _ in range(STEPS):
        token, caches = step(params, token, caches, pos, pad)
        out.append(jnp.where(active, token[:, 0], -1))
        pos = pos + active.astype(jnp.int32)

    np.testing.assert_array_equal(np.asarray(w_out), np.stack(out, axis=1))
    np.testing.assert_array_equal(np.asarray(w_token), np.asarray(token))
    np.testing.assert_array_equal(np.asarray(w_pos), np.asarray(pos))
    flat, tree = jax.tree.flatten(caches)
    w_flat, w_tree = jax.tree.flatten(w_caches)
    assert w_tree == tree
    for got, want in zip(w_flat, flat):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_serve_engine_batched_greedy():
    cfg = get_config("chatglm3-6b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch=2, max_seq=32)
    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                max_new_tokens=4)
        for _ in range(3)
    ]
    outs = eng.generate(reqs)
    assert len(outs) == 3
    assert all(len(o) == 4 for o in outs)
    assert all(0 <= t < cfg.vocab_size for o in outs for t in o)
    # greedy decoding is deterministic
    outs2 = eng.generate(reqs)
    assert outs == outs2


def _engines(decodes=("scan", "loop"), arch="chatglm3-6b", **kw):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, [
        ServeEngine(model, params, batch=2, max_seq=32, decode=d, **kw)
        for d in decodes
    ]


def test_scan_decode_matches_seed_loop_token_for_token():
    """The fused lax.scan decode == the seed per-token Python loop, including
    ragged per-request max_new_tokens (masked slots) and batch padding."""
    cfg, (scan, loop) = _engines()
    rng = np.random.default_rng(0)
    # prompts at the bucket boundary -> identical left-padding in both drivers
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=m)
        for m in (4, 6, 3)
    ]
    o_scan = scan.generate(reqs)
    o_loop = loop.generate(reqs)
    assert o_scan == o_loop
    assert [len(o) for o in o_scan] == [4, 6, 3]    # per-slot budgets honored


def test_scan_decode_syncs_once_per_batch():
    """O(1) host syncs per batch: the scan driver transfers the whole token
    matrix once, independent of max_new; the seed loop syncs every token."""
    cfg, (scan, loop) = _engines()
    rng = np.random.default_rng(1)

    def reqs(max_new, n=3):
        return [
            Request(prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=max_new)
            for _ in range(n)
        ]

    scan.generate(reqs(4))          # 2 batches
    assert scan.host_syncs == 2
    scan.host_syncs = 0
    scan.generate(reqs(12))         # 3x the tokens, same sync count
    assert scan.host_syncs == 2
    loop.host_syncs = 0
    loop.generate(reqs(4))
    assert loop.host_syncs == 2 * 4             # one per decoded step
    loop.host_syncs = 0
    loop.generate(reqs(12))
    assert loop.host_syncs == 2 * 12


def test_scan_decode_with_prepared_params_matches_quantized():
    """Weight-stationary end to end: prepared params + scan decode produce
    the same tokens as raw quantized params + seed loop."""
    from repro.core import LutLinearSpec

    cfg = get_config("stablelm-12b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = model.quantize(params, LutLinearSpec(bw=4, ba=4, mode="dequant"))
    pparams = model.prepare(qparams)
    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=5)
        for _ in range(2)
    ]
    loop = ServeEngine(model, qparams, batch=2, max_seq=32, decode="loop")
    scan = ServeEngine(model, pparams, batch=2, max_seq=32, decode="scan")
    assert scan.generate(reqs) == loop.generate(reqs)
    assert scan.host_syncs == 1


def test_prompt_bucketing_and_limits():
    """Ragged prompt lengths share one bucket trace; oversized requests
    raise (in BOTH drivers) instead of silently overflowing the KV cache."""
    cfg, (scan, loop) = _engines()
    rng = np.random.default_rng(2)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=3)
        for n in (3, 5, 7, 8)
    ]
    outs = scan.generate(reqs)
    assert all(len(o) == 3 for o in outs)
    oversized = [Request(prompt=np.zeros(30, np.int32), max_new_tokens=8)]
    with pytest.raises(ValueError):
        scan.generate(oversized)
    with pytest.raises(ValueError):
        loop.generate(oversized)


def test_unbucketed_scan_matches_loop_at_every_length():
    """prompt_bucket=1 disables bucketing: the scan driver is token-for-token
    identical to the seed loop for prompt lengths OFF any bucket boundary."""
    cfg, (scan, loop) = _engines(prompt_bucket=1)
    rng = np.random.default_rng(3)
    for n in (2, 5, 9):
        reqs = [
            Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=5)
            for _ in range(2)
        ]
        assert scan.generate(reqs) == loop.generate(reqs), n


def test_request_has_no_dead_generated_field():
    import dataclasses as dc

    # prompt + budget, plus the two LiveServer fault-domain knobs (deadline
    # shedding, per-request crash budget) — and in particular no resurrected
    # `generated` accumulator (tokens live in the engine, not the request).
    assert [f.name for f in dc.fields(Request)] == [
        "prompt", "max_new_tokens", "deadline_s", "max_retries",
    ]


# --- pad-masked prefill: bucketing invariance ---------------------------


def test_bucketed_scan_matches_unbucketed_loop_at_every_length():
    """THE pad-mask property (ISSUE 4 acceptance): with default power-of-two
    bucketing, the continuous scan driver is token-for-token identical to
    the ``prompt_bucket=1`` loop oracle at EVERY prompt length in a ragged
    batch — lengths off the bucket boundary included.

    (The loop oracle pads to the exact chunk max by construction, i.e. it
    IS the ``prompt_bucket=1`` reference — the knob only shapes the
    scan/chunked prefill traces.)"""
    cfg, (scan, loop) = _engines()            # scan: default prompt_bucket=8
    rng = np.random.default_rng(4)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=m)
        for n, m in [(2, 4), (3, 6), (5, 3), (7, 5), (9, 4), (11, 6), (13, 2)]
    ]
    assert scan.generate(reqs) == loop.generate(reqs)


def test_padding_is_output_invariant_against_solo_requests():
    """Stronger than scan==loop: every request served in a ragged batch (any
    scheduler) produces the tokens it would produce served ALONE, unpadded —
    left-padding is fully don't-care, as is batch composition."""
    cfg, (scan, chunked) = _engines(decodes=("scan", "chunked"))
    solo = ServeEngine(scan.model, scan.params, batch=1, max_seq=32,
                       decode="loop", prompt_bucket=1)
    rng = np.random.default_rng(5)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=m)
        for n, m in [(3, 5), (6, 2), (10, 6), (5, 4), (2, 3)]
    ]
    want = [solo.generate([r])[0] for r in reqs]
    assert scan.generate(reqs) == want
    assert chunked.generate(reqs) == want


def test_pad_mask_invariance_on_mla_arch():
    """The pad mask also flows through the MLA (latent attention) path.

    deepseek is MoE: capacity-factor routing lets pad tokens compete for
    expert capacity (like recurrent state, a non-attention leak), so the
    invariance claim needs the dropless config — attention itself is exact.
    """
    cfg = _dropless(get_config("deepseek-v2-lite-16b", smoke=True))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    scan, loop = (
        ServeEngine(model, params, batch=2, max_seq=32, decode=d)
        for d in ("scan", "loop")
    )
    rng = np.random.default_rng(6)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=3)
        for n in (3, 5, 7)
    ]
    assert scan.generate(reqs) == loop.generate(reqs)


# --- continuous in-flight batching: scheduler contract -------------------


def test_continuous_admission_reuses_freed_slot_in_order():
    """Requests are admitted FIFO into the slot that freed — mid-decode, not
    at chunk boundaries; ``admissions`` logs (request_idx, slot)."""
    cfg, (scan,) = _engines(decodes=("scan",))
    rng = np.random.default_rng(7)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                max_new_tokens=m)
        for m in (6, 2, 4, 2)
    ]
    outs = scan.generate(reqs)
    assert [len(o) for o in outs] == [6, 2, 4, 2]
    # r0 holds slot 0 throughout; r1 finishes first, so r2 and then r3 both
    # reuse slot 1 while r0 is still mid-decode.
    assert scan.admissions == [(0, 0), (1, 1), (2, 1), (3, 1)]
    # 3 admission waves (r0+r1 | r2 | r3), one sync each
    assert scan.host_syncs == 3


def test_continuous_host_syncs_O1_per_admission_wave():
    """Sync count depends on the admission-wave structure only, not on the
    number of decode steps: scaling every budget 3x leaves it unchanged."""
    cfg, (a, b) = _engines(decodes=("scan", "scan"))
    rng = np.random.default_rng(8)

    def reqs(scale):
        return [
            Request(prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                    max_new_tokens=m * scale)
            for m in (2, 1, 3, 1)
        ]

    a.generate(reqs(1))
    b.generate(reqs(3))
    assert a.host_syncs == b.host_syncs > 0
    assert a.admissions == b.admissions


def test_continuous_mixed_zero_budget_and_singletons():
    """max_new=0 requests are never admitted (empty output), and a batch
    with more requests than slots drains the queue."""
    cfg, (scan, loop) = _engines()
    rng = np.random.default_rng(9)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                max_new_tokens=m)
        for m in (3, 0, 1, 5, 0)
    ]
    outs = scan.generate(reqs)
    assert [len(o) for o in outs] == [3, 0, 1, 5, 0]
    assert loop.generate(reqs) == outs
    assert all(i != 1 and i != 4 for i, _ in scan.admissions)


# --- edge cases: bucket_to / _check_fits / empty prompts ----------------


def test_bucket_to_edge_cases():
    from repro.serve.serving import bucket_to

    # power-of-two ladder from the floor
    assert [bucket_to(n, 8) for n in (1, 8, 9, 16, 17)] == [8, 8, 16, 16, 32]
    # non-power-of-two floors walk floor * 2^i
    assert [bucket_to(n, 3) for n in (1, 3, 4, 6, 7, 13)] == [3, 3, 6, 6, 12, 24]
    # floor <= 1 disables bucketing entirely
    assert [bucket_to(n, 1) for n in (0, 1, 5)] == [0, 1, 5]
    assert bucket_to(7, 0) == 7
    # n=0 still returns the floor (a zero-wide prefill never traces)
    assert bucket_to(0, 8) == 8


def test_check_fits_and_empty_prompt_raise_in_every_driver():
    for decode in ("scan", "chunked", "loop"):
        cfg, (eng,) = _engines(decodes=(decode,))
        oversized = [Request(prompt=np.zeros(30, np.int32), max_new_tokens=8)]
        with pytest.raises(ValueError, match="exceeds max_seq"):
            eng.generate(oversized)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.generate([Request(prompt=np.zeros(0, np.int32),
                                  max_new_tokens=2)])
        assert eng.generate(
            [Request(prompt=np.zeros(4, np.int32), max_new_tokens=0)]
        ) == [[]]


def test_chunked_rejects_infeasible_chunk_pair_continuous_serves_it():
    """A long-prompt + long-budget pair that cannot share one chunk: the
    chunked driver raises; the continuous scheduler admits them into
    separate waves and serves both."""
    cfg, (scan, chunked) = _engines(decodes=("scan", "chunked"))
    reqs = [
        Request(prompt=np.ones(24, np.int32), max_new_tokens=2),
        Request(prompt=np.ones(2, np.int32), max_new_tokens=24),
    ]
    with pytest.raises(ValueError):
        chunked.generate(reqs)
    outs = scan.generate(reqs)
    assert [len(o) for o in outs] == [2, 24]
