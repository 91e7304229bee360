"""Readers of per-layer metrics that need no trace: on a hand-made run, and
on a served run against the program's own counters."""

import types

import jax
import numpy as np
import pytest

import tiny
from bench import run_cell
from bench.runlog import ReqLog, RunLog, Wave
from conftest import REPO
from repro.configs import get_config
from repro.core import LutLinearSpec
from repro.models.model import build_model
from repro.obs import Observer
from repro.serve.serving import Request, ServeEngine


def reader(name: str):
    return run_cell.load_module(REPO / "bench" / "metrics" / f"{name}.py", name)


def view(log: RunLog, batch: int):
    return types.SimpleNamespace(log=log, batch=batch,
                                 window_waves=log.window_waves)


def wave(t_sync, bucket, admitted):
    return Wave(t_sync - 1, t_sync, steps=3, active_slots=4,
                prefill_bucket=bucket, admitted=admitted, emitted=[])


def test_prefill_useful_share_on_a_hand_made_run():
    prompt = lambda n: np.zeros(n, np.int32)
    log = RunLog({0: ReqLog(prompt(10), 4), 1: ReqLog(prompt(16), 4),
                  2: ReqLog(prompt(20), 4), 3: ReqLog(prompt(7), 4)},
                 waves=[wave(1, 8, [3]),             # before the window
                        wave(2, 16, [0, 1]), wave(3, None, []),
                        wave(4, 32, [2])],
                 t_open=1, t_close=4)
    read = reader("prefill_useful_share").read
    assert read(view(log, 4)) == pytest.approx(
        100 * (10 + 16 + 20) / (4 * 16 + 4 * 32))
    log.t_open = 3.5
    assert read(view(log, 4)) == pytest.approx(100 * 20 / (4 * 32))
    log.t_close = 3.9
    assert read(view(log, 4)) is None


def test_prefill_useful_share_matches_the_programs_counters():
    """Read from the waves a served run records, the share equals the
    ``Observer``'s ``prompt_tokens`` over its ``prefill_positions``."""
    import dataclasses as dc

    cfg = dc.replace(get_config("stablelm-12b", smoke=True), name="readers-test",
                     **{k: v for k, v in tiny.SIZES["stablelm-12b"].items()
                        if k not in ("norm_kind", "qkv_bias")})
    model = build_model(cfg)
    tree = model.prepare(model.quantize(model.init(jax.random.PRNGKey(0)),
                                        LutLinearSpec(bw=4, ba=4, mode="dequant")))
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32),
                    max_new_tokens=m)
            for n, m in ((5, 3), (9, 6), (3, 2), (12, 4), (7, 5), (4, 3))]
    obs = Observer()
    engine = ServeEngine(model, tree, batch=2, max_seq=32, obs=obs)
    log = RunLog({i: ReqLog(r.prompt, r.max_new_tokens) for i, r in enumerate(reqs)},
                 t_open=float("-inf"), t_close=float("inf"))
    engine.on_wave = lambda rec: log.add_wave(rec, list(range(len(reqs))))
    engine.generate(reqs)
    counters = obs.metrics.snapshot()["counters"]
    assert counters["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    assert reader("prefill_useful_share").read(view(log, 2)) == pytest.approx(
        100 * counters["prompt_tokens"] / counters["prefill_positions"])
