"""repro.obs: the zero-sync tracing contract, ring buffering, exporters,
SLO derivation, the injectable clock, and the WaveRecord callback shim.

The load-bearing suite is the identity block: with an Observer attached,
every decode driver must emit bit-identical tokens with an identical host
sync count and admission order — tracing records only at existing syncs.
"""

import dataclasses as dc
import json
import math
import os
import pathlib

import jax
import numpy as np
import pytest

from repro import timing
from repro.configs import get_config
from repro.core import LutLinearSpec
from repro.ft import supervisor as sup
from repro.models.model import build_model
from repro.obs import (
    Observer,
    Tracer,
    metrics_records,
    percentile,
    perfetto_trace,
    scrape_engine,
    slo_stats,
    snapshot_text,
    write_metrics_jsonl,
    write_perfetto,
)
from repro.obs import scopes
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import Event
from repro.serve.ops import LiveServer
from repro.serve.serving import Request, ServeEngine, WaveRecord


def _tiny_cfg():
    return dc.replace(
        get_config("stablelm-12b", smoke=True), name="obs-test",
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
        vocab_size=64,
    )


def _tiny_model():
    """Tiny decoder quantized at the fig13 default serve config (W1A3, p=4,
    dequant numerics — batch-composition invariant, replay-exact)."""
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qparams = model.quantize(params, LutLinearSpec(bw=1, ba=3, p=4,
                                                   mode="dequant"))
    return cfg, model, model.prepare(qparams)


def _reqs(cfg, budgets=(6, 2, 4, 2), seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(0, cfg.vocab_size, 3 + i).astype(np.int32),
                max_new_tokens=m)
        for i, m in enumerate(budgets)
    ]


# --- the zero-sync contract ------------------------------------------------


def _host_spans(trace_dir) -> list:
    """``(start, end, name, step_num)`` of the ``serve.*`` spans on the
    host's Python thread of the one profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in scopes.SPANS:
                    step = dict(ev.stats).get("step_num")
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, step))
    return sorted(out)


def _phases_by_wave(spans) -> list:
    """The phase names inside each ``serve.wave`` span, in time order."""
    return [[n for s, e, n, _ in spans
             if n != scopes.WAVE and ws <= s and e <= we]
            for ws, we, wn, _ in spans if wn == scopes.WAVE]


@pytest.mark.parametrize("decode,profiled", [
    pytest.param("scan", False, id="scan"),
    pytest.param("chunked", False, id="chunked"),
    pytest.param("loop", False, id="loop"),
    pytest.param("scan", True, id="scan-profiled"),
])
def test_tracing_is_invisible_to_tokens_syncs_and_admissions(
        decode, profiled, tmp_path):
    """THE obs gate: tokens, host_syncs and admission order bit-identical
    with tracing on vs off, on every decode driver — and with the profiler
    recording the continuous driver's host spans, each wave's phases in
    order inside its ``serve.wave`` span."""
    cfg, model, tree = _tiny_model()
    reqs = _reqs(cfg)
    plain = ServeEngine(model, tree, batch=2, max_seq=32, decode=decode)
    want = plain.generate(reqs)

    obs = Observer()
    traced = ServeEngine(model, tree, batch=2, max_seq=32, decode=decode,
                         obs=obs)
    if profiled:
        jax.profiler.start_trace(str(tmp_path))
        try:
            got = traced.generate(reqs)
        finally:
            jax.profiler.stop_trace()
        spans = _host_spans(tmp_path)
        waves = [s for s in spans if s[2] == scopes.WAVE]
        assert [w[3] for w in waves] == list(range(traced.host_syncs))
        # The Observer's after-the-fact serve.prefill events name the waves
        # that admitted: exactly those show a serve.prefill span.
        admitting = {e.args["wave"] for e in obs.tracer.events()
                     if e.name == scopes.PREFILL}
        assert 0 in admitting
        assert _phases_by_wave(spans) == [
            [scopes.ADMIT] + [scopes.PREFILL] * (w in admitting)
            + [scopes.DECODE, scopes.FETCH, scopes.EMIT]
            for w in range(len(waves))]
    else:
        got = traced.generate(reqs)
    assert got == want
    assert traced.host_syncs == plain.host_syncs
    assert traced.admissions == plain.admissions
    assert len(obs.tracer) > 0           # ...and it actually traced
    # every request was observed through its full lifecycle
    recs = obs.request_records()
    assert len(recs) == len(reqs)
    for r in recs:
        assert r["done"] is not None and r["first"] is not None
        assert r["tokens"] == reqs[r["key"][1]].max_new_tokens


def test_wave_spans_record_existing_sync_timestamps():
    """Continuous-driver wave spans: one ``serve.wave`` span + one
    ``serve.fetch`` (host sync) span per admission wave, under the names of
    the profiler's host spans, each carrying its wave index."""
    cfg, model, tree = _tiny_model()
    obs = Observer()
    eng = ServeEngine(model, tree, batch=2, max_seq=32, obs=obs)
    eng.generate(_reqs(cfg))
    waves = [e for e in obs.tracer.events()
             if e.cat == "wave" and e.name == scopes.WAVE]
    syncs = [e for e in obs.tracer.events() if e.name == scopes.FETCH]
    assert len(waves) == eng.host_syncs == len(syncs)
    assert [e.args["wave"] for e in waves] == list(range(eng.host_syncs))
    for e in waves:
        assert e.ph == "X" and e.dur >= 0


# --- WaveRecord + legacy shim ---------------------------------------------


def test_on_wave_delivers_structured_record():
    cfg, model, tree = _tiny_model()
    eng = ServeEngine(model, tree, batch=2, max_seq=32)
    seen = []
    eng.on_wave = seen.append
    want = eng.generate(_reqs(cfg))
    assert seen and all(isinstance(r, WaveRecord) for r in seen)
    assert [r.wave for r in seen] == list(range(len(seen)))
    emitted = sum(len(t) for r in seen for _i, _s, t in r.emitted)
    assert emitted == sum(len(o) for o in want)
    fin = sorted(i for r in seen for i in r.finished)
    assert fin == list(range(len(want)))
    for r in seen:
        assert r.t_start <= r.t_decode <= r.t_fetch <= r.t_sync
        assert r.sync_s == r.t_sync - r.t_fetch


def test_legacy_positional_on_wave_still_works_with_deprecation():
    cfg, model, tree = _tiny_model()
    eng = ServeEngine(model, tree, batch=2, max_seq=32)
    calls = []

    def legacy(wave, admitted, emitted):
        calls.append((wave, admitted, emitted))

    eng.on_wave = legacy
    with pytest.warns(DeprecationWarning, match="WaveRecord"):
        eng.generate(_reqs(cfg))
    assert calls
    wave0, admitted0, emitted0 = calls[0]
    assert wave0 == 0 and isinstance(admitted0, list)
    assert all(isinstance(t, list) for _i, _s, t in emitted0)


def test_star_args_on_wave_treated_as_legacy():
    cfg, model, tree = _tiny_model()
    eng = ServeEngine(model, tree, batch=2, max_seq=32)
    shapes = []
    eng.on_wave = lambda *a: shapes.append(len(a))
    with pytest.warns(DeprecationWarning):
        eng.generate(_reqs(cfg))
    assert shapes and all(n == 3 for n in shapes)


# --- tracer ring -----------------------------------------------------------


def test_ring_buffer_caps_memory_and_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}", ts=float(i))
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e.name for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0
    with pytest.raises(ValueError):
        Tracer(capacity=0)


# --- exporters -------------------------------------------------------------


def test_perfetto_export_loads_and_has_request_lifecycle_spans(tmp_path):
    cfg, model, tree = _tiny_model()
    obs = Observer()
    eng = ServeEngine(model, tree, batch=2, max_seq=32, obs=obs)
    eng.generate(_reqs(cfg))
    path = tmp_path / "trace.json"
    write_perfetto(obs, str(path))
    d = json.loads(path.read_text())
    evs = d["traceEvents"]
    # chrome://tracing essentials: process_name + per-track thread_name
    # metadata, and exactly one complete lifecycle span per request.
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    tracks = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "engine" in tracks and "slot 0" in tracks
    life = [e for e in evs if e["ph"] == "X" and "lifecycle" in e["name"]]
    assert len(life) == 4
    for e in life:
        assert e["dur"] >= 0 and "ts" in e
    # no tmp residue from the atomic write
    assert list(tmp_path.iterdir()) == [path]


def test_jsonl_and_metrics_exports(tmp_path):
    cfg, model, tree = _tiny_model()
    obs = Observer()
    eng = ServeEngine(model, tree, batch=2, max_seq=32, obs=obs)
    seen = []
    eng.on_wave = seen.append
    eng.generate(_reqs(cfg))
    m_path = write_metrics_jsonl(obs, str(tmp_path / "metrics.jsonl"),
                                 extra={"run": 1})
    recs = [json.loads(ln) for ln in open(m_path)]
    kinds = [r["t"] for r in recs]
    assert kinds[0] == "snapshot" and kinds[1] == "slo"
    assert kinds.count("request") == 4 and kinds[-1] == "extra"
    snap = recs[0]
    assert snap["counters"]["tokens_emitted"] == 14
    assert snap["counters"]["requests_finished"] == 4
    # Every admitting wave prefills all 2 rows to its bucket.
    assert snap["counters"]["prompt_tokens"] == 3 + 4 + 5 + 6
    assert snap["counters"]["prefill_positions"] == sum(
        2 * r.prefill_bucket for r in seen if r.admitted)
    text = snapshot_text(obs)
    assert "goodput" in text and "ttft" in text


def test_atomic_export_preserves_previous_file_on_failure(tmp_path):
    path = tmp_path / "trace.json"
    good = Tracer()
    good.instant("ok", ts=0.0)
    write_perfetto(good, str(path))
    before = path.read_text()
    bad = Tracer()
    bad.emit(Event(name="bad", ts=0.0, args={"x": {1, 2}}))  # sets aren't JSON
    with pytest.raises(TypeError):
        write_perfetto(bad, str(path))
    assert path.read_text() == before            # old file intact, not torn
    assert list(tmp_path.iterdir()) == [path]    # and no tmp residue


# --- chaos point: trace survives a kill ------------------------------------


def test_trace_survives_mid_serve_kill_with_no_torn_file(tmp_path):
    """A kill mid-serve must leave a complete, loadable Perfetto file (the
    attempt-boundary atomic re-export), and the replayed serve is still
    token-identical with live-ops events on the supervisor track."""
    cfg, model, tree = _tiny_model()
    reqs = _reqs(cfg)
    want = ServeEngine(model, tree, batch=2, max_seq=32).generate(reqs)

    obs = Observer()
    trace_path = tmp_path / "live_trace.json"
    server = LiveServer(
        lambda: ServeEngine(model, tree, batch=2, max_seq=32),
        log_path=str(tmp_path / "serve.jsonl"),
        injector=sup.FailureInjector(fail_at_waves=(1,)),
        obs=obs, trace_path=str(trace_path),
    )
    got = server.serve(reqs)
    assert got == want and server.restarts == 1
    d = json.loads(trace_path.read_text())       # complete file, parses
    names = [e["name"] for e in d["traceEvents"]]
    assert "restart" in names and "replay" in names
    sup_events = [e for e in obs.tracer.events() if e.track == "supervisor"]
    assert {"replay", "restart"} <= {e.name for e in sup_events}
    assert not [p for p in tmp_path.iterdir() if ".tmp." in p.name]


# --- metrics + SLO math ----------------------------------------------------


def test_percentile_nearest_rank():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 99) == 5.0
    assert percentile(xs, 0) == 1.0
    assert math.isnan(percentile([], 50))


def test_histogram_buckets_and_stats():
    h = Histogram(buckets=[0.1, 1.0])
    for v in (0.05, 0.5, 2.0, 3.0):
        h.observe(v)
    assert h.count == 4 and h.min == 0.05 and h.max == 3.0
    d = h.to_dict()
    assert d["buckets"] == [[0.1, 1], [1.0, 1], ["+inf", 2]]
    r = MetricsRegistry()
    assert r.counter("c") is r.counter("c")
    r.counter("c").inc(2)
    r.gauge("g").set(7)
    snap = r.snapshot()
    assert snap["counters"]["c"] == 2 and snap["gauges"]["g"] == 7


def test_slo_stats_from_lifecycle_records():
    recs = [
        # submitted at 0, admitted at 1, first token at 2, done at 6,
        # 5 tokens -> ttft 2, queue wait 1, tpot (6-2)/4 = 1
        dict(submit=0.0, admit=1.0, first=2.0, done=6.0, tokens=5),
        # unfinished request: contributes to ttft/queue but not goodput
        dict(submit=0.0, admit=3.0, first=4.0, done=None, tokens=2),
    ]
    s = slo_stats(recs)
    assert s["requests"] == 2 and s["completed"] == 1
    assert s["ttft"]["p50_s"] == 2.0 and s["ttft"]["max_s"] == 4.0
    assert s["queue_wait"]["p99_s"] == 3.0
    assert s["tpot"]["p50_s"] == 1.0
    assert s["goodput"]["completed_tokens"] == 5
    assert s["goodput"]["wall_s"] == 6.0
    assert s["goodput"]["tokens_per_s"] == pytest.approx(5 / 6.0)
    none_done = slo_stats([dict(submit=0.0, admit=None, first=None,
                                done=None, tokens=0)])
    assert none_done["goodput"]["tokens_per_s"] == 0.0


def test_scrape_engine_gauges_from_existing_structures():
    cfg, model, tree = _tiny_model()
    eng = ServeEngine(model, tree, batch=2, max_seq=32)
    eng.generate(_reqs(cfg))
    m = MetricsRegistry()
    out = scrape_engine(eng, metrics=m)
    assert out["batch_slots"] == 2 and out["decode"] == "scan"
    assert out["host_syncs"] == eng.host_syncs > 0
    assert out["prefill_buckets"]                 # buckets were counted
    assert sum(out["prefill_buckets"].values()) >= 1
    assert m.snapshot()["gauges"]["host_syncs"] == eng.host_syncs


# --- injectable clock ------------------------------------------------------


def test_fake_clock_and_override_steer_trace_timestamps():
    fc = timing.FakeClock(start=100.0, tick=1.0)
    assert fc() == 100.0 and fc() == 101.0
    fc.advance(10.0)
    assert fc() == 112.0

    with timing.override_clock(timing.FakeClock(start=5.0, tick=0.5)):
        tr = Tracer()
        tr.instant("a")
        tr.instant("b")
        a, b = tr.events()
        assert (a.ts, b.ts) == (5.0, 5.5)
    # restored: the default perf_counter domain moves forward on its own
    t0 = timing.clock()
    assert timing.clock() >= t0 >= 1e-9


def test_override_clock_restores_on_exception():
    with pytest.raises(RuntimeError):
        with timing.override_clock(lambda: 0.0):
            assert timing.clock() == 0.0
            raise RuntimeError("boom")
    assert timing.clock() != 0.0


# --- tune.measure observability -------------------------------------------


def test_measurer_emits_measurement_spans_and_hit_counters():
    import jax.numpy as jnp

    from repro.core import api
    from repro.tune import measure as measure_mod
    from repro.tune import space

    rng = np.random.default_rng(0)
    spec = api.LutLinearSpec(bw=1, ba=3, p=2, mode="lut")
    q = api.quantize_linear(
        jnp.asarray(rng.normal(size=(12, 8)).astype(np.float32)), spec
    )
    x = measure_mod.sample_activations(12, 4)
    obs = Observer()
    meas = measure_mod.Measurer(iters=1, warmup=1, cache={}, obs=obs)
    c = space.Candidate(mode="lut", p=2)
    meas.measure(q, x, c)
    meas.measure(q, x, c)                         # cache hit
    snap = obs.metrics.snapshot()["counters"]
    assert snap["tune_measure_misses"] == 1
    assert snap["tune_measure_hits"] == 1
    spans = [e for e in obs.tracer.events() if e.cat == "tune"]
    assert len(spans) == 1 and spans[0].ph == "X"
    assert spans[0].track == "tune.measure"
