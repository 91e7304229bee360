"""Zero-sync tracing: a lock-cheap in-process event bus with ring buffering.

The paper's evaluation is a per-stage timing story (Figs. 13/16: where do
cycles go — streaming, compose, buffer hits?), and the serving stack needs
the same visibility at runtime without perturbing the thing it observes.
The contract every recording point obeys:

**Recording happens only at existing host syncs.**  The serve hot path
already crosses device→host exactly once per admission wave
(:attr:`repro.serve.serving.ServeEngine.host_syncs`); every value a trace
event carries — wave index, step counts, admitted request ids, wall-clock
reads — is host-resident at that point.  The tracer NEVER touches a device
array, never calls ``block_until_ready``, never adds a transfer: with
tracing on, ``host_syncs``, ``admissions`` and the emitted tokens are
bit-identical to an untraced run (asserted by ``tests/test_obs.py`` and the
``slo`` section of ``BENCH_serve.json``).

**Lock-cheap ring buffer.**  Events append to a ``collections.deque`` with
a fixed ``maxlen`` — O(1), no allocation churn past capacity, and atomic
under CPython's GIL, so the hot-swap stage thread and the serving thread
share one tracer without a lock on the append path.  When the ring wraps,
the oldest events fall off and ``dropped`` counts them: a bounded-memory
trace of the recent past, the same discipline as the request log's
rotation.

Event vocabulary (``cat`` groups them for the Perfetto exporter's tracks):

* ``request`` — per-request lifecycle: ``submit`` → ``admit`` (slot, queue
  wait) → ``prefill`` (bucket) → per-wave ``decode`` spans → ``finish`` /
  ``shed`` / ``quarantine``.
* ``wave`` — per-admission-wave: the ``serve.wave`` span, its
  ``serve.prefill`` and its host sync, ``serve.fetch`` — the names the
  continuous driver's profiler spans carry (:mod:`repro.obs.scopes`), so
  this export and a device trace speak one vocabulary.
* ``ops`` — live operations: swap ``stage``/``flip``/``refuse``, supervisor
  ``restart``/``backoff``/``giveup``, ``replay``, ``ckpt_restore``, chaos
  kill points.
* ``tune`` — per-candidate measurement spans from
  :class:`repro.tune.measure.Measurer`.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Optional

from repro import timing
from repro.obs import scopes


@dataclasses.dataclass
class Event:
    """One trace event in the Chrome ``trace_event`` vocabulary subset the
    exporter understands: ``ph="X"`` complete span (``ts`` + ``dur``),
    ``ph="i"`` instant, ``ph="C"`` counter sample.  ``ts``/``dur`` are
    seconds in the :func:`repro.timing.clock` domain; ``track`` names the
    Perfetto thread the event renders on (one per slot, one per live-ops
    actor)."""

    name: str
    cat: str = "serve"
    ph: str = "i"
    ts: float = 0.0
    dur: float = 0.0
    track: str = "engine"
    args: dict = dataclasses.field(default_factory=dict)


class Tracer:
    """Ring-buffered event sink; every method is safe to call from any
    thread and never blocks on more than the GIL."""

    def __init__(self, capacity: int = 65536):
        if capacity <= 0:
            raise ValueError(f"tracer capacity must be positive, got {capacity}")
        self.capacity = capacity
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._appended = 0            # lifetime appends (dropped = appended - held)

    # --- recording --------------------------------------------------------

    def emit(self, event: Event) -> None:
        self._appended += 1
        self._events.append(event)

    def instant(self, name: str, *, cat: str = "serve", track: str = "engine",
                ts: Optional[float] = None, **args) -> None:
        self.emit(Event(name=name, cat=cat, ph="i",
                        ts=timing.clock() if ts is None else ts,
                        track=track, args=args))

    def complete(self, name: str, t0: float, t1: float, *, cat: str = "serve",
                 track: str = "engine", **args) -> None:
        """A finished span ``[t0, t1]`` — recorded after the fact, from
        host-side clock reads taken at existing sync points."""
        self.emit(Event(name=name, cat=cat, ph="X", ts=t0,
                        dur=max(0.0, t1 - t0), track=track, args=args))

    def counter(self, name: str, value, *, cat: str = "serve",
                track: str = "engine", ts: Optional[float] = None) -> None:
        self.emit(Event(name=name, cat=cat, ph="C",
                        ts=timing.clock() if ts is None else ts,
                        track=track, args={"value": value}))

    # --- reading ----------------------------------------------------------

    @property
    def dropped(self) -> int:
        """Events that fell off the ring (lifetime appends minus held)."""
        return self._appended - len(self._events)

    def __len__(self) -> int:
        return len(self._events)

    def events(self) -> list[Event]:
        """Snapshot of the ring's current contents, oldest first."""
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()
        self._appended = 0


class Observer:
    """The object a serving stack threads through itself: one
    :class:`Tracer` + one :class:`repro.obs.metrics.MetricsRegistry`, plus
    the request-lifecycle bookkeeping that turns wave timestamps into SLO
    stats (TTFT / TPOT / queue wait / goodput).

    ``ServeEngine(obs=...)`` calls the ``serve_*``/``wave`` hooks at its
    existing host syncs; :class:`repro.serve.ops.LiveServer`,
    :class:`repro.serve.ops.SwapController` and
    :class:`repro.tune.measure.Measurer` call ``ops_span``/``ops_event``/
    ``measurement``.  Every hook is pure host-side bookkeeping — see the
    module docstring's zero-sync contract.
    """

    def __init__(self, *, tracer: Optional[Tracer] = None, metrics=None,
                 capacity: int = 65536):
        from repro.obs.metrics import MetricsRegistry

        self.tracer = Tracer(capacity=capacity) if tracer is None else tracer
        self.metrics = MetricsRegistry() if metrics is None else metrics
        # request-lifecycle records: key -> dict(submit/admit/first/done
        # timestamps, tokens, slot).  Keys are (generation, request_idx) so
        # consecutive generate() calls on one engine never collide.
        self.requests: dict = {}
        self._gen = 0
        self._batch: dict = {}          # generation -> batch slots
        self._lock = threading.Lock()   # generation bump only (cold path)

    # --- request lifecycle (called by ServeEngine at host syncs) ----------

    def serve_begin(self, prompt_lens: list[int], *, decode: str,
                    batch: int) -> int:
        """A generate() call is starting: all its requests, whose prompt
        lengths are ``prompt_lens``, are submitted now.  Returns the
        generation id the engine hands back to the other hooks."""
        with self._lock:
            self._gen += 1
            gen = self._gen
        self._batch[gen] = batch
        now = timing.clock()
        n_requests = len(prompt_lens)
        for i, plen in enumerate(prompt_lens):
            self.requests[(gen, i)] = {
                "submit": now, "admit": None, "first": None, "done": None,
                "tokens": 0, "slot": None, "prompt_len": plen,
            }
        self.tracer.instant("submit", cat="request", track="engine",
                            ts=now, n_requests=n_requests, decode=decode)
        self.metrics.counter("requests_submitted").inc(n_requests)
        self.metrics.gauge("batch_slots").set(batch)
        return gen

    def wave(self, rec, *, gen: int, engine=None) -> None:
        """One admission wave's record (:class:`repro.serve.serving.
        WaveRecord`), at the wave's single host sync.  Emits the wave span,
        per-request admit/prefill/decode/finish events, and updates the
        metric registry — all from host-resident values."""
        tr = self.tracer
        m = self.metrics
        tr.complete(scopes.WAVE, rec.t_start, rec.t_sync, cat="wave",
                    track="engine", wave=rec.wave, steps=rec.steps,
                    admitted=len(rec.admitted), active=rec.active_slots,
                    queue_depth=rec.queue_depth)
        tr.complete(scopes.FETCH, rec.t_fetch, rec.t_sync, cat="wave",
                    track="engine", wave=rec.wave)
        for idx, slot in rec.admitted:
            r = self.requests.get((gen, idx))
            if r is not None:
                r["admit"] = rec.t_start
                r["slot"] = slot
                m.histogram("queue_wait_s").observe(rec.t_start - r["submit"])
            tr.instant(f"admit r{idx}", cat="request", track=f"slot {slot}",
                       ts=rec.t_start, request=idx, slot=slot,
                       bucket=rec.prefill_bucket)
        if rec.admitted and rec.prefill_bucket is not None:
            m.histogram("prefill_bucket").observe(rec.prefill_bucket)
            tr.complete(scopes.PREFILL, rec.t_start, rec.t_decode, cat="wave",
                        track="engine", wave=rec.wave, bucket=rec.prefill_bucket,
                        admitted=len(rec.admitted))
        done = 0
        for idx, slot, toks in rec.emitted:
            r = self.requests.get((gen, idx))
            tr.complete(f"decode r{idx}", rec.t_decode, rec.t_sync,
                        cat="request", track=f"slot {slot}", request=idx,
                        wave=rec.wave, tokens=len(toks))
            if r is None:
                continue
            if toks and r["first"] is None:
                r["first"] = rec.t_sync
                m.histogram("ttft_s").observe(rec.t_sync - r["submit"])
            r["tokens"] += len(toks)
            if idx in rec.finished:
                r["done"] = rec.t_sync
                done += 1
                tr.instant(f"finish r{idx}", cat="request",
                           track=f"slot {slot}", ts=rec.t_sync, request=idx,
                           tokens=r["tokens"])
                # One complete span per request lifecycle (submit -> done):
                # the span an operator hunts for first in the Perfetto UI.
                tr.complete(f"r{idx} lifecycle", r["submit"], rec.t_sync,
                            cat="request", track=f"slot {slot}", request=idx,
                            tokens=r["tokens"], slot=slot)
                if r["first"] is not None and r["tokens"] > 1:
                    m.histogram("tpot_s").observe(
                        (r["done"] - r["first"]) / (r["tokens"] - 1))
        m.counter("waves").inc()
        m.counter("tokens_emitted").inc(
            sum(len(t) for _i, _s, t in rec.emitted))
        m.counter("admissions").inc(len(rec.admitted))
        if rec.admitted and rec.prefill_bucket is not None:
            # Every admission prefills all the batch's rows to the bucket.
            m.counter("prefill_positions").inc(
                self._batch.get(gen, 0) * rec.prefill_bucket)
            m.counter("prompt_tokens").inc(sum(
                self.requests.get((gen, idx), {}).get("prompt_len", 0)
                for idx, _slot in rec.admitted))
        m.counter("requests_finished").inc(done)
        m.histogram("wave_steps").observe(rec.steps)
        m.histogram("host_sync_s").observe(rec.t_sync - rec.t_fetch)
        m.gauge("slot_occupancy").set(rec.active_slots)
        m.gauge("queue_depth").set(rec.queue_depth)
        if engine is not None:
            m.gauge("host_syncs").set(engine.host_syncs)
            m.gauge("swaps").set(engine.swaps)
        tr.counter("slot_occupancy", rec.active_slots, cat="wave",
                   ts=rec.t_sync)
        tr.counter("queue_depth", rec.queue_depth, cat="wave", ts=rec.t_sync)

    def serve_end(self, gen: int, *, engine=None) -> None:
        self.tracer.instant("serve done", cat="request", track="engine",
                            gen=gen)
        if engine is not None:
            self.scrape(engine)

    # --- live-ops / tune events -------------------------------------------

    def ops_event(self, name: str, *, actor: str = "ops",
                  ts: Optional[float] = None, **args) -> None:
        """An instantaneous live-ops event (swap refuse, restart, chaos kill
        point, quarantine, shed, giveup)."""
        self.tracer.instant(name, cat="ops", track=actor, ts=ts, **args)
        self.metrics.counter(f"ops_{name.split()[0]}").inc()

    def ops_span(self, name: str, t0: float, t1: float, *,
                 actor: str = "ops", **args) -> None:
        """A finished live-ops span (swap stage, flip wait, replay,
        checkpoint restore, supervisor backoff)."""
        self.tracer.complete(name, t0, t1, cat="ops", track=actor, **args)
        self.metrics.histogram(f"ops_{name.split()[0]}_s").observe(t1 - t0)

    def measurement(self, key: tuple, us: float, *, cached: bool) -> None:
        """One autotuner candidate measurement (``repro.tune.measure``)."""
        self.metrics.counter(
            "tune_measure_hits" if cached else "tune_measure_misses").inc()
        if not cached:
            now = timing.clock()
            f, k, n, bw, ba, p, mode = key[:7]
            self.tracer.complete(
                f"measure {mode} p={p} [{f}x{k}]", now - us * 1e-6, now,
                cat="tune", track="tune.measure", n=n, bw=bw, ba=ba, us=us)

    # --- engine gauges ----------------------------------------------------

    def scrape(self, engine) -> dict:
        """Scrape engine-level gauges from existing structures — slot count,
        sync/swap counters, the active :class:`repro.tune.ModelPlan`'s
        per-layer mode/p mix — into the registry (and return them).  Pure
        host-side reads."""
        from repro.obs.metrics import scrape_engine

        return scrape_engine(engine, metrics=self.metrics)

    # --- SLO derivation ---------------------------------------------------

    def request_records(self) -> list[dict]:
        """Per-request lifecycle timestamps, submission order."""
        return [dict(r, key=list(k)) for k, r in sorted(self.requests.items())]

    def slo(self) -> dict:
        """Derived SLO stats over every request observed so far — TTFT,
        TPOT, queue wait percentiles and goodput.  See
        :func:`repro.obs.metrics.slo_stats`."""
        from repro.obs.metrics import slo_stats

        return slo_stats(self.request_records())
