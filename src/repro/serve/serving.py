"""Serving: pad-masked prefill + continuous in-flight batching driver.

The paper's deployment regime (§V-B, §VI-J): LoCaLUT-quantized projections do
the GEMMs; prefill processes the prompt, decode emits one token per step
against the KV cache.  ``ServeEngine`` is the continuous-batching driver used
by the examples and benchmarks; the jitted step functions are the objects the
multi-pod dry-run lowers at scale.

Serving is **weight-stationary** end to end: prepare the params once
(``Model.prepare``), then decode runs entirely on device.  Three schedulers
share the jitted prefill/decode programs:

* ``decode="scan"`` (default) — **continuous in-flight batching**: a
  slot-based scheduler admits queued requests into KV-cache slots the moment
  earlier requests finish (mid-decode, not per-chunk).  Each slot carries its
  own write position, pad length and token budget inside one jitted
  ``lax.while_loop`` decode program, so a wave of any step count runs from a
  single trace; freed slots are re-prefilled and merged back with a masked
  ``jnp.where`` (slot-level cache reset, no retrace).  One device→host sync
  per admission wave.
* ``decode="chunked"`` — the previous fixed-chunk driver: requests are cut
  into ``batch``-sized chunks, each chunk prefills together and decodes to
  the chunk's worst-case budget as one fused ``lax.scan`` (the continuous
  scheduler's throughput baseline in ``benchmarks/run.py serve``).
* ``decode="loop"`` — the seed per-token Python loop (one sync per decoded
  token): the equivalence oracle.

**Prefill pad mask.**  Prompt lengths are bucketed to powers of two (one
prefill trace per bucket, not per ragged length) and left-padded into the
bucket.  Every driver threads the per-row pad length through
``Model.prefill``/``decode_step`` into the attention mask: padded positions
become don't-care keys (never attended — ReducedLUT's don't-care exploitation
applied to the sequence dim) and logical positions shift by the pad, so
left-padding — the bucket's or the ragged chunk's — is **output-invariant**
for attention archs.  ``decode="scan"`` with default bucketing is therefore
token-for-token identical to the unbucketed loop oracle at *every* prompt
length, not just bucket boundaries.  (Recurrent M/R/S units still consume
pads through their state; only attention archs get exact invariance.)

**Scheduler contract** (asserted by ``tests/test_serving.py``):

* *Admission*: requests are admitted FIFO into free slots; a wave admits as
  many queued requests as fit ``bucket(max prompt) + max budget <= max_seq``.
  Admission happens the moment slots free — mid-queue, not at chunk
  boundaries.  ``ServeEngine.admissions`` logs ``(request_idx, slot)`` in
  admission order.
* *Slot lifecycle*: free → prefilled (pad-masked, bucketed) → decoding for
  exactly ``max_new_tokens`` tokens (budget-based completion is
  host-predictable: no device readback is needed to know when a slot frees)
  → free.  Slot state (KV rows, position, pad, current token) is reset by a
  masked merge, never a retrace.
* *Sync accounting*: each wave runs ``min(remaining budgets)`` decode steps
  and transfers its token matrix **once** (``ServeEngine.host_syncs`` counts
  the crossings) — O(1) syncs per admission wave, independent of the wave's
  step count.  The loop oracle syncs every token.

**Live operations** (``repro.serve.ops`` drives these hooks):

* *Hot-swap*: :meth:`ServeEngine.request_swap` stages a replacement
  parameter tree; the continuous driver installs it **atomically at the next
  admission-wave boundary** (immediately when idle) — in-flight slots keep
  decoding across the flip, zero requests dropped.  The staged tree must be
  fingerprint-compatible with the active one (same quantized-leaf shapes /
  bitwidths / numerics families, same dense remainder): shape or numerics
  drift is refused with a per-layer diagnostic and the active tree untouched.
  A numerics-identical swap (same weights under a different
  :class:`repro.tune.ModelPlan`) is token-invisible; a weight update applies
  to new admissions in full and to in-flight slots from their current
  position (their KV rows were written by the old weights — standard
  serving-upgrade semantics).
* *Wave observability*: ``ServeEngine.on_wave`` fires once per admission
  wave, after the wave's single host sync, with a structured
  :class:`WaveRecord` (wave index, admitted ``(request, slot)`` pairs,
  per-request emitted tokens, steps decoded, host-sync wall time) — the
  durable request log's write point (``repro.serve.request_log``), and
  where failure injection lands mid-serve.  The pre-PR-8 positional
  signature ``on_wave(wave, admitted, emitted)`` still works through a
  deprecation shim for one release (see :meth:`ServeEngine._dispatch_wave`).
* *Structured observability*: ``ServeEngine(obs=...)`` threads a
  :class:`repro.obs.Observer` through every driver.  Recording happens
  **only at the existing host syncs** — every traced value (wave index,
  steps, request ids, wall-clock reads) is already host-resident there, so
  tracing adds zero device transfers: tokens, ``host_syncs`` and
  ``admissions`` are bit-identical with ``obs`` on or off
  (``tests/test_obs.py``).
* *Profiler spans*: each continuous-driver wave runs inside a
  ``serve.wave`` step span whose phases (``serve.admit``, ``serve.prefill``,
  ``serve.decode``, ``serve.fetch``, ``serve.emit``) are host spans on the
  ``jax.profiler`` timeline, and every layer of the jitted programs carries
  a ``jax.named_scope`` (:mod:`repro.obs.scopes`).  Both are inert when no
  profiler runs, and change no token, sync or admission when one does.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import threading
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import timing
from repro.models.model import Model
from repro.obs import scopes

Array = jax.Array


def make_prefill_step(model: Model, *, ctx=None):
    def prefill_step(params, tokens, caches, prefix_embeds=None, pad_len=None):
        logits, caches = model.prefill(
            params, tokens, caches, prefix_embeds=prefix_embeds, ctx=ctx,
            pad_len=pad_len,
        )
        return logits, caches

    return prefill_step


def make_serve_step(model: Model, *, ctx=None, greedy: bool = True):
    """One decode step: (params, token [B,1], caches, pos) -> (next, caches)."""

    def serve_step(params, token, caches, pos, pad_len=None):
        logits, caches = model.decode_step(
            params, token, caches, pos, ctx=ctx, pad_len=pad_len
        )
        nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        return nxt, caches

    return serve_step


def make_decode_scan(model: Model, *, ctx=None):
    """Fixed-chunk decode program: every step fused into one ``lax.scan``.

    ``(params, prefill_logits [B,1,V], caches, pos0, pad [B], max_new [B],
    length)`` -> ``(tokens [B, length], caches)``.  The first token (greedy
    argmax of the prefill logits) is computed on device too, so the host
    touches nothing until the full token matrix is ready — one transfer per
    chunk.  Caches are donated: each step's KV writes reuse the prior buffers
    instead of allocating ``length`` cache copies.  Slots that exhausted
    their per-request budget keep stepping (static shapes) but their emitted
    tokens are masked to -1.
    """

    @functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(2,))
    def decode_scan(params, logits, caches, pos0, pad, max_new, length: int):
        tok0 = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)  # [B,1]

        def body(carry, _):
            token, caches, pos = carry
            lg, caches = model.decode_step(
                params, token, caches, pos, ctx=ctx, pad_len=pad
            )
            nxt = jnp.argmax(lg[:, -1:, :], axis=-1).astype(jnp.int32)
            return (nxt, caches, pos + 1), nxt[:, 0]

        with jax.named_scope(scopes.DECODE_LOOP):   # see make_decode_wave
            (_, caches, _), ys = jax.lax.scan(
                body, (tok0, caches, jnp.asarray(pos0, jnp.int32)), None,
                length=length - 1,
            )
        toks = jnp.concatenate([tok0, ys.T], axis=1)                 # [B, L]
        step_ix = jnp.arange(length, dtype=jnp.int32)[None, :]
        return jnp.where(step_ix < max_new[:, None], toks, -1), caches

    return decode_scan


def make_decode_wave(model: Model, *, ctx=None, out_cap: int):
    """Continuous-batching decode program: one jitted ``lax.while_loop``.

    ``(params, token [B,1], caches, pos [B], pad [B], active [B], steps)``
    -> ``(token, caches, pos, out [B, out_cap])``.  ``steps`` is a *traced*
    scalar, so every wave — whatever its step count — runs from this single
    trace.  ``out[:, 0]`` is the wave-start token (the prefill argmax for
    freshly admitted slots, already-reported for carried ones); columns
    ``1..steps`` are the tokens generated this wave; inactive slots are
    masked to -1.  Per-slot write positions advance only where ``active``.
    Caches are donated across waves.
    """

    @functools.partial(jax.jit, donate_argnums=(2,))
    def decode_wave(params, token, caches, pos, pad, active, steps):
        out0 = jnp.full((token.shape[0], out_cap), -1, jnp.int32)
        out0 = out0.at[:, 0].set(jnp.where(active, token[:, 0], -1))
        act = active.astype(jnp.int32)

        def cond(carry):
            return carry[0] < steps

        def body(carry):
            t, token, caches, pos, out = carry
            lg, caches = model.decode_step(
                params, token, caches, pos, ctx=ctx, pad_len=pad
            )
            nxt = jnp.argmax(lg[:, -1:, :], axis=-1).astype(jnp.int32)
            out = out.at[:, t + 1].set(jnp.where(active, nxt[:, 0], -1))
            return (t + 1, nxt, caches, pos + act, out)

        # A copy XLA inserts to carry the loop's state has no op_name of its
        # own and takes this scope from the loop (``repro.obs.scopes``).
        with jax.named_scope(scopes.DECODE_LOOP):
            _, token, caches, pos, out = jax.lax.while_loop(
                cond, body, (jnp.int32(0), token, caches, pos, out0)
            )
        return token, caches, pos, out

    return decode_wave


def make_admit_merge():
    """Slot-level state reset without retracing: splice freshly prefilled
    rows into the persistent serving state behind a boolean slot mask.

    Cache leaves are stacked per segment unit (``[n_units, B, ...]`` — batch
    on axis 1); per-slot vectors (token/pos/pad) carry batch on axis 0.  One
    trace serves every admission pattern.
    """

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def admit_merge(caches, new_caches, vecs, new_vecs, mask):
        cm = lambda old, new: jnp.where(
            mask.reshape((1, -1) + (1,) * (old.ndim - 2)), new, old
        )
        vm = lambda old, new: jnp.where(
            mask.reshape((-1,) + (1,) * (old.ndim - 1)), new, old
        )
        return jax.tree.map(cm, caches, new_caches), jax.tree.map(vm, vecs, new_vecs)

    return admit_merge


def bucket_to(n: int, floor: int) -> int:
    """Smallest ``floor * 2^i`` that is >= ``n`` (shape-bucketing helper).

    ``floor <= 1`` disables bucketing and returns ``n`` unchanged.
    """
    if floor <= 1:
        return n
    b = floor
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class WaveRecord:
    """What one admission wave did — the structured ``on_wave`` payload.

    Every field is host-resident when the record is built (the wave's
    single device→host sync has already happened), so consuming it —
    logging, tracing, metrics — adds no synchronization.  Timestamps are
    :func:`repro.timing.clock` seconds: ``t_start`` (wave boundary, before
    admission), ``t_decode`` (decode program dispatched), ``t_fetch``
    (host sync begins), ``t_sync`` (token matrix on host).  The chunked and
    loop drivers emit coarse per-chunk records to ``obs`` with the same
    shape (one chunk == one "wave").
    """

    wave: int
    admitted: list                      # [(request_idx, slot)], this wave
    emitted: list                       # [(request_idx, slot, tokens)]
    finished: frozenset = frozenset()   # request idxs that completed
    steps: int = 0                      # decode steps run this wave
    t_start: float = 0.0
    t_decode: float = 0.0
    t_fetch: float = 0.0
    t_sync: float = 0.0
    prefill_bucket: Optional[int] = None   # bucket of this wave's admissions
    queue_depth: int = 0                # requests still queued after admission
    active_slots: int = 0

    @property
    def sync_s(self) -> float:
        """Host-sync wall time: how long the host blocked on the device."""
        return self.t_sync - self.t_fetch


def _wave_cb_is_legacy(cb) -> bool:
    """True when ``cb`` expects the pre-PR-8 positional signature
    ``(wave, admitted, emitted)`` rather than one :class:`WaveRecord`.
    Detection is by required-positional-parameter count; undecidable
    callables (builtins, ``*args``) are treated as record-style."""
    try:
        sig = inspect.signature(cb)
    except (TypeError, ValueError):
        return False
    required = 0
    for p in sig.parameters.values():
        if p.kind == p.VAR_POSITIONAL:
            return True                 # *args almost certainly the old shape
        if (p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                and p.default is p.empty):
            required += 1
    return required >= 2


@dataclasses.dataclass
class Request:
    prompt: np.ndarray                  # [S] int32
    max_new_tokens: int = 16
    # Live-ops annotations (consumed by repro.serve.ops.LiveServer; the bare
    # engine ignores them):
    deadline_s: Optional[float] = None  # shed if still unfinished this many
                                        # seconds after serve() starts
    max_retries: Optional[int] = None   # per-request crash budget override
                                        # (None -> server default)


class ServeEngine:
    """Continuous-batching serving driver (static batch slots, greedy)."""

    def __init__(
        self,
        model: Model,
        params,
        *,
        batch: int,
        max_seq: int,
        ctx=None,
        decode: str = "scan",
        prompt_bucket: int = 8,
        plan=None,
        obs=None,
    ):
        if decode not in ("scan", "chunked", "loop"):
            raise ValueError(
                f"decode must be 'scan', 'chunked' or 'loop', got {decode!r}"
            )
        self.model = model
        if plan is not None:
            # Autotuned serving: apply the repro.tune ModelPlan (per-layer
            # spec rewrite + weight-stationary prepare; fingerprint-checked).
            # ``params`` must be the raw quantized tree — a prepared tree is
            # already frozen to one config and apply_plan refuses it.
            params = model.prepare(params, plan=plan, n_hint=batch)
        self.plan = plan
        self.params = params
        self.batch = batch
        self.max_seq = max_seq
        self.ctx = ctx
        self.decode = decode
        self.prompt_bucket = prompt_bucket
        self._prefill = jax.jit(make_prefill_step(model, ctx=ctx))
        self._step = jax.jit(make_serve_step(model, ctx=ctx))
        self._decode_scan = make_decode_scan(model, ctx=ctx)
        self._decode_wave = make_decode_wave(model, ctx=ctx, out_cap=max_seq)
        self._admit_merge = make_admit_merge()
        # ``prompt_bucket`` shapes the scan/chunked prefill traces; the loop
        # oracle always pads to the exact chunk max (i.e. behaves as
        # ``prompt_bucket=1`` by construction).
        self.host_syncs = 0             # device->host transfers, CUMULATIVE
                                        # across generate() calls (seed
                                        # contract; callers reset to re-count)
        self.admissions: list[tuple[int, int]] = []   # (request_idx, slot),
                                                      # reset per generate()
                                                      # (indices are per-call)
        self.bucket_counts: dict[int, int] = {}       # prefill bucket -> uses,
                                                      # cumulative (obs gauge)
        # --- observability + live-ops hooks -------------------------------
        self.obs = obs                  # repro.obs.Observer or None; records
                                        # ONLY at the existing host syncs
        self._obs_gen = 0               # Observer generation of this call
        self.on_wave = None             # callback(WaveRecord); the legacy
                                        # (wave, admitted, emitted) signature
                                        # is shimmed with a DeprecationWarning
        self.swaps = 0                  # completed hot-swaps, cumulative
        self.last_swap_wave: int | None = None
        self._swap_pending = None       # (params, on_applied) under _swap_lock
        self._swap_lock = threading.Lock()
        self._serving = False

    def _fetch(self, x) -> np.ndarray:
        """The ONLY device→host crossing point — counted so the O(1)-syncs
        property of the scan/wave decode is assertable from outside."""
        self.host_syncs += 1
        return np.asarray(x)

    def _validate(self, requests: list[Request]) -> None:
        for r in requests:
            if len(r.prompt) == 0:
                raise ValueError(
                    "empty prompt: with pad-masked prefill a zero-length "
                    "prompt has no valid key position to attend"
                )
            self._check_fits(len(r.prompt), r.max_new_tokens)

    def generate(self, requests: list[Request]) -> list[list[int]]:
        """Serve a list of equal-or-ragged prompts; returns per-request
        greedy tokens in request order."""
        self._validate(requests)
        self._serving = True
        if self.obs is not None:
            self._obs_gen = self.obs.serve_begin(
                [len(r.prompt) for r in requests], decode=self.decode,
                batch=self.batch,
            )
        try:
            if self.decode == "scan":
                return self._generate_continuous(requests)
            out: list[list[int]] = []
            for start in range(0, len(requests), self.batch):
                chunk = requests[start : start + self.batch]
                out.extend(
                    self._generate_batch_chunked(chunk, start)
                    if self.decode == "chunked"
                    else self._generate_batch_loop(chunk, start)
                )
            return out
        finally:
            self._serving = False
            # Batch drained: the boundary a swap requested mid-final-wave
            # (or mid-chunk in the non-continuous drivers) lands on.
            self._poll_swap()
            if self.obs is not None:
                self.obs.serve_end(self._obs_gen, engine=self)

    def _dispatch_wave(self, rec: WaveRecord) -> None:
        """Deliver one wave's record to ``obs`` and ``on_wave`` — after the
        wave's host sync, BEFORE the engine's own output bookkeeping (the
        durable-log crash-window contract).  ``obs`` records first, so a
        crash injected through ``on_wave`` still leaves the wave traced.

        Legacy shim: an ``on_wave`` written against the pre-PR-8 positional
        signature ``(wave, admitted, emitted)`` is still called that way,
        once-per-process warned.  The shim is scheduled for removal next
        release — migrate to ``on_wave(record)``."""
        if self.obs is not None:
            self.obs.wave(rec, gen=self._obs_gen, engine=self)
        cb = self.on_wave
        if cb is None:
            return
        if _wave_cb_is_legacy(cb):
            warnings.warn(
                "ServeEngine.on_wave(wave, admitted, emitted) is deprecated; "
                "accept a single serving.WaveRecord instead (its .wave, "
                ".admitted, .emitted fields carry the old arguments). The "
                "positional shim will be removed in the next release.",
                DeprecationWarning, stacklevel=3,
            )
            cb(rec.wave, rec.admitted, rec.emitted)
        else:
            cb(rec)

    # --- live operations: double-buffered parameter hot-swap --------------

    def request_swap(self, new_params, *, check: bool = True,
                     on_applied=None) -> None:
        """Stage ``new_params`` as the serving tree; the continuous driver
        installs it atomically at the next admission-wave boundary (the
        non-continuous drivers at the next batch boundary; immediately when
        idle).  In-flight slots are never dropped: they continue decoding
        across the flip.

        ``check`` (default) refuses incompatible trees — quantized-leaf
        fingerprint drift (shape / bitwidth / numerics-family changes,
        diagnosed per layer) or a different dense remainder — leaving the
        active tree untouched.  ``on_applied()`` fires on the serving thread
        the moment the flip lands (swap-latency instrumentation)."""
        if check:
            errs = self._swap_drift(self.params, new_params)
            if errs:
                shown = "; ".join(errs[:6]) + ("; ..." if len(errs) > 6 else "")
                raise ValueError(
                    f"incompatible hot-swap refused (active tree untouched): "
                    f"{shown}"
                )
        with self._swap_lock:
            self._swap_pending = (new_params, on_applied)
        if not self._serving:
            self._poll_swap()

    @staticmethod
    def _swap_drift(old_params, new_params) -> list[str]:
        """Why two trees cannot be hot-swapped (empty list == compatible):
        the quantized leaves must share their plan-invariant identities
        (``repro.tune.plan.describe_drift``) and the *dense* remainder —
        embeddings, norms, anything un-quantized — must match leaf-for-leaf
        in structure, shape and dtype.  The prepared products themselves
        (``p``/``wcanon``/mode-within-family) may differ freely: those are
        exactly what a plan swap replaces."""
        from repro.tune.plan import describe_drift, map_quantized_leaves

        msgs = describe_drift(old_params, new_params)

        def dense_sig(params):
            rest = map_quantized_leaves(params, lambda _p, _q: None)
            leaves, treedef = jax.tree.flatten(rest)
            # Non-array leaves degrade to their type name: a malformed tree
            # is *refused* (signature mismatch), never a crash mid-check.
            return (
                str(treedef),
                [(tuple(getattr(x, "shape", ())),
                  str(getattr(x, "dtype", type(x).__name__)))
                 for x in leaves],
            )

        if dense_sig(old_params) != dense_sig(new_params):
            msgs.append(
                "dense (non-quantized) parameter structure/shapes/dtypes "
                "differ between the active and staged trees"
            )
        return msgs

    def _poll_swap(self, wave: int | None = None) -> None:
        """Install a pending staged tree, if any — the single point where
        ``self.params`` changes while serving (called only between waves /
        batches, never with a decode program in flight)."""
        with self._swap_lock:
            pending, self._swap_pending = self._swap_pending, None
        if pending is None:
            return
        new_params, on_applied = pending
        self.params = new_params
        self.swaps += 1
        self.last_swap_wave = wave
        if on_applied is not None:
            on_applied()

    # --- shared helpers ---------------------------------------------------

    def _pad_prompts(self, chunk: list[Request], plen: int):
        """Left-pad ragged prompts into a [batch, plen] matrix; returns the
        tokens and the per-row pad lengths (the prefill pad mask)."""
        toks = np.zeros((self.batch, plen), np.int32)
        pad = np.zeros((self.batch,), np.int32)
        for i, r in enumerate(chunk):
            toks[i, plen - len(r.prompt) :] = r.prompt          # left-pad
            pad[i] = plen - len(r.prompt)
        return toks, pad

    def _check_fits(self, plen: int, max_new: int) -> None:
        if plen + max_new > self.max_seq:
            raise ValueError(
                f"prompt ({plen}) + max_new ({max_new}) exceeds max_seq "
                f"{self.max_seq}"
            )

    def _wave_bucket(self, reqs: list[Request]) -> int:
        """Prefill extent for a set of co-admitted requests: the prompt
        bucket, shrunk to the exact max length when the bucket would push the
        worst-case decode past max_seq."""
        plen = max(len(r.prompt) for r in reqs)
        worst = max(r.max_new_tokens for r in reqs)
        plen_b = bucket_to(plen, self.prompt_bucket)
        if plen_b + worst > self.max_seq:
            plen_b = max(plen, self.max_seq - worst)
        return plen_b

    def _wave_fits(self, reqs: list[Request]) -> bool:
        plen_b = self._wave_bucket(reqs)
        return plen_b >= max(len(r.prompt) for r in reqs) and all(
            plen_b + r.max_new_tokens <= self.max_seq for r in reqs
        )

    # --- continuous driver: slot scheduler + while-loop decode waves ------

    def _generate_continuous(self, requests: list[Request]) -> list[list[int]]:
        b = self.batch
        self.admissions = []      # per-call log: request indices are local
        outs: list[list[int]] = [[] for _ in requests]
        queue = [i for i, r in enumerate(requests) if r.max_new_tokens > 0]
        caches = self.model.init_cache(b, self.max_seq, dtype=jnp.float32)
        token = jnp.zeros((b, 1), jnp.int32)
        pos = jnp.zeros((b,), jnp.int32)
        pad = jnp.zeros((b,), jnp.int32)
        slot_req: list[int | None] = [None] * b   # request idx per slot
        slot_rem = [0] * b                        # decode steps still owed
        qi = 0
        wave = 0
        while qi < len(queue) or any(s is not None for s in slot_req):
            # Admission-wave boundary: no decode program in flight, so a
            # staged hot-swap installs atomically here — new admissions
            # prefill under the new tree, carried slots continue under it.
            self._poll_swap(wave)
            with jax.profiler.StepTraceAnnotation(scopes.WAVE, step_num=wave):
                t_wave = timing.clock()     # host-side read at the boundary
                plen_b: Optional[int] = None
                admitted: list[int] = []
                wave_reqs: list[Request] = []
                with scopes.span(scopes.ADMIT):
                    # Admission: FIFO into free slots, as many as legally
                    # share one prefill extent (singletons always fit, so
                    # the queue drains).
                    for s in range(b):
                        if slot_req[s] is not None or qi >= len(queue):
                            continue
                        cand = requests[queue[qi]]
                        if not self._wave_fits(wave_reqs + [cand]):
                            break
                        wave_reqs.append(cand)
                        slot_req[s] = queue[qi]
                        slot_rem[s] = cand.max_new_tokens - 1
                        admitted.append(s)
                        qi += 1
                    if admitted:
                        plen_b = self._wave_bucket(wave_reqs)
                        self.bucket_counts[plen_b] = (
                            self.bucket_counts.get(plen_b, 0) + 1)
                        toks = np.zeros((b, plen_b), np.int32)
                        npad = np.zeros((b,), np.int32)
                        amask = np.zeros((b,), bool)
                        for s in admitted:
                            pr = requests[slot_req[s]].prompt
                            toks[s, plen_b - len(pr) :] = pr
                            npad[s] = plen_b - len(pr)
                            amask[s] = True
                        toks, npad, amask = (jnp.asarray(toks), jnp.asarray(npad),
                                             jnp.asarray(amask))
                if admitted:
                    with scopes.span(scopes.PREFILL):
                        # Prefill must see a ZERO cache, not a reused
                        # scratch: recurrent units (M/R/S) consume the
                        # incoming state as their initial state during
                        # prefill, so a previous occupant's state would leak
                        # into the new request.  (Attention rows would be
                        # safe — stale keys past the written extent are
                        # never attended.)
                        fresh = self.model.init_cache(b, self.max_seq,
                                                      dtype=jnp.float32)
                        lg, fresh = self._prefill(self.params, toks, fresh,
                                                  pad_len=npad)
                        tok0 = jnp.argmax(lg[:, -1:, :], axis=-1).astype(jnp.int32)
                        caches, (token, pos, pad) = self._admit_merge(
                            caches, fresh, (token, pos, pad),
                            (tok0, jnp.full((b,), plen_b, jnp.int32), npad),
                            amask,
                        )
                    self.admissions.extend((slot_req[s], s) for s in admitted)
                active = np.array([s is not None for s in slot_req])
                steps = min(
                    (slot_rem[s] for s in range(b) if slot_req[s] is not None),
                    default=0,
                )
                with scopes.span(scopes.DECODE):
                    t_decode = timing.clock()   # decode program dispatched
                    token, caches, pos, out_dev = self._decode_wave(
                        self.params, token, caches, pos, pad,
                        jnp.asarray(active), jnp.int32(steps),
                    )
                # The wave's single device->host sync; steps is host-known,
                # so only the used columns cross (the slice is outside the
                # trace).
                with scopes.span(scopes.FETCH):
                    t_fetch = timing.clock()
                    mat = self._fetch(out_dev[:, : 1 + steps])
                    t_sync = timing.clock()
                with scopes.span(scopes.EMIT):
                    emitted: list[tuple[int, int, list[int]]] = []
                    for s in range(b):
                        i = slot_req[s]
                        if i is None:
                            continue
                        lo = 0 if s in admitted else 1   # col 0 = wave-start token
                        emitted.append(
                            (i, s, [int(t) for t in mat[s, lo : 1 + steps]]))
                    # Fires after the sync but before outs/slot bookkeeping:
                    # the request log's write point.  A crash here (injected
                    # or real) lands after the wave's tokens are durable, so
                    # replay resumes *including* this wave with no
                    # duplicates.  Every record field is already
                    # host-resident — building it syncs nothing.
                    self._dispatch_wave(WaveRecord(
                        wave=wave,
                        admitted=[(slot_req[s], s) for s in admitted],
                        emitted=emitted,
                        finished=frozenset(
                            i for i, s, _t in emitted if slot_rem[s] == steps
                        ),
                        steps=steps,
                        t_start=t_wave, t_decode=t_decode,
                        t_fetch=t_fetch, t_sync=t_sync,
                        prefill_bucket=plen_b,
                        queue_depth=len(queue) - qi,
                        active_slots=int(active.sum()),
                    ))
                    for i, s, toks_w in emitted:
                        outs[i].extend(toks_w)
                        slot_rem[s] -= steps
                        if slot_rem[s] == 0:
                            slot_req[s] = None       # freed: next wave re-admits
            wave += 1
        return outs

    # --- chunked driver: bucketed prefill + one fused decode per chunk ----

    def _generate_batch_chunked(self, chunk: list[Request],
                                start: int = 0) -> list[list[int]]:
        b = self.batch
        t_wave = timing.clock()
        plen = max(len(r.prompt) for r in chunk)
        max_new = max(r.max_new_tokens for r in chunk)
        # Chunked decode runs the whole chunk to the worst-case budget, so
        # the chunk's (max plen, max budget) pair must fit — a per-request
        # check is not enough (the continuous driver needs only that).
        self._check_fits(plen, max_new)
        if max_new == 0:
            return [[] for _ in chunk]
        # Bucket prompt length and decode length to powers of two so each
        # bucket traces once; when a bucket would overflow max_seq, fall back
        # to the exact size (an off-bucket trace either way — don't also pay
        # for masked decode steps past max_new).
        length = bucket_to(max_new, 2)
        if plen + length > self.max_seq:
            length = max_new
        plen_b = min(bucket_to(plen, self.prompt_bucket), self.max_seq - length)
        self.bucket_counts[plen_b] = self.bucket_counts.get(plen_b, 0) + 1

        toks, pad = self._pad_prompts(chunk, plen_b)
        caches = self.model.init_cache(b, self.max_seq, dtype=jnp.float32)
        logits, caches = self._prefill(
            self.params, jnp.asarray(toks), caches, pad_len=jnp.asarray(pad)
        )
        mn = np.ones((b,), np.int32)
        for i, r in enumerate(chunk):
            mn[i] = r.max_new_tokens
        t_decode = timing.clock()
        ys, _ = self._decode_scan(
            self.params, logits, caches, jnp.int32(plen_b), jnp.asarray(pad),
            jnp.asarray(mn), length,
        )
        t_fetch = timing.clock()
        mat = self._fetch(ys)            # the chunk's single device->host sync
        t_sync = timing.clock()
        outs = [
            [int(t) for t in mat[i, : chunk[i].max_new_tokens]]
            for i in range(len(chunk))
        ]
        if self.obs is not None:
            # Coarse per-chunk record (one chunk == one "wave"): same host
            # sync point, same zero-sync discipline as the continuous driver.
            self.obs.wave(WaveRecord(
                wave=start // b,
                admitted=[(start + i, i) for i in range(len(chunk))],
                emitted=[(start + i, i, outs[i]) for i in range(len(chunk))],
                finished=frozenset(start + i for i in range(len(chunk))),
                steps=length,
                t_start=t_wave, t_decode=t_decode,
                t_fetch=t_fetch, t_sync=t_sync,
                prefill_bucket=plen_b, queue_depth=0,
                active_slots=len(chunk),
            ), gen=self._obs_gen, engine=self)
        return outs

    # --- seed driver: per-token Python loop (baseline / oracle) -----------

    def _generate_batch_loop(self, chunk: list[Request],
                             start: int = 0) -> list[list[int]]:
        t_wave = timing.clock()
        plen = max(len(r.prompt) for r in chunk)
        self._check_fits(plen, max(r.max_new_tokens for r in chunk))
        self.bucket_counts[plen] = self.bucket_counts.get(plen, 0) + 1
        toks, pad = self._pad_prompts(chunk, plen)
        pad_dev = jnp.asarray(pad)
        caches = self.model.init_cache(self.batch, self.max_seq, dtype=jnp.float32)
        logits, caches = self._prefill(
            self.params, jnp.asarray(toks), caches, pad_len=pad_dev
        )
        token = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        max_new = max(r.max_new_tokens for r in chunk)
        outs: list[list[int]] = [[] for _ in chunk]
        if max_new == 0:
            return outs
        tok_h = self._fetch(token)                  # one sync per decoded step
        for i, r in enumerate(chunk):
            if r.max_new_tokens > 0:
                outs[i].append(int(tok_h[i, 0]))
        for t in range(max_new - 1):
            token, caches = self._step(
                self.params, token, caches, jnp.int32(plen + t), pad_dev
            )
            tok_h = self._fetch(token)
            for i, r in enumerate(chunk):
                if len(outs[i]) < r.max_new_tokens:
                    outs[i].append(int(tok_h[i, 0]))
        if self.obs is not None:
            t_sync = timing.clock()
            # The loop driver syncs every step; record one coarse per-chunk
            # span so SLO stats stay comparable across decode modes.
            self.obs.wave(WaveRecord(
                wave=start // self.batch,
                admitted=[(start + i, i) for i in range(len(chunk))],
                emitted=[(start + i, i, outs[i]) for i in range(len(chunk))],
                finished=frozenset(start + i for i in range(len(chunk))),
                steps=max_new,
                t_start=t_wave, t_decode=t_wave,
                t_fetch=t_wave, t_sync=t_sync,
                prefill_bucket=plen, queue_depth=0,
                active_slots=len(chunk),
            ), gen=self._obs_gen, engine=self)
        return outs
