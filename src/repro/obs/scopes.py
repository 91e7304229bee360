"""The names the profiler sees: scopes inside the compiled programs and spans
on the host.

**Device scopes** are ``jax.named_scope`` names.  They are compile-time
metadata: each HLO operation's ``op_name`` carries the scope path it was
traced under (``jit(decode_wave)/decode_loop/while/body/layers/while/body/
closed_call/attention/qlinear/dot_general``).  The compiled program's HLO
text keeps it per instruction, and a device trace names each operation by
its instruction.  The innermost scope of :data:`SCOPES` on that path names
the layer the operation's time belongs to:

* ``qlinear`` — a quantized projection (code expansion and matmul, or the
  Pallas kernel), whatever module calls it;
* ``attention`` — scores, softmax and values, with the slice of the
  layer's cache they read;
* ``kv_write`` — the write of a step's KV rows into the stacked cache;
* ``layers`` — the layer ``lax.scan``: what no inner scope claims (norms,
  residuals, the FFN's activation, a recurrent state's write-back);
* ``decode_loop`` — the serving layer's decode loop (``decode_wave``'s
  ``while_loop``, ``decode_scan``'s scan): the loop body's own work (the
  embedding lookup, the token matrix's write, greedy sampling where XLA
  does not fuse it into the head) and any copy XLA inserts to carry the
  loop's state;
* ``lm_head`` — the float32 head.

XLA names a fused operation after its root, so a scope placed only around
an operation that fuses into another one's computation is lost, and the
copies XLA inserts to carry a loop's state have no ``op_name`` at all: a
trace reduction gives such an operation the scope of the loop that
encloses it.  ``tests/test_scopes.py`` checks that every scope survives
compilation.

**Host spans** (:data:`SPANS`) are ``jax.profiler`` annotations on the
profiler's own host timeline, to which the device planes are aligned: the
wave boundary's phases in the continuous driver, each inside its wave's
``serve.wave`` step span (a ``jax.profiler.StepTraceAnnotation`` whose
step number is the wave index).  With no profiler running an annotation
costs a few hundred nanoseconds; a scope costs nothing at run time.  Span
names carry no arguments.
"""

from __future__ import annotations

import functools

import jax

SCOPES = (QLINEAR, ATTENTION, KV_WRITE, LAYERS, DECODE_LOOP, LM_HEAD) = (
    "qlinear", "attention", "kv_write", "layers", "decode_loop", "lm_head")

SPANS = (WAVE, ADMIT, PREFILL, DECODE, FETCH, EMIT) = (
    "serve.wave", "serve.admit", "serve.prefill", "serve.decode",
    "serve.fetch", "serve.emit")


def scoped(name: str):
    """Decorator: trace the function inside ``jax.named_scope(name)``, with a
    fresh context per call (one shared scope object is not thread-safe)."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` on the profiler's timeline."""
    return jax.profiler.TraceAnnotation(name)

