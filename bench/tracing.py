"""The profiler window of a traced run, and the count of compilations.

A traced run (``--trace 1``) profiles the start of the measured window:
from its opening wave boundary to the first wave boundary at least
``TRACE_SECONDS`` later (or the window's close).  Markers written at both
ends put the traced span on the trace's own clock; the host clock reads of
the same moments select the waves that ran inside it.
"""

from __future__ import annotations

import glob
import os
import time

import jax

TRACE_SECONDS = 8.0
BEGIN, END = "bench.trace_begin", "bench.trace_end"


class Tracer:
    def __init__(self, out_dir: str | None):
        self.out_dir = out_dir          # None: an untraced run
        self.t_begin = self.t_end = None
        self.active = False

    def start(self) -> None:
        if self.out_dir is None or self.t_begin is not None:
            return
        # Host spans are the benchmark's own annotations; Python function
        # tracing would slow the host it measures.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(BEGIN):
            self.t_begin = time.perf_counter()
        self.active = True

    def maybe_stop(self, now: float) -> None:
        if self.active and now - self.t_begin >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        with jax.profiler.TraceAnnotation(END):
            self.t_end = time.perf_counter()
        jax.profiler.stop_trace()
        self.active = False

    def xplane(self) -> str:
        files = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return files[0]


class CompileCounter:
    """Counts program lowerings (each new program a process needs, whether
    it is then compiled or read from the persistent cache) and backend
    compilations, through ``jax.monitoring``."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw) -> None:
        if name == self.LOWER:
            self.lowered += 1
        elif name == self.COMPILE:
            self.compiled += 1

    def snapshot(self) -> tuple[int, int]:
        return self.lowered, self.compiled
