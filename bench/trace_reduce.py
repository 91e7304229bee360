"""Reduce a profiler trace of the traced span to the numbers the metrics read.

The trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``) holds
one plane per TPU core, ``/device:TPU:<n>``, whose ``XLA Modules`` line has
one event per program run (named ``jit_<function>(<id>)``) and whose
``XLA Ops`` line has one event per operation run; and host planes whose
thread lines hold the benchmark's ``jax.profiler.TraceAnnotation`` spans.
Everything is clipped to the span between the ``bench.trace_begin`` and
``bench.trace_end`` markers.

- ``busy_s``: the union of the operation intervals, averaged over the chips.
- ``programs``: device time per program, by function name
  (``decode_wave``, ``prefill_step``, ``admit_merge``, ...).
- ``kernels``: device time per custom call (a Pallas kernel), by the name
  of its instruction (``lut_dequant_gemm``).
- ``breakdown``: the ten operations with the most self time (a ``while``
  less the operations inside it), and the ten longest idle gaps, each named
  by the innermost span of the host's Python thread at the gap's middle (the
  benchmark's own, or JAX's dispatch and fetch spans).
"""

from __future__ import annotations

import re
from collections import defaultdict

BEGIN, END = "bench.trace_begin", "bench.trace_end"
MODULES, OPS = "XLA Modules", "XLA Ops"
# A Pallas kernel's operation in the trace, named by its instruction
# (``%lut_dequant_gemm.47 = f32[8,13824]{...} custom-call(...)``).
KERNEL = re.compile(r"%?([A-Za-z_]\w*?)(\.\d+)? = .* custom-call\(")
TOP = 10


def _program(name: str) -> str:
    """``jit_decode_wave(123)`` -> ``decode_wave``."""
    name = re.sub(r"\(.*\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def events(pd):
    """``(plane name, line name, event name, start ns, end ns)`` of a trace."""
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                yield (plane.name, line.name, ev.name, ev.start_ns,
                       ev.start_ns + ev.duration_ns)


def short(name: str) -> str:
    """``%fusion.6 = bf16[8,13824]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.6 bf16[8,13824]``: an operation's HLO text cut to its name and
    result shape."""
    m = re.match(r"%?([^\s=]+) = (\(?[a-z0-9]+\[[^\]]*\])", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _self_times(iv) -> dict:
    """Self time per name of nested ``(start, end, name)`` intervals of one
    line: each event's duration less that of the events directly inside it
    (a ``while`` contains its body's operations)."""
    out: dict = defaultdict(float)
    stack: list = []                       # [end, name, duration, children]
    def close(top):
        out[top[1]] += top[2] - top[3]
    for s, e, name in sorted(iv, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce_events(evs) -> dict:
    evs = list(evs)
    marks = {n: (s, e) for p, _l, n, s, e in evs
             if not p.startswith("/device:") and n in (BEGIN, END)}
    if BEGIN not in marks or END not in marks:
        raise RuntimeError("the trace lacks the benchmark's window markers")
    lo, hi = marks[BEGIN][1], marks[END][0]
    devices = sorted({p for p, *_ in evs if re.fullmatch(r"/device:TPU:\d+", p)})
    busy_ns = 0.0
    programs: dict = defaultdict(float)
    kernels: dict = defaultdict(float)
    ops: dict = defaultdict(float)
    gaps: list = []
    for dev in devices:
        op_iv = []
        for p, line, name, s, e in evs:
            if p != dev or e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            if line == MODULES:
                programs[_program(name)] += e - s
            elif line == OPS:
                op_iv.append((s, e, short(name)))
                kernel = KERNEL.match(name)
                if kernel:
                    kernels[kernel.group(1)] += e - s
        for k, v in _self_times(op_iv).items():
            ops[k] += v
        busy = _union([(s, e) for s, e, _ in op_iv])
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = max(len(devices), 1)
    # What the host's Python thread was in: the benchmark's spans and JAX's
    # own (dispatch of a jitted function, a blocking fetch).
    spans = [(s, e, name) for p, line, name, s, e in evs
             if p.startswith("/host:") and line.startswith("python")
             and name not in (BEGIN, END)]

    def host_doing(t: float) -> str:
        inside = [(e - s, name) for s, e, name in spans if s <= t <= e]
        return min(inside)[1] if inside else "host outside any span"

    gaps.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    sec = lambda ns: ns / n / 1e9
    out = {
        "devices": len(devices),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sec(busy_ns),
        "programs": {k: sec(v) for k, v in programs.items()},
        "kernels": {k: sec(v) for k, v in kernels.items()},
        "breakdown": {
            "device_ops": [[k, sec(v)] for k, v in top_ops],
            "idle_gaps": [[host_doing((s + e) / 2), sec(e - s) * n]
                          for s, e in gaps[:TOP]],
        },
    }
    out["summary"] = {k: out[k] for k in
                      ("devices", "window_s", "busy_s", "programs", "kernels")}
    return out


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_events(events(ProfileData.from_file(path)))
