"""Device time by the program's named scopes, and device idle time by the
serving program's host spans, from a profiler trace of the traced span.

This extends the reduction of ``bench/trace_reduce.py`` (the same events,
planes, lines and window between the ``bench.trace_begin`` and
``bench.trace_end`` markers, and its self-time and busy-union helpers) with
two keys:

- ``scopes``: device self time per program and innermost program scope,
  ``{"decode_wave": {"layers": s, "qlinear": s, ...}, ...}``.  Self time is
  an operation's duration less that of the operations directly inside it
  (a ``while`` less its body), so a program's buckets add up to its
  operations' self time.  ``scope_ops`` names the ``TOP`` operations with
  the most self time in each scope, over all programs.
- ``host_idle``: the device's idle time (no operation running) split by
  the innermost ``serve.*`` span (``repro.obs.SPANS``) the host's Python
  thread was in, or ``outside serve spans``; ``waves`` counts the
  ``serve.wave`` spans that end inside the window.

A v5e trace names each operation by its HLO instruction and carries no
``op_name``, so each operation's scope comes from the compiled programs'
HLO text (``jax.stages.Compiled.as_text()``, which keeps the metadata),
taken outside the window: :func:`op_scopes` keys it as
``trace_reduce.short`` keys the trace's operations, by instruction name and
result shape.  An operation's scope is the innermost name of
``repro.obs.SCOPES`` on its ``op_name`` path.  An instruction whose path
holds none (the copies XLA inserts to carry a loop's state) takes the scope
of the ``while`` (or other caller) whose computation holds it, which is
the operation that encloses it on the trace's ``XLA Ops`` line; only what
remains is ``unscoped``.  Times are seconds, averaged over the chips.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from bench.trace_reduce import (BEGIN, END, MODULES, OPS, _program, _self_times,
                                _union, events, short)
from repro.obs import SCOPES, SPANS

UNSCOPED = "unscoped"
OUTSIDE = "outside serve spans"
TOP = 8
OP_NAME = re.compile(r'op_name="([^"]*)"')
# ``%body.12 (p: (s32[], ...)) -> (...) {`` opens a computation.
COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
# The computations an instruction runs: a while's body and condition, a
# call's or conditional's.
CALLEES = re.compile(r"\b(?:body|condition|to_apply|calls)=%([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")


def scope_of(op_name: str) -> str | None:
    """The innermost name of ``SCOPES`` on an ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def op_scopes(hlo_text: str) -> dict:
    """``{short name: scope}`` of every instruction of a compiled program's
    HLO text: the innermost scope on its ``op_name``, else its caller's."""
    own, home, caller = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        line = line.strip()
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        line = line.removeprefix("ROOT ")
        if " = " not in line or comp is None:
            continue
        key = short(line)
        op = OP_NAME.search(line)
        own[key] = scope_of(op.group(1)) if op else None
        home[key] = comp
        for one, many in CALLEES.findall(line):
            for callee in [one] if one else re.findall(r"%([\w.\-]+)", many):
                caller[callee] = key

    def resolve(key, depth=0):
        if own[key] or depth > 64:
            return own[key]
        up = caller.get(home[key])
        return resolve(up, depth + 1) if up else None

    return {k: resolve(k) or UNSCOPED for k in own}


def _timeline(spans, lo: float, hi: float) -> list:
    """Segments ``(start, end, name)`` covering ``[lo, hi]``, each named by
    the innermost of the nested ``spans`` (``(start, end, name)``, inside
    the window) over it, or ``OUTSIDE``."""
    marks = sorted([(s, 1, e, n) for s, e, n in spans]
                   + [(e, 0, e, n) for s, e, n in spans])
    out, open_, t = [], [], lo
    for at, opens, end, name in marks:
        if at > t:
            out.append((t, at, min(open_)[1] if open_ else OUTSIDE))
            t = at
        if opens:
            open_.append((end - at, name, end))
        else:
            open_.remove(next(x for x in open_ if x[1:] == (name, end)))
    if hi > t:
        out.append((t, hi, OUTSIDE))
    return out


def _split(gaps, segments) -> dict:
    """Time of ``gaps`` (sorted, disjoint) within each named segment."""
    out: dict = defaultdict(float)
    starts = [s for s, _e, _n in segments]
    for a, b in gaps:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segments) and segments[i][0] < b:
            s, e, name = segments[i]
            if e > a:
                out[name] += min(b, e) - max(a, s)
            i += 1
    return out


def reduce_events(evs, scopes_by_program: dict) -> dict:
    """The ``scopes`` and ``host_idle`` of ``trace_reduce.events`` tuples;
    ``scopes_by_program`` maps each program to the :func:`op_scopes` of its
    compiled variants (one per prefill bucket, say)."""
    evs = list(evs)
    marks = {n: (s, e) for p, _l, n, s, e in evs
             if not p.startswith("/device:") and n in (BEGIN, END)}
    if BEGIN not in marks or END not in marks:
        raise RuntimeError("the trace lacks the benchmark's window markers")
    lo, hi = marks[BEGIN][1], marks[END][0]
    devices = sorted({p for p, *_ in evs if re.fullmatch(r"/device:TPU:\d+", p)})
    host = [(max(s, lo), min(e, hi), n) for p, line, n, s, e in evs
            if p.startswith("/host:") and line.startswith("python")
            and n in SPANS and min(e, hi) > max(s, lo)]
    segments = _timeline(host, lo, hi)
    scopes: dict = defaultdict(lambda: defaultdict(float))
    by_op: dict = defaultdict(lambda: defaultdict(float))
    idle: dict = defaultdict(float)
    for dev in devices:
        mods, ops = [], []
        for p, line, name, s, e in evs:
            if p != dev or e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            if line == MODULES:
                mods.append((s, e, _program(name)))
            elif line == OPS:
                ops.append((s, e, short(name)))
        mods.sort()
        starts = [s for s, _e, _n in mods]

        def program(t):
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else "outside programs"

        named = [(s, e, (program(s), op)) for s, e, op in ops]
        for (prog, op), ns in _self_times(named).items():
            scope = scopes_by_program.get(prog, {}).get(op, UNSCOPED)
            scopes[prog][scope] += ns
            by_op[scope][op] += ns
        busy = _union([(s, e) for s, e, _op in ops])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, ns in _split(gaps, segments).items():
            idle[name] += ns
    n = max(len(devices), 1)
    return {
        "scopes": {prog: {k: v / n / 1e9 for k, v in by.items()}
                   for prog, by in scopes.items()},
        "scope_ops": {scope: [[op, v / n / 1e9] for op, v in
                              sorted(by.items(), key=lambda kv: -kv[1])[:TOP]]
                      for scope, by in by_op.items()},
        "host_idle": {k: v / n / 1e9 for k, v in idle.items()},
        "waves": sum(1 for p, _l, name, _s, e in evs
                     if p.startswith("/host:") and name == SPANS[0] and lo < e <= hi),
    }


def reduce(path: str, hlo: dict) -> dict:
    """:func:`reduce_events` of the trace at ``path``; ``hlo`` maps each
    program to the HLO texts of its compiled variants."""
    from jax.profiler import ProfileData

    by_program = {prog: {k: v for text in texts for k, v in op_scopes(text).items()}
                  for prog, texts in hlo.items()}
    return reduce_events(events(ProfileData.from_file(path)), by_program)
