"""What a run records: its waves, its requests and its window.

The drivers fill a :class:`RunLog` from the engine's ``on_wave`` records
(host-clock stamps taken at the wave's one host sync) and from their own
clock reads; the metric readers and the correctness check read it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax


class WindowClosed(Exception):
    """Raised from ``on_wave`` to end an offline job at a wave boundary."""


@dataclasses.dataclass
class ReqLog:
    prompt: object                      # [P] int32 token ids
    max_new: int
    due: Optional[float] = None         # open loop: when it was due
    t_admit: Optional[float] = None     # t_start of the wave that admitted it
    t_first: Optional[float] = None     # t_sync of the wave with its 1st token
    t_last: Optional[float] = None
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Wave:
    t_start: float
    t_sync: float
    steps: int
    active_slots: int
    prefill_bucket: Optional[int]
    admitted: list          # global request ids
    emitted: list           # (global id, index of its first token here, count)


@dataclasses.dataclass
class RunLog:
    reqs: dict
    waves: list = dataclasses.field(default_factory=list)
    t_open: Optional[float] = None
    t_close: Optional[float] = None

    def add_wave(self, rec, ids: list) -> Wave:
        """Record one ``WaveRecord``; ``ids[i]`` is the global id of the
        call's request ``i``."""
        with jax.profiler.TraceAnnotation("bench.record_wave"):
            emitted = []
            for i, _slot, toks in rec.emitted:
                r = self.reqs[ids[i]]
                if toks and r.t_first is None:
                    r.t_first = rec.t_sync
                if toks:
                    r.t_last = rec.t_sync
                emitted.append((ids[i], len(r.tokens), len(toks)))
                r.tokens.extend(toks)
            for i in rec.finished:
                self.reqs[ids[i]].done = True
            for i, _slot in rec.admitted:
                self.reqs[ids[i]].t_admit = rec.t_start
            w = Wave(rec.t_start, rec.t_sync, rec.steps,
                     rec.active_slots, rec.prefill_bucket,
                     [ids[i] for i, _ in rec.admitted], emitted)
            self.waves.append(w)
            return w

    def window_waves(self) -> list:
        """Waves that ended inside the window (after its opening boundary)."""
        return [w for w in self.waves
                if self.t_open < w.t_sync <= self.t_close]
