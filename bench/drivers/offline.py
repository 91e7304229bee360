"""Offline driver: one deep job, queued before the window opens.

The whole queue goes to one ``ServeEngine.generate`` call.  The window
opens at the first wave boundary after every slot has been filled once, so
the fill from empty slots is not timed, and closes at the first wave
boundary ``seconds`` after that: ``on_wave`` raises there to end the call.
"""

from __future__ import annotations

import jax

from bench import generate
from bench.runlog import ReqLog, RunLog, WindowClosed


def run(s) -> RunLog:
    mix = s.mix
    reqs = generate.requests(mix, s.vocab, s.seed,
                             mix["queue_blocks"] * mix["block"])
    log = RunLog({i: ReqLog(r.prompt, r.max_new) for i, r in enumerate(reqs)})
    ids = list(range(len(reqs)))
    filled: set = set()

    def on_wave(rec):
        with jax.profiler.TraceAnnotation("bench.on_wave"):
            log.add_wave(rec, ids)
            filled.update(slot for _, slot in rec.admitted)
            if log.t_open is None:
                if len(filled) == s.batch:
                    log.t_open = rec.t_sync
                    s.window_opened()
            elif rec.t_sync >= log.t_open + s.seconds:
                log.t_close = rec.t_sync
                s.window_closed()
                raise WindowClosed
            else:
                s.wave_boundary(rec.t_sync)

    s.engine.on_wave = on_wave
    try:
        with jax.profiler.TraceAnnotation("bench.generate"):
            s.engine.generate([s.request(r) for r in reqs])
    except WindowClosed:
        return log
    finally:
        s.engine.on_wave = None
    raise RuntimeError(
        f"the queue of {len(reqs)} requests emptied before the window closed; "
        f"raise queue_blocks in the mix"
    )
