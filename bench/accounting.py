"""Work of recorded waves, from the configuration's shapes.

Each wave records which tokens of which request it delivered.  Token ``j``
(0-based) of a request with a ``p``-token prompt came from its prefill when
``j == 0`` and otherwise from a decode step attending ``p + j`` keys; the
``t``-th decode step of a wave produced the ``t``-th decode token of every
request active in it.
"""

from __future__ import annotations


def _decode_keys(run, w) -> list[list[int]]:
    """Per decode step of wave ``w``: the keys each active slot attended."""
    steps: list[list[int]] = [[] for _ in range(w.steps)]
    for gid, j0, n in w.emitted:
        p = run.log.reqs[gid].prompt_len
        js = [j for j in range(j0, j0 + n) if j >= 1]
        for t, j in enumerate(js):
            steps[t].append(p + j)
    return steps


def flops(run, waves) -> float:
    """Algorithmic FLOPs of ``waves``: the prompts they admitted and every
    decode token they delivered."""
    total = 0.0
    for w in waves:
        for gid in w.admitted:
            total += run.work.prefill_flops(run.cfg, run.log.reqs[gid].prompt_len)
        for keys in _decode_keys(run, w):
            total += sum(run.work.decode_flops(run.cfg, k) for k in keys)
    return total


def decode_least(run, waves) -> tuple[float, int, dict]:
    """Least time of every decode step in ``waves``, the step count, and
    how many steps each bound (compute or memory) limited."""
    total, n, bounds = 0.0, 0, {"compute": 0, "memory": 0}
    for w in waves:
        for keys in _decode_keys(run, w):
            if not keys:
                continue
            t, bound = run.work.least_time(*run.work.decode_step(run.cfg, keys),
                                           run.peak)
            total += t
            n += 1
            bounds[bound] += 1
    return total, n, bounds
