"""Scheduler: share of slot-steps that decoded a live request over the
window's waves, sum(active_slots * steps) / (batch * sum(steps)), in %."""


def read(run):
    waves = run.window_waves()
    steps = sum(w.steps for w in waves)
    if not steps:
        return None
    return 100.0 * sum(w.active_slots * w.steps for w in waves) / (run.batch * steps)
