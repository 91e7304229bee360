"""Scheduler: decode steps per admission wave over the window (each wave
is one host round trip)."""


def read(run):
    waves = run.window_waves()
    return sum(w.steps for w in waves) / len(waves) if waves else None
