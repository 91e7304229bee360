"""Model step: device time of the traced decode_wave programs over the
decode steps they ran, in ms."""


def read(run):
    waves = run.traced_waves()
    t = run.trace["programs"].get("decode_wave") if run.trace else None
    steps = sum(w.steps for w in waves)
    return 1e3 * t / steps if t and steps else None
