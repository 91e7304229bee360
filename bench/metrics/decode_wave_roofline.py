"""Quantized linear and the whole decode step: the least time of the traced
decode steps (larger of FLOPs at the bfloat16 peak and least bytes at HBM
bandwidth, per step) over the device time of the decode_wave programs that
ran them, in %."""

from bench import accounting


def read(run):
    waves = run.traced_waves()
    t = run.trace["programs"].get("decode_wave") if run.trace else None
    if not waves or not t:
        return None
    least, steps, _bounds = accounting.decode_least(run, waves)
    return 100.0 * least / t if steps else None
