"""Whole step: algorithmic FLOPs of the traced waves (admitted prompts and
delivered decode tokens, from the configuration's shapes) over the traced
span and the chip's bfloat16 peak, in %."""

from bench import accounting


def read(run):
    waves = run.traced_waves()
    if run.trace is None or not run.trace["devices"] or not waves:
        return None
    f = accounting.flops(run, waves)
    return 100.0 * f / run.trace["window_s"] / run.peak["bf16_flops_per_s"]
