"""Model step: device time of the traced prefill_step programs over the
device's busy time, in %."""


def read(run):
    if run.trace is None or not run.trace["busy_s"]:
        return None
    return 100.0 * run.trace["programs"].get("prefill_step", 0.0) / run.trace["busy_s"]
