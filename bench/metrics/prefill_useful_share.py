"""Scheduler: share of the prompt positions prefilled over the window's
waves that held the admitted prompts' own tokens, sum(prompt lengths) /
sum(batch * prefill bucket), in %.  Every admission prefills all the
batch's rows to its wave's bucket."""


def read(run):
    waves = [w for w in run.window_waves() if w.admitted]
    positions = sum(run.batch * w.prefill_bucket for w in waves)
    if not positions:
        return None
    prompts = sum(run.log.reqs[i].prompt_len for w in waves for i in w.admitted)
    return 100.0 * prompts / positions
