"""Output tokens delivered by the waves that ended inside the window, over
the time from the window's first wave boundary to its last (host clock)."""


def read(run):
    waves = run.window_waves()
    if not waves:
        return None
    tokens = sum(n for w in waves for _gid, _j0, n in w.emitted)
    return tokens / (run.log.t_close - run.log.t_open)
