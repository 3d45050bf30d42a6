"""100 * (1 - union of the launched programs' device time / slice), from
the traced slice; averaged over the device planes."""


def read(obs):
    trace = obs["trace"]
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
