"""scale * device busy seconds in the traced slice / counters[per], where
`per` counts something over the same slice (rounds, jobs)."""


def read(obs, per, scale=1.0):
    trace = obs["trace"]
    if trace is None or not obs["counters"].get(per):
        return None
    return scale * trace.busy_s / obs["counters"][per]
