"""scale * counters[num] / counters[den]; `den` left out divides by 1.
A counter is what a driver wrote into obs["counters"]: a rise over the
measured window, or a size such as `window_s` or `lanes`."""


def read(obs, num, den=None, scale=1.0):
    c = obs["counters"]
    if num not in c or (den is not None and not c.get(den)):
        return None
    return scale * c[num] / (c[den] if den is not None else 1)
