"""scale * median|mean of the list obs["samples"][of], optionally over
the counter `over` (a share of a size, such as lanes)."""

import statistics


def read(obs, of, stat="median", over=None, scale=1.0):
    values = obs["samples"].get(of)
    if not values or (over is not None and not obs["counters"].get(over)):
        return None
    value = statistics.median(values) if stat == "median" \
        else statistics.fmean(values)
    return scale * value / (obs["counters"][over] if over else 1)
