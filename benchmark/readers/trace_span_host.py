"""scale * median over the benchmark's own spans named `span` of the
span's length less the device's busy time inside it: what the host adds
to each piece of work."""

import statistics


def read(obs, span, scale=1.0):
    trace = obs["trace"]
    if trace is None or not trace.spans.get(span):
        return None
    return scale * statistics.median(
        (b - a) - trace.busy_in(a, b) for a, b in trace.spans[span])
