"""scale * median | sum of the lengths of the program's own spans named
exactly `span` (the `wasm/...` spans that `obs.timed` writes as
`jax.profiler.TraceAnnotation`s, with the recorder on or off) inside the
traced slice.

    stat "median"                 median length of the spans that lie
                                  wholly inside the slice
    stat "sum", per <counter>     summed length of what lies inside the
                                  slice (a span that crosses its border
                                  counts with the part inside, as the
                                  device's busy time does in
                                  trace_busy_per) / obs["counters"][per]
    host_only                     each length less the device's busy time
                                  inside it: what the host adds there

The events come from `Trace._host`, the (starts, ends, names) triple of
host-plane events that the reduced trace keeps for naming gaps: it is
the only place `reduce_trace.load` puts an event whose name does not
start with `bench/`.  A later benchmark PR should make that public (a
`Trace.host_spans(name)`), and this reader should then use it.

None where there is no trace, where the trace keeps no such triple, or
where no such span lies in the slice (the parent commit, whose program
writes none): the line then leaves the metric out.
"""

import statistics


def read(obs, span, stat, per=None, scale=1.0, host_only=False):
    if stat not in ("median", "sum"):
        raise ValueError(f"trace_program_span: unknown stat {stat!r}")
    trace = obs["trace"]
    host = getattr(trace, "_host", None)
    if host is None or (per is not None and not obs["counters"].get(per)):
        return None
    lo, hi = trace.window
    whole = stat == "median"
    lengths = []
    for a, b, name in zip(*host):
        if name != span or (whole and not lo <= a <= b <= hi):
            continue
        a, b = max(a, lo), min(b, hi)
        if b > a:
            lengths.append((b - a)
                           - (trace.busy_in(a, b) if host_only else 0.0))
    if not lengths:
        return None
    value = statistics.median(lengths) if whole else sum(lengths)
    return scale * value / (obs["counters"][per] if per else 1)
