"""scale * self time | count of the program's own span `span` inside the
`root` spans of the traced slice, over obs["counters"][per].

The `wasm/...` spans that `obs.timed` writes nest on the thread that
opens them (`wasm/batch/run` > `statuses` > `split` > `d2h`), so their
lengths cannot be added up.  This reader takes the nest apart: inside
each `root` span, clipped to the slice, every instant belongs to the
**innermost** `wasm/` span that covers it, which is the covering span
that started last.

    stat "self_idle"    seconds in which `span` is the innermost span
                        and the device is idle: the time less
                        `Trace.busy_in` of it, as `trace_program_span`'s
                        `host_only` takes it (a batch cell's trace has
                        one device plane with events).  With `span` = `root`
                        it is the idle time inside a run that no child
                        span names.  Over all names it sums to the idle
                        time inside the roots, and it reads every gap,
                        however short, under the program's own name for
                        it: an event that is not `wasm/` hides nothing.
    stat "count"        how many `span` events start inside the roots

A root that crosses the slice's border counts with the part inside, and
so do the spans in it.  The events come from `Trace._host`, as
`trace_program_span` takes them (and with its note on making that
public).

None where there is no trace or no such counter, where no `span` lies
inside a root (the parent commit, whose program opens none: the line
then leaves the metric out), and where two `root` spans overlap: spans
of several threads do not nest, and a slice like that (a mesh drive)
needs a reader of its own.
"""

PREFIX = "wasm/"


def _innermost(events, root):
    """Cut `root` = (start, end) at every border of the `events` inside
    it: [(start, end, name of the innermost event)], without a hole,
    since the root is one of the events."""
    lo, hi = root
    inside = sorted((max(a, lo), min(b, hi), a, b, name)
                    for a, b, name in events if a < hi and b > lo)
    cuts = sorted({t for e in inside for t in e[:2]})
    pieces, active, nxt = [], [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while nxt < len(inside) and inside[nxt][0] <= t0:
            active.append(inside[nxt])
            nxt += 1
        active = [e for e in active if e[1] > t0]
        # started last, by its own start and not the clipped one; of two
        # that start together, the one that ends first lies inside
        inner = max(active, key=lambda e: (e[2], -e[3]))
        pieces.append((t0, t1, inner[4]))
    return pieces


def read(obs, span, stat, per, scale=1.0, root="wasm/batch/run"):
    if stat not in ("self_idle", "count"):
        raise ValueError(f"trace_span_self: unknown stat {stat!r}")
    trace = obs["trace"]
    host = getattr(trace, "_host", None)
    if host is None or not obs["counters"].get(per):
        return None
    lo, hi = trace.window
    events = [(a, b, name) for a, b, name in zip(*host)
              if name.startswith(PREFIX) and a < hi and b > lo]
    roots = sorted((max(a, lo), min(b, hi)) for a, b, name in events
                   if name == root)
    if any(b > a2 for (_a, b), (a2, _b2) in zip(roots, roots[1:])):
        return None
    if stat == "count":
        values = [1 for r in roots for a, _b, name in events
                  if name == span and r[0] <= a < r[1]]
    else:
        values = [(t1 - t0) - trace.busy_in(t0, t1) for r in roots
                  for t0, t1, name in _innermost(events, r) if name == span]
    if not values:
        return None
    return scale * sum(values) / obs["counters"][per]
