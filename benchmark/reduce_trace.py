"""reduce_trace.py: a profiler trace (`*.xplane.pb`) -> what the per-layer
readers and the `breakdown` need, with `jax.profiler.ProfileData` alone.

What a v5e trace looks like (PR 22/25 chip runs): plane `/device:TPU:<n>`
holds the line `XLA Modules` (one event per launched program, named
`jit_<fn>(<fingerprint>)`) and the line `XLA Ops` (one event per executed
operation, named by its whole HLO text, hundreds of characters long).  The
plane `/host:CPU` holds one line per host thread; `jax.profiler.
TraceAnnotation` spans land there under their own names, on the same clock
as the device events.

The benchmark wraps its traced slice in the span `bench/slice` and its own
phases in `bench/<phase>` spans; everything here is clipped to the slice.
"""

import bisect
import re

import numpy as np

SLICE_SPAN = "bench/slice"
OWN_PREFIX = "bench/"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
NAMED_GAPS = 64      # only the longest gap pieces are looked up on the host


def short_op_name(hlo):
    """`%run.1 = (s32[1,16]{...}, ...) custom-call(...), ...` ->
    `%run.1 custom-call`: the text before ` = ` and the operation kind,
    never the whole HLO string."""
    lhs, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    rest = rest.lstrip()
    if rest.startswith("("):            # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:                               # a plain shape has no space in it
        rest = rest.partition(" ")[2]
    kind = re.match(r"\s*([A-Za-z0-9_.\-]+)", rest)
    return f"{lhs} {kind.group(1)}" if kind else lhs[:80]


def _merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _covered(merged, starts, a, b):
    """Length of [a, b] that the disjoint sorted intervals cover."""
    total = 0.0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(merged) and merged[i][0] < b:
        lo, hi = max(merged[i][0], a), min(merged[i][1], b)
        if hi > lo:
            total += hi - lo
        i += 1
    return total


class Trace:
    """One traced slice, in seconds on the profiler's clock.

    window      (start, end) of the slice
    busy        per device plane: disjoint sorted [start, end] in which a
                launched program (an `XLA Modules` event) ran
    ops         {short name: [count, seconds]} of the `XLA Ops` events
    modules     {program name: [count, seconds]}
    spans       {name: [(start, end), ...]} of the benchmark's own spans
    """

    def __init__(self, window, busy, ops, modules, spans, host):
        self.window = window
        self.busy = busy
        self._starts = [[iv[0] for iv in plane] for plane in busy]
        self.ops = ops
        self.modules = modules
        self.spans = spans
        self._host = host       # (starts, ends, names) numpy arrays / list

    @property
    def window_s(self):
        return self.window[1] - self.window[0]

    def busy_in(self, a, b):
        """Seconds of [a, b] in which an operation ran on the device,
        averaged over the device planes."""
        if not self.busy:
            return 0.0
        return sum(_covered(plane, st, a, b) for plane, st
                   in zip(self.busy, self._starts)) / len(self.busy)

    @property
    def busy_s(self):
        return self.busy_in(*self.window)

    def op_seconds(self, pattern):
        """Summed device time of the operations whose short name matches."""
        rx = re.compile(pattern)
        return sum(s for name, (_n, s) in self.ops.items()
                   if rx.search(name))

    def gaps(self):
        """Idle pieces of the slice on the first device plane, cut at the
        borders of the benchmark's own spans: [(start, end), ...]."""
        a, b = self.window
        plane = self.busy[0] if self.busy else []
        edges = sorted({t for spans in self.spans.values()
                        for s in spans for t in s if a < t < b})
        out, at = [], a
        for lo, hi in plane + [[b, b]]:
            lo = min(max(lo, a), b)
            if lo > at:
                cuts = [at] + [t for t in edges if at < t < lo] + [lo]
                out += list(zip(cuts, cuts[1:]))
            at = max(at, min(hi, b))
        return out

    def _name_gap(self, a, b):
        mid = (a + b) / 2
        own = None
        for name, spans in self.spans.items():
            for s, e in spans:
                if s <= mid <= e and (own is None or e - s < own[0]):
                    own = (e - s, name[len(OWN_PREFIX):])
        host = None
        if len(self._host[0]):
            starts, ends, names = self._host
            hit = np.flatnonzero((starts <= mid) & (ends >= mid))
            if hit.size:
                host = names[hit[np.argmin(ends[hit] - starts[hit])]]
        if own:
            return own[1] + (f"/{host}" if host else "")
        if self.spans:
            return "between_spans" + (f"/{host}" if host else "")
        return f"host:{host}" if host else "unattributed"

    def breakdown(self, top=10):
        """The contract's `breakdown`: the device operations that took
        most time, and the idle time by what the host was doing."""
        ops = sorted(([n, s] for n, (_c, s) in self.ops.items()),
                     key=lambda r: -r[1])[:top]
        pieces = sorted(self.gaps(), key=lambda g: g[0] - g[1])
        named = {}
        for i, (a, b) in enumerate(pieces):
            name = (self._name_gap(a, b) if i < NAMED_GAPS
                    else "(shorter gaps)")
            named[name] = named.get(name, 0.0) + (b - a)
        gaps = sorted(([n, s] for n, s in named.items()),
                      key=lambda r: -r[1])[:top]
        return {"device_ops": ops, "idle_gaps": gaps}


def load(path, slice_span=SLICE_SPAN):
    """Reduce one `.xplane.pb`.  Returns None where the trace has no
    device plane (a CPU rehearsal), so every reader finds nothing."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ns = 1e-9
    own, h_start, h_end, h_name = {}, [], [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                a = ev.start_ns * ns
                b = a + ev.duration_ns * ns
                if ev.name.startswith(OWN_PREFIX):
                    own.setdefault(ev.name, []).append((a, b))
                elif not ev.name.startswith("$"):   # "$..." are Python frames
                    h_start.append(a)
                    h_end.append(b)
                    h_name.append(ev.name)
    window = own.pop(slice_span, [None])[0]
    busy, ops, modules = [], {}, {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        intervals = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    a = ev.start_ns * ns
                    b = a + ev.duration_ns * ns
                    intervals.append((a, b))
                    if window is None or window[0] <= a <= window[1]:
                        rec = modules.setdefault(
                            ev.name.partition("(")[0], [0, 0.0])
                        rec[0] += 1
                        rec[1] += b - a
            elif line.name == OPS_LINE:
                short = {}
                for ev in line.events:
                    a = ev.start_ns * ns
                    if window is not None and \
                            not window[0] <= a <= window[1]:
                        continue
                    name = short.get(ev.name)
                    if name is None:
                        name = short[ev.name] = short_op_name(ev.name)
                    rec = ops.setdefault(name, [0, 0.0])
                    rec[0] += 1
                    rec[1] += ev.duration_ns * ns
        if intervals:
            busy.append(_merge(intervals))
    if not busy:
        return None
    if window is None:
        window = (min(p[0][0] for p in busy), max(p[-1][1] for p in busy))
    host = (np.asarray(h_start), np.asarray(h_end), h_name)
    return Trace(window, busy, ops, modules, own, host)
