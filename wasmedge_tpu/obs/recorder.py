"""Flight recorder: bounded ring of structured events for batch runs.

The batch engines execute thousands of lanes behind two or three layers
of scheduling (block scheduler -> kernel launches -> hostcall drains ->
supervisor retries); when a 4096-lane run misbehaves, aggregate G/s
numbers say nothing about *where* the time or the lanes went.  The
recorder is the single sink every layer reports into:

  timed(name)       a phase of the calling thread, as a context
                    manager: a jax.profiler.TraceAnnotation named
                    "wasm/<name>" on BOTH recorders (so a profiler
                    trace holds the program's phases with obs off), and
                    on the FlightRecorder a ring span with its parent
  span(name, t0)    a timed phase closed after the fact, ring only
                    (kernel launch, hostcall drain, checkpoint save,
                    and what crosses threads: request/<tenant>)
  instant(name)     a point incident (block split, quarantine, retry,
                    every FailureRecord)
  counter(name, v)  a sampled value series (live-lane occupancy,
                    hostcall queue depth)
  hostcall(kind, s) one tier-1 drain observation into the per-kind
                    latency histogram

Events land in a bounded deque (oldest dropped, drop count kept), so a
long-lived server can leave the recorder on without unbounded growth.
Exports: Chrome trace_event JSON (obs/trace.py — opens in Perfetto /
chrome://tracing) and Prometheus text format (obs/metrics.py).

Timing discipline: durations are differences of time.monotonic() (span
timing survives wall-clock steps); the wall clock is sampled ONCE at
recorder creation and event timestamps are reconstructed as
epoch + (mono - mono0), so the trace timeline is still wall-anchored.

Overhead discipline (guard-object pattern): when observability is off,
every instrumented component holds NULL_RECORDER, whose hooks are
no-ops and whose `enabled` is False — hot paths pay one attribute check
(`if obs.enabled:`) per *launch/serve round*, never per step.  A
timed() span is the one thing the guard object does not make a no-op:
it builds one TraceAnnotation and its wrapper; the annotation is an
inactive TraceMe (one flag test) while no profiler session runs.
"""

from __future__ import annotations

import bisect
import functools
import threading
import time
from collections import deque
from typing import Optional

SPAN_PREFIX = "wasm/"   # the program's spans in a jax.profiler trace

# Log-spaced latency bucket upper bounds (seconds) for the hostcall
# drain histograms; the +Inf bucket is implicit.  10us..30s covers
# in-process NumPy drains through serves that wait on device transfers.
LATENCY_BUCKETS = (
    1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2,
    1e-1, 3e-1, 1.0, 3.0, 10.0, 30.0,
)


class LatencyHistogram:
    """Fixed-bucket latency histogram (Prometheus-shaped: per-bucket
    counts + total observation count + sum of observed seconds), with a
    drained-lane tally on the side (one drain call serves many lanes)."""

    __slots__ = ("counts", "count", "sum_s", "lanes")

    def __init__(self):
        self.counts = [0] * len(LATENCY_BUCKETS)
        self.count = 0
        self.sum_s = 0.0
        self.lanes = 0

    def observe(self, dur_s: float, lanes: int = 1):
        i = bisect.bisect_left(LATENCY_BUCKETS, dur_s)
        if i < len(self.counts):
            self.counts[i] += 1
        self.count += 1
        self.sum_s += float(dur_s)
        self.lanes += int(lanes)

    def cumulative(self):
        """[(le_bound, cumulative_count)] for Prometheus rendering."""
        out, acc = [], 0
        for le, c in zip(LATENCY_BUCKETS, self.counts):
            acc += c
            out.append((le, acc))
        return out


class _NullSpan:
    """What timed() returns where jax cannot be imported."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()


@functools.cache
def _trace_annotation():
    """jax.profiler.TraceAnnotation, imported once, on the first span;
    None where jax cannot be imported (the scalar-only install)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class _ProfilerSpan:
    """NullRecorder.timed(): the profiler's annotation and no state."""

    __slots__ = ("_ann",)

    def __init__(self, ann):
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def set(self, **args):
        """Args known only at the end of the span: a TraceAnnotation
        took its own when it was entered, so these reach the ring event
        alone, and nothing here."""


class NullRecorder:
    """Guard object for disabled observability: every hook is a no-op.

    Instrumented code never branches per event on "is obs on?" — it
    calls the recorder unconditionally at coarse seams (per launch /
    serve / split), and guards only the *extra data gathering* (device
    reads like occupancy) behind `if obs.enabled:`.  now() avoids even
    the clock syscall."""

    enabled = False

    def now(self) -> float:
        return 0.0

    def span(self, name, t0, cat="", track="main", **args):
        pass

    def timed(self, name, cat="", track=None, **args):
        ann = _trace_annotation()
        if ann is None:
            return _NULL_SPAN
        return _ProfilerSpan(ann(SPAN_PREFIX + name, **args))

    def instant(self, name, cat="", track="main", **args):
        pass

    def counter(self, name, value, track="counters"):
        pass

    def hostcall(self, kind, dur_s, lanes=1, vectorized=True):
        pass

    def observe_admission(self, dur_s):
        pass

    def observe_swap(self, direction, dur_s):
        pass

    def observe_convergence(self, unique_pcs, largest_group):
        pass

    def observe_compaction(self, dur_s):
        pass

    def add_tier_seconds(self, tier, dur_s):
        pass

    def add_opcode_counts(self, counts):
        pass

    def add_fused_counts(self, dispatches, retired_fused, retired_total):
        pass

    def set_memfuse_static(self, section):
        pass

    def set_dispatch_static(self, expected, deepest):
        pass

    def set_memory_static(self, static):
        pass

    def add_window_counts(self, fills, writebacks, accesses):
        pass

    def set_superblock_static(self, edges):
        pass

    def set_block_shapes_static(self, counts):
        pass

    def set_shuffle_static(self, sites):
        pass

    def set_donation_static(self, planes):
        pass

    def add_dispatch_counts(self, dispatches):
        pass

    def add_softfloat_counts(self, ops):
        pass

    def add_simd_counts(self, ops):
        pass

    def add_indirect_counts(self, ops):
        pass

    def add_hostcall_counts(self, rounds, calls, vectorized, out_bytes):
        pass

    def add_split_counts(self, splits=0, launches=0, rechecks=0,
                         careful_steps=0, surgery_programs=0,
                         snap_restored=0, snap_commits=0,
                         d2h_transfers=0, h2d_transfers=0,
                         programs_enqueued=0):
        pass

    def add_tierup_counts(self, dispatches, retired_comp, retired_total):
        pass

    def set_tierup_static(self, report):
        pass

    def failure(self, rec):
        pass


NULL_RECORDER = NullRecorder()


class _Span(_ProfilerSpan):
    """FlightRecorder.timed(): the annotation, and a ring span that
    names as `parent` the span open on this thread when it was entered.
    Without a track of its own it lands on its parent's, so the Chrome
    export nests it there."""

    __slots__ = ("_rec", "name", "_cat", "track", "_args", "_t0",
                 "_parent")

    def __init__(self, rec, ann, name, cat, track, args):
        self._rec = rec
        self._ann = ann
        self.name = name
        self._cat = cat
        self.track = track
        self._args = args

    def __enter__(self):
        stack = self._rec._open_spans()
        parent = stack[-1] if stack else None
        self._parent = parent and parent.name
        if self.track is None:
            self.track = parent.track if parent else "phases"
        stack.append(self)
        self._t0 = self._rec.now()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._rec._open_spans().pop()
        self._rec.span(self.name, self._t0, cat=self._cat,
                       track=self.track, parent=self._parent,
                       **self._args)
        return False

    def set(self, **args):
        self._args.update(args)


class FlightRecorder:
    """Bounded-ring event recorder (see module docstring).

    Events are plain dicts {name, ph, cat, ts, dur, track, args}: ph is
    the Chrome trace_event phase ("X" complete span, "i" instant, "C"
    counter), ts/dur are SECONDS (the trace exporter scales to us),
    track is a logical lane mapped to a trace tid at export time."""

    enabled = True

    def __init__(self, capacity: int = 65536):
        self.capacity = int(capacity)
        self.events = deque(maxlen=self.capacity)
        self.dropped = 0
        self._epoch = time.time()       # wall anchor, sampled once
        self._mono0 = time.monotonic()  # duration clock zero
        self._open = threading.local()  # .stack: this thread's open spans
        self.hostcalls = {}        # kind -> LatencyHistogram
        self.admission = LatencyHistogram()  # serve submit -> install
        self.hv_swaps = {}         # "in"/"out" -> LatencyHistogram
        # per-round convergence gauges (batch/engine.py run_from_state)
        # + lane-compaction counters (batch/compact.py): last-observed
        # values for the Prometheus gauges, counts for the totals
        self.convergence = {"rounds": 0, "unique_pcs": 0,
                            "largest_group": 1.0}
        self.compactions_total = 0
        self.compaction = LatencyHistogram()
        self.tier_seconds = {}     # tier -> accumulated seconds
        self.failure_counts = {}   # fault_class -> count
        self.opcode_counts = None  # np.int64 [NUM_OPCODES+3] when folded
        # superinstruction-fusion counters folded from the device
        # fu_ctr plane (batch/engine.py _fold_fuse_ctr)
        self.fused_counts = {"dispatches": 0, "retired_fused": 0,
                             "retired_total": 0}
        # memory-run fusion planning statics (r19): licensed vs
        # reverted (license-refused) load/store sites + realized runs,
        # set once per plan by BatchEngine._plan_fusion
        self.memfuse_static = None
        # dispatch-tree depths of the newest Pallas kernel build
        self.dispatch_static = None
        # the newest Pallas kernel's mem_static (how it holds linear
        # memory), and the hbm_window kernel's DMA counts folded after
        # each run
        self.memory_static = None
        self.window_counts = {"fills": 0, "writebacks": 0, "accesses": 0}
        # forward edges the newest Pallas kernel's blocks run through
        # ({"jump", "guard_tail"}), and the handlers its loop dispatched
        self.superblock_static = None
        self.pallas_dispatches = 0
        # its block shapes ({"kept", "wanted"})
        self.block_shapes_static = None
        # its image's i8x16.shuffle slots by lowering ({"word",
        # "dynamic"}), where the image holds one
        self.shuffle_static = None
        # the plane arguments its launch donates (None before a kernel)
        self.donated_planes = None
        # the binary64 routines those kernels ran (softfloat.py)
        self.softfloat_ops = 0
        # and the instructions of a v128 class they ran
        self.simd_ops = 0
        # and the br_table and call_indirect they ran
        self.indirect_ops = 0
        # what the Pallas block serve drained, folded after each run
        # that parked: park, drain and re-arm cycles, lanes drained,
        # those a vectorised implementation served, bytes the calls
        # handed to an fd
        self.hostcall_counts = {"rounds": 0, "calls": 0, "vectorized": 0,
                                "out_bytes": 0}
        # what the block scheduler did, folded after each run: blocks
        # split, launches of the optimistic kernel, rounds of the
        # careful one, the block-steps those rounds retired, and the
        # compiled programs of block surgery, the children given the
        # full snapshot interval back, and the periodic commits the
        # launches' intervals imply; and its crossings of the host link
        # (batch/pallas_engine.py HostLink): blocking downloads, uploads,
        # calls of a compiled program
        self.split_counts = {"splits": 0, "launches": 0, "rechecks": 0,
                             "careful_steps": 0, "surgery_programs": 0,
                             "snap_restored": 0, "snap_commits": 0,
                             "d2h_transfers": 0, "h2d_transfers": 0,
                             "programs_enqueued": 0}
        # compiled-function tier counters folded from the device
        # tu_ctr plane (batch/engine.py _fold_tierup_ctr) + the
        # promotion report set once per plan by _plan_tierup (r20)
        self.tierup_counts = {"dispatches": 0, "retired_comp": 0,
                              "retired_total": 0}
        self.tierup_static = None

    # The recorder is a shared sink, not configuration data: components
    # deepcopy their Configure (gas bridging, scalar reruns) and must
    # keep reporting into the SAME ring, not a silent private copy.
    def __deepcopy__(self, memo):
        return self

    def __copy__(self):
        return self

    # -- clock -------------------------------------------------------------
    def now(self) -> float:
        return time.monotonic()

    def _ts(self, mono: float) -> float:
        """Wall timestamp (seconds since epoch) for a monotonic stamp."""
        return self._epoch + (mono - self._mono0)

    # -- event hooks -------------------------------------------------------
    def _push(self, ev: dict):
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)

    def span(self, name, t0, cat="", track="main", **args):
        """Record a completed span begun at monotonic stamp `t0`."""
        t1 = time.monotonic()
        self._push({"name": name, "ph": "X", "cat": cat,
                    "ts": self._ts(t0), "dur": max(t1 - t0, 0.0),
                    "track": track, "args": args})

    def timed(self, name, cat="", track=None, **args):
        ann = _trace_annotation()
        return _Span(self, ann(SPAN_PREFIX + name, **args) if ann
                     else _NULL_SPAN, name, cat, track, args)

    def _open_spans(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def instant(self, name, cat="", track="main", **args):
        self._push({"name": name, "ph": "i", "cat": cat,
                    "ts": self._ts(time.monotonic()), "dur": 0.0,
                    "track": track, "args": args})

    def counter(self, name, value, track="counters"):
        self._push({"name": name, "ph": "C", "cat": "counter",
                    "ts": self._ts(time.monotonic()), "dur": 0.0,
                    "track": track, "args": {name: value}})

    # -- aggregates --------------------------------------------------------
    def hostcall(self, kind, dur_s, lanes=1, vectorized=True):
        """One tier-1 drain observation: histogram + trace span on the
        hostcall track."""
        h = self.hostcalls.get(kind)
        if h is None:
            h = self.hostcalls[kind] = LatencyHistogram()
        h.observe(dur_s, lanes)
        t1 = time.monotonic()
        self._push({"name": f"drain/{kind}", "ph": "X", "cat": "hostcall",
                    "ts": self._ts(t1 - dur_s), "dur": dur_s,
                    "track": "hostcalls",
                    "args": {"lanes": int(lanes),
                             "vectorized": bool(vectorized)}})

    def observe_admission(self, dur_s):
        """One serving-layer admission observation: queue wait from
        submit() to lane install (wasmedge_tpu/serve/)."""
        self.admission.observe(dur_s)

    def observe_swap(self, direction, dur_s):
        """One lane-virtualization swap observation (wasmedge_tpu/hv/):
        serialize+store for "out", fetch+install for "in"."""
        h = self.hv_swaps.get(direction)
        if h is None:
            h = self.hv_swaps[direction] = LatencyHistogram()
        h.observe(dur_s)

    def observe_convergence(self, unique_pcs, largest_group):
        """One launch-round convergence observation: distinct active
        pcs + largest convergent group fraction among live lanes
        (batch/engine.py pulls the pc mirror once per launch when obs
        is on).  Last values back the Prometheus gauges; counter
        events land on the ring for the trace."""
        self.convergence["rounds"] += 1
        self.convergence["unique_pcs"] = int(unique_pcs)
        self.convergence["largest_group"] = float(largest_group)
        self.counter("convergence_unique_pcs", int(unique_pcs))
        self.counter("convergence_largest_group",
                     round(float(largest_group), 4))

    def observe_compaction(self, dur_s):
        """One fired lane compaction (batch/compact.py): latency
        histogram + total, rendered as wasmedge_compactions_total and
        wasmedge_compaction_latency_seconds."""
        self.compactions_total += 1
        self.compaction.observe(dur_s)

    def add_tier_seconds(self, tier, dur_s):
        self.tier_seconds[tier] = \
            self.tier_seconds.get(tier, 0.0) + float(dur_s)

    def add_fused_counts(self, dispatches, retired_fused, retired_total):
        """Fold the device fusion counters (fused dispatch cells
        executed / instructions retired through them / total retired
        while the plane was live — batch/engine.py _fold_fuse_ctr)."""
        self.fused_counts["dispatches"] += int(dispatches)
        self.fused_counts["retired_fused"] += int(retired_fused)
        self.fused_counts["retired_total"] += int(retired_total)

    def set_memfuse_static(self, section):
        """Record the memory-run fusion planning statics (the
        plan_fusion report's "memory" section: licensed vs reverted
        sites, realized runs/cells) for the Prometheus export."""
        self.memfuse_static = dict(section)

    def set_dispatch_static(self, expected, deepest):
        """Record the shape of the dispatch tree the newest Pallas
        kernel was built with (batch/pallas_engine.py
        plan_dispatch_tree): expected depth over the static entry
        weights and the deepest leaf, in branches walked."""
        self.dispatch_static = {"expected": float(expected),
                                "max": int(deepest)}

    def set_memory_static(self, static):
        """Point at the newest Pallas kernel's own record of how it
        holds linear memory (PallasUniformEngine.mem_static, which
        says what the keys are)."""
        self.memory_static = static

    def add_window_counts(self, fills, writebacks, accesses):
        """Fold the hbm_window kernel's counts of one run (window fills
        and dirty write-backs, and the loads and stores it resolved
        against the window, summed over blocks and launches by
        batch/scheduler.py)."""
        self.window_counts["fills"] += int(fills)
        self.window_counts["writebacks"] += int(writebacks)
        self.window_counts["accesses"] += int(accesses)

    def set_superblock_static(self, edges):
        """Record the forward edges the newest Pallas kernel's blocks
        run through instead of ending at (batch/pallas_engine.py
        fuse_blocks): absorbed `br`s and guards with a tail."""
        self.superblock_static = dict(edges)

    def set_block_shapes_static(self, counts):
        """Record the newest Pallas kernel's block shapes
        (batch/pallas_engine.py fuse_blocks_counted): "kept", the shapes
        its plane holds, and "wanted", the distinct shapes its image's
        heads want; more wanted than kept where MAX_BLOCK_SHAPES made
        fuse_blocks keep those of the deepest loops."""
        self.block_shapes_static = dict(counts)

    def set_shuffle_static(self, sites):
        """Record the `i8x16.shuffle` slots of the newest Pallas
        kernel's image by lowering (batch/pallas_engine.py
        shuffle_sites): "word" run as row moves inside a fused block,
        "dynamic" fetch their mask at run time."""
        self.shuffle_static = dict(sites)

    def set_donation_static(self, planes):
        """Record how many of the newest Pallas kernel's plane
        arguments its launch donates, so that XLA writes them in place
        (batch/pallas_engine.py donated_planes)."""
        self.donated_planes = int(planes)

    def add_dispatch_counts(self, dispatches):
        """Fold the handlers the Pallas kernels dispatched in one run
        (ctrl column 13, summed over blocks and launches by
        batch/scheduler.py)."""
        self.pallas_dispatches += int(dispatches)

    def add_softfloat_counts(self, ops):
        """Fold the binary64 routines of batch/softfloat.py the Pallas
        kernels ran in one run, a lane-block step each (ctrl column
        15, which only a kernel whose image holds a binary64 ALU op
        writes; summed by batch/scheduler.py)."""
        self.softfloat_ops += int(ops)

    def add_simd_counts(self, ops):
        """Fold the instructions of a v128 class (CLS_VCONST ..
        CLS_VSTORE) the Pallas kernels ran in one run, a lane-block
        step each (ctrl column 16, which only the rows of a kernel
        whose image has v128 hold; summed by batch/scheduler.py)."""
        self.simd_ops += int(ops)

    def add_indirect_counts(self, ops):
        """Fold the br_table and call_indirect the Pallas kernels ran in
        one run, a lane-block step each, at their handlers and as fused
        blocks' terminals (the last ctrl column, which only a kernel
        whose image holds one of the two writes; summed by
        batch/scheduler.py)."""
        self.indirect_ops += int(ops)

    def add_hostcall_counts(self, rounds, calls, vectorized, out_bytes):
        """Fold what one run's hostcall serves on the Pallas path
        counted (batch/pallas_engine.py `_serve_hostcalls_finish`, out
        of the run's `hostcall_stats`): rounds of park, drain and
        re-arm, lanes drained, those of them a tier-1 vectorised
        implementation served, and the bytes the calls handed to an fd
        (the WASI environ's own count)."""
        hc = self.hostcall_counts
        hc["rounds"] += int(rounds)
        hc["calls"] += int(calls)
        hc["vectorized"] += int(vectorized)
        hc["out_bytes"] += int(out_bytes)

    def add_split_counts(self, splits=0, launches=0, rechecks=0,
                         careful_steps=0, surgery_programs=0,
                         snap_restored=0, snap_commits=0,
                         d2h_transfers=0, h2d_transfers=0,
                         programs_enqueued=0):
        """Fold what the block scheduler did in one run
        (batch/scheduler.py): blocks it split, launches of the
        optimistic kernel, rounds of the careful kernel after a
        rollback, the block-steps those rounds retired, the compiled
        programs of block surgery (one that gathers a child's columns,
        one that sets them into a free slot), the children installed
        with the full snapshot interval in place of a halved one, the
        periodic commits the launches' intervals imply, and what its
        HostLink counted: blocking downloads, uploads, calls of a
        compiled program.  The scheduler folds the two snapshot counts,
        the engine the rest."""
        for key, n in (("splits", splits), ("launches", launches),
                       ("rechecks", rechecks),
                       ("careful_steps", careful_steps),
                       ("surgery_programs", surgery_programs),
                       ("snap_restored", snap_restored),
                       ("snap_commits", snap_commits),
                       ("d2h_transfers", d2h_transfers),
                       ("h2d_transfers", h2d_transfers),
                       ("programs_enqueued", programs_enqueued)):
            self.split_counts[key] += int(n)

    def add_tierup_counts(self, dispatches, retired_comp, retired_total):
        """Fold the device tier-up counters (compiled-function bodies
        dispatched / instructions retired through them / total retired
        while the plane was live — batch/engine.py _fold_tierup_ctr)."""
        self.tierup_counts["dispatches"] += int(dispatches)
        self.tierup_counts["retired_comp"] += int(retired_comp)
        self.tierup_counts["retired_total"] += int(retired_total)

    def set_tierup_static(self, report):
        """Record the tier-up planning report (batch/tierup.py
        plan_tierup: promoted functions, refusal reasons, device-loop
        counts) for the Prometheus export."""
        self.tierup_static = dict(report)

    def add_opcode_counts(self, counts):
        """Fold a device-side opcode histogram (index = original opcode
        id, the Statistics cost_table domain) into the run aggregate."""
        import numpy as np

        counts = np.asarray(counts, np.int64)
        if self.opcode_counts is None:
            self.opcode_counts = counts.copy()
        else:
            n = max(len(self.opcode_counts), len(counts))
            if len(self.opcode_counts) < n:
                self.opcode_counts = np.pad(
                    self.opcode_counts, (0, n - len(self.opcode_counts)))
            self.opcode_counts[:len(counts)] += counts

    def failure(self, rec):
        """Mirror one FailureRecord as an instant event + taxonomy count."""
        fc = getattr(rec, "fault_class", "unknown")
        self.failure_counts[fc] = self.failure_counts.get(fc, 0) + 1
        self._push({"name": f"failure/{fc}", "ph": "i", "cat": "failure",
                    "ts": self._ts(time.monotonic()), "dur": 0.0,
                    "track": "supervisor", "args": rec.asdict()})

    # -- queries (tests / exporters) ---------------------------------------
    def event_names(self):
        return [e["name"] for e in self.events]


def recorder_of(conf) -> "FlightRecorder | NullRecorder":
    """The recorder for a Configure: NULL_RECORDER unless conf.obs is
    enabled, in which case one FlightRecorder is lazily created and
    shared by every component holding (a copy of) that Configure."""
    obs_conf = getattr(conf, "obs", None)
    if obs_conf is None or not getattr(obs_conf, "enabled", False):
        return NULL_RECORDER
    rec = getattr(obs_conf, "_recorder", None)
    if rec is None:
        rec = FlightRecorder(capacity=obs_conf.ring_capacity)
        obs_conf._recorder = rec
    return rec
