"""Deterministic fault injection for supervised batch execution.

The supervisor (batch/supervisor.py) exposes seeded injection seams —
`"launch"` before every kernel dispatch, `"serve"` before every tier-1
hostcall drain (both armed through `BatchEngine._fault_hook` inside
`run_from_state`), `"checkpoint_save"` / `"checkpoint_load"` around the
snapshot lineage.  A `FaultInjector` counts arrivals at each seam and
raises an `InjectedFault` at the configured occurrence indices, so a test
can reproduce "the 3rd launch dies", "the first WASI drain raises", or
"the newest checkpoint is corrupt" bit-for-bit every run.

The mesh supervisor (parallel/supervisor.py) adds device-level seams:
`"device_launch"` / `"device_serve"` fire per device-engine chunk with
`device=<index>` in the context, and `"mesh_checkpoint_save"` brackets a
coordinated mesh snapshot.  Arrivals at a shared seam interleave across
device threads in scheduling order, so device-targeted faults should use
`Fault.match` (e.g. `match={"device": 2}`) — matched faults count their
OWN arrivals, making "device 2's first launch" deterministic regardless
of thread interleaving.  `fire` is locked: concurrent device threads
never corrupt the arrival counters.

The gateway (gateway/service.py, gateway/http.py) adds the tier above
the engines — r13's chaos surface:
  - `"gateway_register"`   at the top of a registration transaction
  - `"generation_build"`   before a serving generation's engine build
                           (injected -> atomic rollback to the prior
                           generation, retryable 503)
  - `"generation_swap"`    before the submit-pointer swap (same
                           rollback contract; never half-swapped)
  - `"journal_write"`      before every durable manifest/journal write
                           (gateway/durable.py; a submit whose journal
                           write faults is rejected retryably — the
                           202 id is never issued undurably)
  - `"http_response_delay"` / `"http_response_drop"` at the HTTP edge:
                           these are ABSORBED by the handler (delay
                           sleeps ~50ms before the bytes; drop closes
                           the connection with no response), modelling
                           a slow/flaky network rather than a server
                           exception.
A gateway process kill/restart is NOT a seam — it is orchestrated by
the test that drives it (tests/test_gateway_durability.py:
Gateway.kill() then a fresh GatewayService(resume=True) over the same
state dir), with the seams
above supplying the weather around it.

The lane-virtualization layer (wasmedge_tpu/hv/) adds the swap seams
— r14's oversubscription surface:
  - `"swap_out"`          before a victim lane's columns serialize
                          (ctx: lane, id).  A faulted swap-out leaves
                          the lane RESIDENT and retries at the next
                          launch boundary — no state moves.
  - `"swap_in"`           before a swapped virtual lane reinstalls
                          onto a physical lane (ctx: lane, id).  A
                          faulted swap-in re-queues the virtual lane
                          without losing it; the target lane stays
                          free.
  - `"swap_store_write"`  inside SwapStore.put, before any bytes move
                          (ctx: key, nbytes) — an injected store
                          failure surfaces as a faulted swap-out (the
                          crash-atomic writer guarantees no partial
                          blob either way).

The fleet federation layer (wasmedge_tpu/fleet/) adds the peer seams
— r16's multi-host chaos surface:
  - `"peer_send"`       in PeerClient before every outbound peer
                        request (ctx: src, dst, route in {heartbeat,
                        journal, execute, migrate, modules,
                        requests...}).  An injected fault is a severed
                        outbound link: the sender sees
                        PeerUnreachable, the receiver sees nothing.
  - `"peer_recv"`       in the /v1/fleet/* handlers on receipt (ctx:
                        src, dst, route).  An injected fault is a
                        message lost at the receiver: the sender gets
                        a 5xx it counts as unreachable, and the
                        receiver processes nothing.
  - `"peer_heartbeat"`  in the heartbeat loop before each liveness
                        probe (ctx: src, dst) — the cheap way to
                        starve ONE peer's probes without touching the
                        data plane.
  `partition_schedule()` composes these into deterministic network
  partitions: directional link cuts between named peers over a window
  of arrivals, healing when the window passes.  A gateway process
  kill/restart is still driver-orchestrated (tests/test_fleet.py),
  with these seams supplying the weather.

The elastic-fleet layer (r21) adds the churn seams:
  - `"membership_gossip"`  in FleetController before a piggybacked
                           membership view MERGES (ctx: src, dst,
                           epoch).  An injected fault drops JUST that
                           gossip message — the heartbeat it rode
                           still counts for liveness, and the next
                           exchange re-gossips the view (the CRDT
                           merge converges regardless of which
                           messages are lost).
  - `"reshard_install"`    in BatchServer.reshard before the new-mesh
                           install mutates anything (ctx: old_devices,
                           new_devices, old_lanes, lanes).  An
                           injected fault rolls the server back onto
                           the OLD mesh with every resident lane
                           intact — the reshard fails closed.
  `churn_schedule()` composes these into the seeded join/leave/reshard
  weather tests/test_elastic.py arms.

The effects layer (r23, wasmedge_tpu/effects/) adds the suspend/resume
seams:
  - `"session_park"`       in EffectsRuntime.park_boundary before a
                           TRAP_PARKED lane serializes out (ctx: lane,
                           id).  A faulted park leaves the lane
                           RESIDENT — its trap returns to
                           TRAP_HOSTCALL and the intercept re-marks it
                           at the next boundary; no state moves.
  - `"session_wake"`       in EffectsRuntime.process_wakes before a
                           wake applies (ctx: id, source in {http,
                           timer}).  A faulted HTTP wake RE-QUEUES
                           (payload intact); a faulted timer wake
                           re-arms the timer entry — either way the
                           session is never lost and the wake applies
                           at a later boundary.

The imagestore layer (r22) adds the cold-start seams:
  - `"cache_read"`         in CompileCache.load before a persistent
                           compile-cache entry is consulted (ctx:
                           sha).  An injected fault — like a corrupt
                           or truncated entry — is a MISS: the
                           registration lowers fresh and re-stores;
                           wrong code is never served.
  - `"snapshot_install"`   in imagestore.decode_overlay before a
                           module's pre-initialized snapshot becomes a
                           generation's init overlay (ctx: module,
                           key).  An injected fault — like a SwapStore
                           integrity failure — drops the overlay for
                           that generation: the module's requests
                           admit through plain template init (the r21
                           path), bit-identical results, just colder.

The integrity layer (r24, wasmedge_tpu/integrity/) adds the silent-
corruption seams.  Unlike every seam above, the `corrupt_*` family is
a BIT-FLIP seam driven by `FaultInjector.flip(point, obj, **ctx)` —
it never raises; it returns `obj` with exactly one seeded bit flipped
when an armed `BitFlip` covers the arrival, modelling SDC that the
runtime must DETECT rather than an error it gets told about:
  - `"corrupt_plane"`   in BatchEngine.run_from_state after a launch
                        slice lands, before the shadow auditor's
                        post-slice gather (ctx: total).  One bit of
                        one lane column of one BatchState plane flips
                        on device — the audit must catch it, roll
                        back, and attribute the device.
  - `"corrupt_swap"`    in SwapStore.put after the blob is stored
                        (ctx: key, nbytes).  The AT-REST copy rots
                        (memory and disk mirror both); `get` detects
                        on read, the scrubber detects BEFORE a wake
                        needs it and repairs from a healthy mirror or
                        a fleet peer replica.
  - `"corrupt_cache"`   in CompileCache.store after the entry lands
                        (ctx: sha).  The stored WTIC envelope rots;
                        `load` detects via the embedded digest (miss,
                        fresh lower), the scrubber detects early and
                        repairs from a peer or evicts.
  Checkpoint-shard rot has no runtime seam — drive `flip_file(path)`
  against a lineage member like `corrupt_checkpoint` does; the
  scrubber's sha256 sidecar verification detects it.
The raising seams that pair with the scrubber/auditor:
  - `"audit_compare"`   in ShadowAuditor.post before the reference
                        replay/compare (ctx: boundary, lanes).  An
                        injected fault models the audit INFRA failing
                        — the audit voids (counted as an error),
                        execution continues; it is never reported as
                        a divergence.
  - `"scrub_read"`      in Scrubber before each entry's local read
                        (ctx: kind, key).  An injected fault is an
                        unreadable local copy: the scrubber goes down
                        the same repair path a hash mismatch takes.

Fault classes covered by the tier-1 suites (ISSUE 2 + ISSUE 5):
  - launch-time device error       Fault(point="launch", ...)
  - mid-serve host exception       Fault(point="serve", ...)
  - corrupted/truncated checkpoint corrupt_checkpoint(path, ...) via
                                   Fault.before, or a "checkpoint_load"
                                   fault
  - runaway / poison lane          build_selective_runaway() +
                                   SupervisorConfigure.lane_step_cap, or
                                   a lane-attributed Fault(lanes=(k,))
  - per-device mesh failure        Fault(point="device_launch",
                                   match={"device": k}, ...)
  - gateway swap/journal/edge      the gateway-tier seams above
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Optional, Sequence, Tuple

import numpy as np


class InjectedFault(RuntimeError):
    """The exception a Fault raises; carries the seam name and an
    optional lane attribution the supervisor's poison-quarantine path
    consumes (real device errors carry no attribution — whole-batch
    retry is the fallback)."""

    def __init__(self, point: str, index: int, lanes: Tuple[int, ...] = (),
                 message: str = ""):
        super().__init__(
            message or f"injected fault at {point}[{index}]"
            + (f" lanes={list(lanes)}" if lanes else ""))
        self.point = point
        self.index = index
        self.lanes = tuple(int(x) for x in lanes)


@dataclasses.dataclass
class Fault:
    """One armed fault: fire on arrivals [at, at + times) at `point`."""

    point: str                 # "launch" | "serve" | "checkpoint_save" |
    #                            "checkpoint_load" | "device_launch" |
    #                            "device_serve" | "mesh_checkpoint_save" |
    #                            "gateway_register" | "generation_build" |
    #                            "generation_swap" | "journal_write" |
    #                            "http_response_delay" |
    #                            "http_response_drop" | "swap_out" |
    #                            "swap_in" | "swap_store_write" |
    #                            "peer_send" | "peer_recv" |
    #                            "peer_heartbeat" |
    #                            "membership_gossip" | "reshard_install" |
    #                            "cache_read" | "snapshot_install" |
    #                            "session_park" | "session_wake"
    at: int = 0                # 0-based arrival index at that seam
    times: int = 1             # consecutive arrivals that fault
    lanes: Tuple[int, ...] = ()  # lane attribution (poison quarantine)
    message: str = ""
    # runs just before raising — e.g. corrupt the newest checkpoint file
    # so the restore path exercises the lineage fallback
    before: Optional[Callable[..., None]] = None
    # custom exception factory (ctx dict -> exception); default
    # InjectedFault
    exc: Optional[Callable[..., BaseException]] = None
    # context filter: only arrivals whose fire() ctx is a superset of
    # this dict are considered, and `at` then indexes the MATCHED
    # arrivals (per-fault counter) instead of all arrivals at the seam —
    # "device 2's first launch" stays deterministic under the mesh
    # drive's thread interleaving
    match: Optional[dict] = None


@dataclasses.dataclass
class BitFlip:
    """One armed bit flip: on arrivals [at, at + times) at a
    `corrupt_*` seam, `FaultInjector.flip` returns the seam's object
    with exactly one seeded bit flipped (it never raises).  For
    `corrupt_plane` the object is a BatchState; `plane`/`lane`/`bit`
    pin the target (None = seeded pick; the default plane pool avoids
    control planes like trap/pc so the corruption is plausible data,
    not an instant crash).  For byte seams the object is the stored
    payload."""

    point: str                   # "corrupt_plane" | "corrupt_swap" |
    #                              "corrupt_cache"
    at: int = 0
    times: int = 1
    seed: int = 0
    plane: Optional[str] = None  # corrupt_plane: BatchState field name
    lane: Optional[int] = None   # corrupt_plane: lane column
    bit: Optional[int] = None    # bit index within the chosen byte
    match: Optional[dict] = None  # same matched-counter contract as Fault


# corrupt_plane's seeded pick draws from data planes: flipping pc/trap/
# sp would typically crash the lane outright (a detected failure, not
# SDC), while a rotted stack cell or memory word is exactly the wrong-
# but-plausible result the shadow audit exists to catch.
_FLIP_PLANE_POOL = ("stack_lo", "stack_hi", "mem", "glob_lo", "glob_hi")


def flip_bit_bytes(data: bytes, seed: int = 0,
                   bit: Optional[int] = None) -> bytes:
    """Return `data` with one seeded bit flipped."""
    buf = bytearray(data)
    if not buf:
        return bytes(buf)
    rng = np.random.RandomState(int(seed) & 0x7FFFFFFF)
    pos = int(rng.randint(len(buf)))
    b = int(bit) if bit is not None else int(rng.randint(8))
    buf[pos] ^= 1 << b
    return bytes(buf)


def flip_file(path, seed: int = 0, bit: Optional[int] = None):
    """Flip one seeded bit of a file in place — at-rest rot for
    checkpoint shards / cache entries.  Deliberately NOT atomic: rot
    does not fsync."""
    with open(path, "rb") as f:
        data = f.read()
    with open(path, "wb") as f:
        f.write(flip_bit_bytes(data, seed=seed, bit=bit))


def _flip_batch_state(state, f: BitFlip, idx: int, ctx: dict):
    """Flip one bit of one lane column of one plane; returns a new
    state with that plane re-deviced (respecting its sharding)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState((int(f.seed) + idx) & 0x7FFFFFFF)
    lanes = ctx.get("lanes")
    if lanes is None:
        lanes = int(np.asarray(state.pc).shape[-1])
    names = [n for n in state._fields
             if getattr(state, n) is not None
             and getattr(getattr(state, n), "ndim", 0)
             and getattr(state, n).shape[-1] == lanes]
    if f.plane is not None:
        name = f.plane
        if name not in names:
            return state
    else:
        pool = [n for n in _FLIP_PLANE_POOL if n in names] or names
        name = pool[int(rng.randint(len(pool)))]
    plane = getattr(state, name)
    mirror = np.ascontiguousarray(np.asarray(plane)).copy()
    lane = int(f.lane) if f.lane is not None else int(rng.randint(lanes))
    sub = np.ascontiguousarray(mirror[..., lane]).reshape(-1)
    raw = sub.view(np.uint8)
    pos = int(rng.randint(raw.size))
    bit = int(f.bit) if f.bit is not None else int(rng.randint(8))
    raw[pos] ^= np.uint8(1 << bit)
    mirror[..., lane] = sub.reshape(np.shape(mirror[..., lane]))
    sharding = getattr(plane, "sharding", None)
    if sharding is not None:
        new = jax.device_put(mirror, sharding)
    else:
        new = jnp.asarray(mirror)
    return state._replace(**{name: new})


class FaultInjector:
    """Deterministic seam counter: `fire(point, **ctx)` raises when an
    armed fault covers this arrival.  `log` records every raised fault
    as (point, index) for assertions.  Thread-safe: the mesh drive fires
    seams from concurrent per-device threads.

    `flip(point, obj, **ctx)` is the r24 bit-flip sibling: it counts
    arrivals at the `corrupt_*` seams and returns `obj` with one seeded
    bit flipped when an armed `BitFlip` covers the arrival (unchanged
    otherwise); `flip_log` records (point, index, ctx)."""

    def __init__(self, faults: Sequence[Fault],
                 flips: Sequence[BitFlip] = ()):
        self.faults = list(faults)
        self.flips = list(flips)
        self.counts = {}
        self.flip_counts = {}
        self.log = []
        self.flip_log = []
        self._match_counts = {}
        self._flip_match_counts = {}
        self._lock = threading.Lock()

    def fire(self, point: str, **ctx):
        with self._lock:
            i = self.counts.get(point, 0)
            self.counts[point] = i + 1
            fire_f = fire_idx = None
            for fi, f in enumerate(self.faults):
                if f.point != point:
                    continue
                if f.match is not None:
                    if any(ctx.get(k) != v for k, v in f.match.items()):
                        continue
                    j = self._match_counts.get(fi, 0)
                    self._match_counts[fi] = j + 1
                    idx = j
                else:
                    idx = i
                if not (f.at <= idx < f.at + f.times):
                    continue
                if fire_f is None:
                    fire_f, fire_idx = f, idx
            if fire_f is None:
                return
            f, idx = fire_f, fire_idx
            if f.before is not None:
                f.before()
            self.log.append((point, idx))
        if f.exc is not None:
            raise f.exc(dict(ctx, point=point, index=idx))
        raise InjectedFault(point, idx, lanes=f.lanes,
                            message=f.message)

    def flip(self, point: str, obj, **ctx):
        """Bit-flip seam: return `obj` (bytes or a BatchState) with one
        seeded bit flipped when an armed BitFlip covers this arrival,
        else `obj` unchanged.  Never raises into the caller's path —
        corruption is silent by definition."""
        with self._lock:
            i = self.flip_counts.get(point, 0)
            self.flip_counts[point] = i + 1
            hit = hit_idx = None
            for fi, f in enumerate(self.flips):
                if f.point != point:
                    continue
                if f.match is not None:
                    if any(ctx.get(k) != v for k, v in f.match.items()):
                        continue
                    j = self._flip_match_counts.get(fi, 0)
                    self._flip_match_counts[fi] = j + 1
                    idx = j
                else:
                    idx = i
                if not (f.at <= idx < f.at + f.times):
                    continue
                if hit is None:
                    hit, hit_idx = f, idx
            if hit is None:
                return obj
            self.flip_log.append((point, hit_idx, dict(ctx)))
        if isinstance(obj, (bytes, bytearray)):
            return flip_bit_bytes(bytes(obj), seed=hit.seed + hit_idx,
                                  bit=hit.bit)
        if hasattr(obj, "_fields") and hasattr(obj, "_replace"):
            return _flip_batch_state(obj, hit, hit_idx, ctx)
        return obj

    @property
    def fired(self) -> int:
        return len(self.log)

    @property
    def flipped(self) -> int:
        return len(self.flip_log)


def seeded_faults(seed: int, points: Sequence[str] = ("launch", "serve"),
                  n: int = 1, max_at: int = 4) -> list:
    """Derive `n` faults deterministically from a seed — the fuzz mode
    of the harness (same seed, same incident schedule)."""
    rng = np.random.RandomState(int(seed) & 0x7FFFFFFF)
    out = []
    for _ in range(n):
        out.append(Fault(point=points[int(rng.randint(len(points)))],
                         at=int(rng.randint(max_at + 1))))
    return out


def gateway_chaos_schedule(seed: int,
                           engine_faults: int = 2,
                           swap_faults: int = 1,
                           journal_faults: int = 1,
                           edge_faults: int = 2,
                           max_at: int = 6) -> list:
    """The seeded fault schedule a chaos run arms on the gateway:
    engine launch/serve faults (the supervisor tier recovers), one-shot
    generation build/swap faults (the registration tier rolls back with
    a retryable 503), durable-journal write faults (the submit is
    rejected retryably, never accepted undurably), and HTTP edge
    delay/drop faults (clients see a slow or severed wire).  Same seed,
    same incident schedule — the chaos run is reproducible bit-for-bit
    up to thread interleaving.  The gateway process kill/restart is
    orchestrated by the driver, not armed here."""
    rng = np.random.RandomState(int(seed) & 0x7FFFFFFF)
    out = []
    for _ in range(engine_faults):
        out.append(Fault(point=("launch", "serve")[int(rng.randint(2))],
                         at=int(rng.randint(1, max_at + 1))))
    for k in range(swap_faults):
        # at = 1 + k: arrival 0 is the boot/resume generation build —
        # the schedule breaks the k-th RUNTIME registration (which
        # point along the build->swap transaction it breaks stays
        # seeded), and its retry (the next arrival) goes through
        out.append(Fault(
            point=("generation_build",
                   "generation_swap")[int(rng.randint(2))],
            at=1 + 2 * k))
    for _ in range(journal_faults):
        out.append(Fault(point="journal_write",
                         at=int(rng.randint(1, 4 * max_at))))
    for _ in range(edge_faults):
        point = ("http_response_delay",
                 "http_response_drop")[int(rng.randint(2))]
        # drops target only the POLLING route: a dropped poll is
        # retried harmlessly, while a dropped submit response would
        # strand an accepted id the client never learned (real clients
        # need idempotency keys for that; the harness asserts the
        # ids it KNOWS about)
        out.append(Fault(
            point=point,
            at=int(rng.randint(0, 8 * max_at)),
            match={"route": "requests"}
            if point == "http_response_drop" else None))
    return out


def partition_schedule(links, at: int = 0, times: int = 1000000,
                       both_ends: bool = False) -> list:
    """Deterministic network partition for the fleet peer seams.

    `links` is [(src, dst), ...] — each cuts the src->dst direction:
    every `peer_send` from src to dst (heartbeat probes included —
    they ride the same transport) faults for arrivals [at, at+times)
    of THAT link (per-fault matched counters, so multi-link schedules
    stay deterministic under thread interleaving).  Only the TRANSPORT
    seam is armed: arming `peer_heartbeat` too would shield the
    `peer_send` window behind it (the probe fires heartbeat first) and
    the partition would outlive its `times` — target `peer_heartbeat`
    directly only to starve probes while leaving the data plane up.
    `both_ends=True` also arms the receiver's `peer_recv` seam,
    modelling loss on the wire rather than at the sender's NIC.  A
    finite `times` heals the partition after the window —
    heartbeat-flap tests arm small windows to flap a peer into suspect
    and back."""
    out = []
    for src, dst in links:
        m = {"src": str(src), "dst": str(dst)}
        out.append(Fault(point="peer_send", at=at, times=times,
                         match=dict(m)))
        if both_ends:
            out.append(Fault(point="peer_recv", at=at, times=times,
                             match={"src": str(src),
                                    "dst": str(dst)}))
    return out


def churn_schedule(seed: int, gossip_drops: int = 2,
                   reshard_faults: int = 0,
                   max_at: int = 6) -> list:
    """The seeded churn weather an elastic-fleet run arms: a few
    dropped membership-gossip messages (the CRDT view must still
    converge through later exchanges) and, optionally, reshard-install
    faults (the live reshard must roll back onto the old mesh and a
    retry must succeed).  Same seed, same schedule.  The join/leave/
    reshard EVENTS themselves are driver-orchestrated — these seams
    supply the weather around them, exactly like partition_schedule
    for r16 partitions."""
    rng = np.random.RandomState(int(seed) & 0x7FFFFFFF)
    out = []
    for _ in range(gossip_drops):
        out.append(Fault(point="membership_gossip",
                         at=int(rng.randint(max_at + 1))))
    for k in range(reshard_faults):
        # arrival 2k faults, its retry (2k+1) goes through — mirrors
        # the gateway_chaos_schedule build/swap pairing
        out.append(Fault(point="reshard_install", at=2 * k))
    return out


def corrupt_checkpoint(path, mode: str = "truncate", seed: int = 0):
    """Damage a checkpoint file in place — the "corrupted/truncated
    checkpoint" fault class.  `truncate` cuts the file mid-archive (an
    interrupted non-atomic writer); `flip` xor-scrambles a byte span (bit
    rot / torn write).  checkpoint.load must refuse both cleanly."""
    with open(path, "rb") as f:
        data = bytearray(f.read())
    if mode == "truncate":
        data = data[:max(len(data) // 2, 1)]
    elif mode == "flip":
        rng = np.random.RandomState(seed)
        pos = int(rng.randint(max(len(data) - 64, 1)))
        for k in range(min(64, len(data) - pos)):
            data[pos + k] ^= 0xA5
    else:
        raise ValueError(f"unknown corruption mode {mode!r}")
    with open(path, "wb") as f:
        f.write(bytes(data))


def build_selective_runaway() -> bytes:
    """Module whose export `work(n)` loops forever for n < 0 and returns
    sum(0..n) otherwise — one poisoned argument turns one lane into a
    runaway while its neighbours finish.  Drives the supervisor's
    lane_step_cap quarantine in tests and the faults smoke bench."""
    from wasmedge_tpu.utils.builder import ModuleBuilder

    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], ["i32", "i32"], [
        ("local.get", 0), ("i32.const", 0), "i32.lt_s",
        ("if", None),
        ("loop", None), ("br", 0), "end",
        "end",
        ("block", None),
        ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        ("local.get", 2), ("local.get", 1), "i32.add", ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0),
        "end",
        "end",
        ("local.get", 2),
    ], export="work")
    return b.build()
