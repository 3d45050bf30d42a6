"""BatchServer: the continuous-batching execution service.

The ROADMAP north star serves heavy traffic from millions of users, yet
every pre-r9 entry point (`VM.execute_batch`, `run_mixed`, the CLI)
executes one pre-packed cohort and drains it to completion — a short
request admitted behind fib(30) waits for the whole batch while freed
lanes sit parked.  `BatchServer` turns the drain-to-empty batch runner
into a long-lived service:

  submit(func, args, tenant=, deadline_s=) -> ServeFuture
      bounded queue (QueueSaturated backpressure), per-tenant
      weighted-fair admission with in-flight quotas (serve/queue.py)

  serving loop (step / run_until_idle / start)
      each round runs ONE steps_per_launch slice of the SIMT engine
      (`run_from_state`, hostcalls served between chunks as always),
      then harvests every lane that retired, resolves its future, and
      RE-INITIALIZES the freed lanes in place with queued requests
      (serve/recycle.py — the `initial_state` column seam) instead of
      waiting for batch drain.  Suspendable instances make this sound:
      a BatchState lane is exactly the "continuation" the effect-
      handlers line of work reifies, and recycling it is a column set.
      Results are bit-identical to a solo `execute_batch` run for
      lane-placement-independent guests; tier-0 random_get keys its
      stream on the physical lane index, so a random-drawing guest's
      output depends on which lane freed — same as any batch placement.

  supervision
      a serving state checkpoints/restores like any batch
      (batch/checkpoint.py; the lane->request binding journal rides the
      checkpoint's invocation metadata).  Launch/serve failures restore
      the newest good snapshot with backoff; requests admitted after
      that snapshot are re-queued at the front, so in-flight requests
      survive a crash — across processes too (`resume=True` adopts the
      lineage and returns fresh futures for the adopted requests).

  observability
      queue-depth / live-occupancy counter tracks, an admission-latency
      histogram, and one span per request on the "serve" track land on
      the shared flight recorder (obs/); `Configure.serve.autotune`
      additionally drives steps_per_launch from the drain-latency
      histograms (serve/autotune.py).

  cross-host migration seams (r16, wasmedge_tpu/fleet/)
      `export_vlane` detaches one parked (swapped) virtual lane as its
      content-keyed SwapStore payload + journal entry;  `adopt_vlane`
      installs one received from a peer (hash-verified) as a swapped
      virtual lane under its ORIGINAL id, reinstalled by the ordinary
      hv boundary rebalance;  `list_swapped` is the migratable set.
"""

from __future__ import annotations

import copy
import heapq
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from wasmedge_tpu.common.errors import EngineFailure, ErrCode, WasmError
from wasmedge_tpu.common.statistics import FailureRecord, record_failure
from wasmedge_tpu.batch.image import TRAP_DONE, TRAP_PARKED
from wasmedge_tpu.batch.lineage import Lineage
from wasmedge_tpu.serve.queue import (
    DeadlineExceeded,
    FairQueue,
    QueueSaturated,
    ServeFuture,
    ServeRejected,
    ServeRequest,
)
from wasmedge_tpu.serve.recycle import LaneRecycler


def device_info(devices=None) -> dict:
    """The `device` object of the gateway's `listening` line and of
    `GET /v1/status`: platform, kind and count of `devices`, the ones
    the serving state lives on (BatchServer.device_info); None means
    where an uncommitted array lands.  Never from
    jax.default_backend()."""
    if devices is None:
        import jax.numpy as jnp

        devices = jnp.zeros((), jnp.int32).sharding.device_set
    devs = sorted(devices, key=lambda d: d.id)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class BatchServer:
    """Continuous-batching server over one instantiated module.

    `weights` / `quotas` map tenant name -> DRR weight / max in-flight
    lanes (serve/queue.py).  `faults` is an optional
    testing.faults.FaultInjector armed on the engine's deterministic
    launch/serve/checkpoint seams.  `resume=True` adopts an existing
    `checkpoint_dir` lineage: the serving state and its in-flight
    requests come back under fresh futures (`server.adopted`)."""

    def __init__(self, inst=None, store=None, conf=None,
                 lanes: Optional[int] = None,
                 stats=None, weights=None, quotas=None, faults=None,
                 checkpoint_dir: Optional[str] = None,
                 resume: bool = False, engine=None,
                 resident_budgets=None, devices=None):
        from wasmedge_tpu.common.configure import Configure
        from wasmedge_tpu.batch.engine import BatchEngine
        from wasmedge_tpu.obs.recorder import recorder_of

        if engine is not None:
            # pre-built engine (the gateway's multi-module concatenated
            # engine, gateway/): its Configure governs the run, and the
            # CALLER must hand a dedicated copy — the server mutates
            # serve/autotune knobs on it (inst/store/lanes are the
            # engine's own).  The mesh, too: a caller wanting a
            # sharded server builds the engine over the mesh itself
            # (registry.build_engine(devices=...)), so `devices` here
            # would be silently ignored — refuse loudly instead.
            if devices is not None:
                raise ValueError(
                    "BatchServer(engine=..., devices=...): a pre-built "
                    "engine carries its own mesh; build it over the "
                    "devices instead (e.g. BatchEngine(..., "
                    "mesh=lane_mesh(devices=...)))")
            self.conf = engine.conf
            self.k = self.conf.serve
            if self.k.autotune:
                self.conf.obs.enabled = True
            self.engine = engine
        else:
            # the server owns its knobs (autotune mutates
            # steps_per_launch); the shared flight recorder's identity
            # survives the deepcopy
            self.conf = copy.deepcopy(conf) if conf is not None \
                else Configure()
            self.k = self.conf.serve
            if self.k.autotune:
                # the tuner feeds on the tier-1 drain-latency
                # histograms; with the recorder off it would silently
                # never fire (the CLI forces the same pairing)
                self.conf.obs.enabled = True
            # mesh-tier continuous batching (ROADMAP #1): `devices`
            # builds the engine over a lane-sharded named mesh driven
            # by the single-program shard drive — the serving pool
            # rounds UP to a device multiple (extra lanes are just
            # capacity; idle lanes park TRAP_DONE, so no pad masking
            # is needed) and every install/harvest/swap addresses
            # GLOBAL lane indices, so a recycled or hv-swapped request
            # can land on any device's shard.
            mesh = None
            if devices is not None:
                from wasmedge_tpu.parallel.mesh import (
                    lane_mesh, normalize_devices)
                from wasmedge_tpu.parallel.shard_drive import padded_lanes

                devs = normalize_devices(devices)
                mesh = lane_mesh(devices=devs)
                lanes = padded_lanes(lanes or self.conf.batch.lanes,
                                     len(devs))
            self.engine = BatchEngine(inst, store=store, conf=self.conf,
                                      lanes=lanes, mesh=mesh)
        self.lanes = self.engine.lanes
        # divergence-aware lane compaction (batch/compact.py): the
        # SERVER owns the boundary pass — the engine-level compactor
        # stays disarmed (_compact_external) so a permutation can never
        # fire under the lane->request bindings without the remap below
        # (_compact_round).  Narrowing is off: serving lanes are
        # capacity, not a fixed cohort.
        self.engine._compact_external = True
        self.engine.compactor = None
        self._compactor = None
        if getattr(self.conf.batch, "compact", False):
            from wasmedge_tpu.batch.compact import LaneCompactor

            self._compactor = LaneCompactor(self.engine, narrow=False)
        self.obs = recorder_of(self.conf)
        self.stats = stats
        self.faults = faults
        self.queue = FairQueue(self.k.queue_capacity, weights=weights,
                               quotas=quotas)
        self.recycler = LaneRecycler(self.engine)
        # lane virtualization (wasmedge_tpu/hv/): when either capacity
        # knob is set, admission counts the resident-bytes budget and
        # virtual-lane headroom instead of the raw free-lane heap, and
        # the boundary rebalance swaps cold lanes host-side.  Off (the
        # default) every path below behaves exactly as before.
        self.hv = None
        if getattr(self.conf, "hv", None) is not None \
                and self.conf.hv.active:
            from wasmedge_tpu.hv import LaneVirtualizer

            self.hv = LaneVirtualizer(
                self.engine, self.recycler, self.conf.hv, self.obs,
                faults=faults, record=self._record,
                tenant_budgets=resident_budgets)
            self.hv.install_cb = self._hv_on_install
            # a corrupt-entry loss is an admitted request terminated by
            # the infrastructure — counted like an in-flight kill so
            # the outcome counters keep reconciling with submitted
            self.hv.lost_cb = self._hv_on_lost
        # guest suspend/resume (wasmedge_tpu/effects/): when
        # Configure.effects is on, blocking hostcalls (await_event,
        # pure-clock poll_oneoff) park their lanes through the
        # SwapStore at the boundary and re-enter on an external wake or
        # timer.  Off (the default) the engine never grows an _effects
        # attribute and every path below matches the pre-effects server
        # exactly.
        self.effects = None
        if getattr(self.conf, "effects", None) is not None \
                and self.conf.effects.active:
            from wasmedge_tpu.effects import EffectsRuntime

            self.effects = EffectsRuntime(
                self.conf.effects, self.lanes,
                store=(self.hv.store if self.hv is not None else None),
                faults=faults, obs=self.obs, record=self._record)
            self.engine._effects = self.effects
        # parked-table fingerprint at the last good checkpoint: park /
        # wake changes are durable state even when total stands still
        self._eff_snap_ids = None
        # shadow-audit lanes (wasmedge_tpu/integrity/, r24): armed as
        # the engine's _audit_hook for every launch slice _step_body
        # drives.  A divergence raises out of the slice like a device
        # failure, lands in _recover with fault class "integrity", and
        # repeated attributions to one device eject it through the r21
        # reshard path.  Off (the default) no hook exists anywhere on
        # the launch path — bit-identical r23.
        self.auditor = None
        integ = getattr(self.conf, "integrity", None)
        if integ is not None and integ.audit:
            from wasmedge_tpu.integrity import ShadowAuditor

            self.auditor = ShadowAuditor(integ, obs=self.obs,
                                         faults=faults)
            self.engine._audit_hook = self.auditor
        self.checkpoint_dir = checkpoint_dir or self.k.checkpoint_dir
        self.state = None
        self.total = 0
        self._bindings: Dict[int, ServeRequest] = {}
        self._kills: Dict[int, BaseException] = {}
        self._planes = None   # host (trap, retired) mirrors, one round
        self._stepping = False   # one driver per round (see step())
        self._inflight = False   # a launch slice is running off-lock
        # min-heap of free lane indices: lowest-lane-first admission
        # stays deterministic at O(log n) per pop instead of list.pop(0)
        # shifts under the lock (an ascending list IS a valid heap)
        self._free: List[int] = list(range(self.lanes))
        self._served_before = np.zeros(self.lanes, bool)
        # checkpoint members with the lane->request binding snapshot as
        # the payload (shared machinery, batch/lineage.py)
        self._lineage = Lineage()
        # stdout cursor positions captured when self.state was current:
        # the launch slice runs outside the lock and its end-of-slice
        # flush advances the engine-resident cursor while self.state is
        # still the PRE-launch state — an on-demand checkpoint() from
        # another thread must journal this snapshot, not the live cursor,
        # or a restore would suppress output the saved state has not
        # produced yet (silent loss)
        self._stdout_snap = None
        self._consecutive = 0
        self._pending_backoff = 0.0
        self.retries = 0
        # checkpoint-write health: consecutive failed snapshot saves
        # since the last good one (the gateway's /healthz reads this —
        # a server that cannot persist its state is degraded, not dead)
        self.checkpoint_fail_streak = 0
        self.last_checkpoint_error: Optional[BaseException] = None
        self.failures: List[FailureRecord] = []
        self.failed: Optional[BaseException] = None
        self._draining = False
        self._lock = threading.RLock()
        self._wake = threading.Condition(self._lock)
        self._thread = None
        self._stop = False
        self.counters = {
            "submitted": 0, "admitted": 0, "completed": 0, "trapped": 0,
            "rejected": 0, "expired": 0, "killed": 0, "recycled_lanes": 0,
            "rounds": 0, "retired_instructions": 0, "reshards": 0,
        }
        self.adopted: Dict[int, ServeFuture] = {}
        if resume:
            self._adopt_lineage()

    # -- submission --------------------------------------------------------
    def submit(self, func_name: str, args=(),
               tenant: str = "default",
               deadline_s: Optional[float] = None,
               request_id: Optional[int] = None) -> ServeFuture:
        """Queue one request; returns its future.  Raises QueueSaturated
        when the bounded queue is full, KeyError for an unknown export,
        and the server's terminal error once it has failed.

        `request_id` re-queues a journaled request under its ORIGINAL id
        (the gateway's durable-resume path: a polling client's 202 id
        must survive a gateway restart) — the process-global counter is
        advanced past it so fresh submissions can never collide."""
        with self._lock:
            if self.failed is not None:
                raise self.failed
            if self._draining:
                raise WasmError(ErrCode.Terminated,
                                "server is draining; submissions closed")
            # a tenant configured out of admission (quota<=0 / weight<=0)
            # can never be installed: reject now, never strand a future.
            # NOT QueueSaturated — that signals "try later", and a
            # retry-on-backpressure caller (the CLI's idiom) would
            # livelock retrying a permanent condition
            quota = self.queue.quotas.get(tenant)
            if (quota is not None and quota <= 0) \
                    or self.queue.weights.get(tenant, 1.0) <= 0:
                raise WasmError(
                    ErrCode.Terminated,
                    f"tenant {tenant!r} has no admission capacity "
                    f"(quota/weight <= 0)")
            self.recycler.func_idx(func_name)  # validate the export now
            now = time.monotonic()
            req = ServeRequest(
                func_name, tuple(int(a) for a in args), tenant=tenant,
                deadline=(now + float(deadline_s))
                if deadline_s is not None else None,
                t_submit=now, request_id=request_id)
            if request_id is not None:
                from wasmedge_tpu.serve.queue import advance_request_ids

                advance_request_ids(req.id)
            self.queue.push(req)   # raises QueueSaturated on backpressure
            self.counters["submitted"] += 1
            self.obs.counter("serve_queue_depth", len(self.queue),
                             track="serve")
            self._wake.notify_all()
            return req.future

    def withdraw(self, request_id: int) -> bool:
        """Remove a still-QUEUED request (the gateway's take-back for
        an acceptance it could not journal durably): the guest must
        not burn a lane on work whose id the client was told never
        existed.  Counted as rejected so the counters reconcile;
        returns False when the request was already admitted (its lane
        runs to completion, but its future is already rejected and the
        first-outcome-wins guard swallows the late result)."""
        with self._lock:
            req = self.queue.remove_by_id(int(request_id))
            if req is None:
                return False
            self.counters["rejected"] += 1
            return True

    # -- cross-host lane migration (fleet/, r16) ---------------------------
    def list_swapped(self) -> List[int]:
        """Request ids currently parked off-device with a SwapStore
        payload: hv SWAPPED virtual lanes plus effects parked sessions
        — the migratable set (their full lane state is already a
        content-addressed blob)."""
        with self._lock:
            out: List[int] = []
            if self.hv is not None:
                out = [rid for rid, v in self.hv.waiting.items()
                       if v.key is not None]
            if self.effects is not None:
                out.extend(self.effects.parked_ids())
            return out

    def export_vlane(self, request_id: int):
        """Detach one waiting virtual lane for cross-host migration:
        returns (entry, payload) where `entry` is the JSON-shaped
        journal record (id/func/args/tenant/key/stdout_pos plus the
        remaining deadline in seconds) and `payload` the SwapStore
        blob bytes (None for a FRESH vlane that never installed — its
        state is reproducible from func+args alone).  The request
        leaves this server's accounting as `migrated`; its future is
        NOT resolved — the caller (fleet/federation.py) keeps it and
        resolves it from the receiving peer's outcome.  An effects
        PARKED SESSION exports the same way, its entry carrying the
        wake condition (pending payloads, remaining timer, paused
        deadline) so the receiving host resumes it bit-identically.
        Raises KeyError when the id is neither a waiting virtual lane
        nor a parked session."""
        with self._lock:
            if self.effects is not None \
                    and int(request_id) in self.effects.parked_ids():
                entry, payload = self.effects.export_parked(
                    int(request_id))
                self.counters["migrated"] = \
                    self.counters.get("migrated", 0) + 1
                return entry, payload
            if self.hv is None:
                raise KeyError("lane virtualization is off: no "
                               "migratable virtual lanes")
            v = self.hv.waiting.get(int(request_id))
            if v is None:
                raise KeyError(f"request {request_id} is not a waiting "
                               f"virtual lane")
            # read the payload BEFORE detaching anything: a corrupt /
            # unreadable blob leaves the vlane exactly where it was —
            # the next boundary's swap-in attempt surfaces it through
            # the existing corrupt-entry path (machine-readable
            # rejection), never a silently-lost request
            payload = None
            if v.key is not None:
                payload = self.hv.store.get(v.key)
            self.hv.waiting.pop(int(request_id), None)
            entry = v.journal()
            if v.req.deadline is not None:
                entry["deadline_s"] = max(
                    v.req.deadline - time.monotonic(), 0.001)
            if v.key is not None:
                self.hv.store.release(v.key)
            self.counters["migrated"] = \
                self.counters.get("migrated", 0) + 1
            return entry, payload

    def adopt_vlane(self, entry: dict, payload: Optional[bytes],
                    requeue: bool = False):
        """Install a migrated lane from a peer (or re-adopt a failed
        outbound migration with `requeue=True`): the payload is
        verified against its content key by SwapStore.adopt (hash
        verification IS the integrity check), parked as a swapped
        virtual lane under the request's ORIGINAL id, and reinstalled
        by a coming boundary rebalance through the existing jitted
        column-set pass.  Without a payload the request re-queues
        fresh (same at-least-once semantics as a crash re-queue).
        Returns the (new) local future.  Raises KeyError for an
        unknown export and ValueError when hv is off but a payload
        (mid-run state) was shipped."""
        from wasmedge_tpu.serve.queue import advance_request_ids

        rid = int(entry["id"])
        func = entry.get("func", "")
        args = tuple(entry.get("args", ()))
        with self._lock:
            if self.failed is not None:
                raise self.failed
            if self._draining:
                raise WasmError(ErrCode.Terminated,
                                "server is draining; migrations closed")
            self.recycler.func_idx(func)   # unknown export raises NOW
            if payload is None or entry.get("key") is None:
                # stateless: indistinguishable from a fresh re-queue
                fut = None
            elif entry.get("wake") is not None:
                # a migrated PARKED SESSION (the entry carries its wake
                # condition): verify + park under the ORIGINAL id; the
                # wake routes here from now on
                if self.effects is None:
                    raise ValueError(
                        "cannot adopt a parked session: the effects "
                        "subsystem is off on this server")
                now = time.monotonic()
                req = ServeRequest(
                    func, args, tenant=entry.get("tenant", "default"),
                    deadline=(now + float(entry["deadline_s"]))
                    if entry.get("deadline_s") is not None else None,
                    t_submit=now, request_id=rid)
                advance_request_ids(rid)
                self.effects.adopt_parked(entry, payload, req)
                if not requeue:
                    self.counters["submitted"] += 1
                    self.counters["admitted"] += 1
                else:
                    self.counters["migrated"] = \
                        self.counters.get("migrated", 0) - 1
                self._wake.notify_all()
                return req.future
            elif self.hv is None:
                raise ValueError(
                    "cannot adopt mid-run lane state: lane "
                    "virtualization is off on this server")
            else:
                self.hv.store.adopt(entry["key"], bytes(payload))
                now = time.monotonic()
                req = ServeRequest(
                    func, args, tenant=entry.get("tenant", "default"),
                    deadline=(now + float(entry["deadline_s"]))
                    if entry.get("deadline_s") is not None else None,
                    t_submit=now, request_id=rid)
                advance_request_ids(rid)
                from wasmedge_tpu.hv.manager import VirtualLane

                v = VirtualLane(req, key=entry["key"],
                                stdout_pos=int(entry.get("stdout_pos",
                                                         0)))
                v.swaps = 1
                self.hv.waiting[rid] = v
                if not requeue:
                    self.counters["submitted"] += 1
                    self.counters["admitted"] += 1
                else:
                    self.counters["migrated"] = \
                        self.counters.get("migrated", 0) - 1
                self._wake.notify_all()
                return req.future
        if fut is None:
            fut = self.submit(func, args,
                              tenant=entry.get("tenant", "default"),
                              deadline_s=entry.get("deadline_s"),
                              request_id=rid)
            if requeue:
                with self._lock:
                    # the failed migration's export counted `migrated`
                    # and this re-queue counted `submitted` again: back
                    # both out so the ledger shows one request once
                    self.counters["migrated"] = \
                        self.counters.get("migrated", 0) - 1
                    self.counters["submitted"] -= 1
        return fut

    def device_info(self) -> dict:
        """Read off a state plane's own sharding; before the first
        admission (no state yet) off the mesh the idle state will be
        placed on."""
        state = self.state
        mesh = getattr(self.engine, "mesh", None)
        if state is not None:
            return device_info(state.trap.sharding.device_set)
        return device_info(mesh.devices.flat if mesh is not None else None)

    # -- serving loop ------------------------------------------------------
    @property
    def in_flight(self) -> int:
        """Admitted requests holding capacity: resident lanes plus (hv)
        virtual lanes waiting off-device plus parked sessions."""
        n = len(self._bindings)
        if self.hv is not None:
            n += len(self.hv.waiting)
        if self.effects is not None:
            n += self.effects.in_flight()
        return n

    def _has_work(self) -> bool:
        """In-flight or queued work exists — drain() waits on this
        (a parked session IS in-flight work, even while nothing about
        it can move until its wake arrives)."""
        return bool(self._bindings or len(self.queue)
                    or (self.hv is not None and self.hv.waiting)
                    or (self.effects is not None
                        and self.effects.in_flight()))

    def _runnable_work(self) -> bool:
        """Work a round would actually advance — step()'s return value
        and the background driver's idle gate.  Parked sessions count
        only once a wake / due timer / pending park makes a boundary
        pass productive; otherwise the driver sleeps instead of
        burning no-op rounds."""
        if self._bindings or len(self.queue) \
                or (self.hv is not None and self.hv.waiting):
            return True
        return self.effects is not None and self.effects.runnable()

    def _flight_by_tenant(self) -> Dict[str, int]:
        """Per-tenant admitted counts for FairQueue quota accounting —
        virtual lanes and parked sessions count too: an admitted-but-
        suspended request holds its tenant's quota exactly like a
        resident one."""
        out: Dict[str, int] = {}
        for req in self._bindings.values():
            out[req.tenant] = out.get(req.tenant, 0) + 1
        if self.hv is not None:
            for v in self.hv.waiting.values():
                out[v.req.tenant] = out.get(v.req.tenant, 0) + 1
        if self.effects is not None:
            for tenant, n in self.effects.parked_by_tenant().items():
                out[tenant] = out.get(tenant, 0) + n
        return out

    def step(self) -> bool:
        """One serving round: expire, admit, run one launch slice,
        enforce deadlines/budgets, harvest, checkpoint, autotune.
        Returns True while queued or in-flight work remains."""
        # `serve/step_enter`, `serve/step_exit` and `_drive`'s
        # `serve/drive_wait` leave no time of the drive thread between
        # two `serve/round` spans outside a span
        with self.obs.timed("serve/step_enter", cat="serve",
                            track="serve/phases"), self._lock:
            if self.failed is not None:
                return False
            if self._stepping:
                # another driver is mid-round (e.g. a manual step()
                # racing the start() thread): launching again from the
                # same state would double-run the slice and clobber the
                # first driver's harvest — wait for the round to end
                # (so a run_until_idle() polling alongside start()
                # parks instead of busy-spinning) and report status
                self._wake.wait(timeout=0.05)
                return self._runnable_work()
            self._stepping = True
        try:
            return self._step_body()
        finally:
            # only the thread that RAN the round consumes the recovery
            # backoff its _recover() may have set — a caller that
            # bounced off the _stepping guard returns above and can
            # neither steal the nap nor zero it.  The sleep itself
            # stays OUTSIDE the lock: submit()/shutdown() from other
            # threads must not block on it.
            with self.obs.timed("serve/step_exit", cat="serve",
                                track="serve/phases"):
                with self._lock:
                    self._stepping = False
                    self._inflight = False  # safety: never strand a waiter
                    self._wake.notify_all()
                    nap, self._pending_backoff = \
                        self._pending_backoff, 0.0
                if nap > 0:
                    time.sleep(nap)

    def _step_body(self) -> bool:
        with self.obs.timed("serve/round", cat="serve",
                            track="serve/phases",
                            round=self.counters["rounds"] + 1):
            return self._round()

    def _lock_timed(self):
        """Acquire the server lock inside a `serve/lock_wait` span (a
        thousand handler threads contend for it); the caller releases
        it in a `finally`."""
        with self.obs.timed("serve/lock_wait", cat="serve"):
            self._lock.acquire()

    def _round(self) -> bool:
        timed = self.obs.timed
        self._lock_timed()
        try:
            now = time.monotonic()
            with timed("serve/admit", cat="serve") as span:
                self._expire_queued(now)
                if self.effects is not None:
                    with timed("serve/effects", cat="serve"):
                        self._effects_boundary(now)
                admitted = self._admit(now)
                span.set(admitted=admitted)
            if self.hv is not None:
                with timed("serve/hv", cat="serve"):
                    admitted += self._hv_boundary(now)
            if self._compactor is not None and self._bindings:
                with timed("serve/compact", cat="serve"):
                    self._compact_round()
            if self.effects is not None:
                # lane -> request id snapshot for the launch slice's
                # intercept (bindings are boundary-stable, so the
                # off-lock serve rounds read it without this lock)
                self.effects.begin_launch(
                    {lane: req.id
                     for lane, req in self._bindings.items()})
            run_from = (self.state, self.total) if self._bindings else None
            self._snap_stdout()   # pre-launch pairing for checkpoint()
            self._inflight = run_from is not None
        finally:
            self._lock.release()
        # the device launch slice runs OUTSIDE the lock — submit()/
        # shutdown() from other threads must not block for a whole
        # round's wall time.  Safe because only the serving thread
        # reassigns state/total/bindings; concurrent submitters touch
        # the queue, which every path still guards with the lock.
        launched = launch_err = None
        t_launch = 0.0
        stats0 = None
        if run_from is not None:
            eng = self.engine
            chunk = max(int(eng.cfg.steps_per_launch), 1)
            stats0 = dict(eng.hostcall_stats)
            t0 = time.monotonic()
            try:
                if self.faults is not None:
                    eng._fault_hook = self.faults.fire
                    if hasattr(self.faults, "flip"):
                        eng._flip_hook = self.faults.flip
                with timed("serve/launch", cat="serve") as span:
                    launched = eng.run_from_state(
                        run_from[0], run_from[1], run_from[1] + chunk)
                    span.set(steps=launched[1] - run_from[1])
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                launch_err = e
            finally:
                eng._fault_hook = None
                eng._flip_hook = None
            t_launch = time.monotonic() - t0
        self._lock_timed()
        try:
            self._inflight = False
            self._wake.notify_all()   # unblock a waiting checkpoint()
            if self.failed is not None:
                return False
            progressed = False
            if run_from is not None:
                progressed = True
                if launch_err is not None:
                    self._recover(launch_err)
                else:
                    self._consecutive = 0
                    self.state, self.total = launched
                    self._snap_stdout()   # cursor consistent again
                    if self.k.autotune:
                        self._autotune_observe(t_launch, stats0)
                now = time.monotonic()
                with timed("serve/enforce", cat="serve") as span:
                    self._enforce(now)
                    span.set(killed=len(self._kills))
            self.counters["rounds"] += 1
            with timed("serve/harvest", cat="serve") as span:
                harvested = self._harvest()
                span.set(harvested=harvested)
            if self.effects is not None and self._bindings \
                    and self.state is not None:
                # the park half of the suspend boundary: serialize
                # every TRAP_PARKED lane out through the SwapStore and
                # free its physical lane for the recycler
                with timed("serve/effects", cat="serve"):
                    self.state = self.effects.park_boundary(
                        self.engine, self.state, self._bindings,
                        self.recycler, self._effects_on_free)
            self.obs.counter("serve_live_lanes", len(self._bindings),
                             track="serve")
            self.obs.counter("serve_queue_depth", len(self.queue),
                             track="serve")
            if self.effects is not None:
                self.obs.counter("serve_parked_sessions",
                                 self.effects.in_flight(),
                                 track="serve")
            if self.k.checkpoint_every_rounds:
                with timed("serve/checkpoint", cat="serve"):
                    self._maybe_checkpoint()
            if not (admitted or progressed or harvested) \
                    and not self._bindings and len(self.queue) \
                    and not (self.hv is not None and self.hv.waiting) \
                    and not (self.effects is not None
                             and self.effects.in_flight()):
                # possibly stalled — but a submit() racing the launch
                # window lands in the queue AFTER this round's admit
                # phase; re-try admission before declaring a stall so a
                # perfectly admissible late arrival is installed (it
                # runs next round) instead of swept.  An hv server with
                # virtual lanes outstanding is NEVER swept here: "no
                # physical lane free but resident budget / virtual
                # headroom available" is backpressure (the waiters
                # drain at coming boundaries), not a permanent
                # admission block — the pre-hv free-lane-heap check
                # would have misclassified exactly this state.
                if self._admit(time.monotonic()):
                    return True
                # genuinely stalled: everything queued is admission-
                # blocked with no in-flight work to unblock it — nothing
                # will ever move, so reject rather than strand the
                # futures.  NOT QueueSaturated (that means "try later");
                # this is the same permanent condition submit() rejects
                # with a non-backpressure error
                for req in self.queue.pop_all():
                    self.counters["rejected"] += 1
                    req.future._reject(ServeRejected(
                        f"request {req.id} can never be admitted "
                        f"(tenant {req.tenant!r} admission-blocked)"))
                return False
            return self._runnable_work()
        finally:
            self._lock.release()

    def run_until_idle(self, max_rounds: Optional[int] = None) -> int:
        """Drive step() until no work remains; returns rounds executed."""
        rounds = 0
        while self.step():
            rounds += 1
            if max_rounds is not None and rounds >= max_rounds:
                break
        return rounds

    # -- background drive --------------------------------------------------
    def start(self):
        """Run the serving loop on a background thread until shutdown."""
        with self._lock:
            t = self._thread
            if t is not None and t.is_alive() and not self._stop:
                return self
        if t is not None:
            # a stopped/stopping driver exits at its round boundary —
            # reap it (off-lock: it needs the lock to finish) so two
            # drivers can never race the same state
            t.join()
        with self._lock:
            if self._thread is t:
                self._thread = None
            if self._thread is not None:   # lost a race to another start()
                return self
            self._stop = False
            self._thread = threading.Thread(target=self._drive,
                                            name="wasmedge-serve",
                                            daemon=True)
            self._thread.start()
        return self

    def _wait_for_work(self) -> bool:
        """Block the drive thread until a round would advance
        something; False once the server is stopping."""
        while True:
            with self._lock:
                if self._stop:
                    return False
                if self._runnable_work():
                    return True
                # nothing a round would advance (possibly parked
                # sessions waiting on an external wake): sleep on
                # the condvar — submit()/wake() notify it, and the
                # 50ms cap bounds timer-wake latency
                self._wake.wait(timeout=0.05)
                if self._stop:
                    return False
                # still nothing after the wait: don't burn an idle
                # round (rounds counter, no-op checkpoint checks)
                if self._runnable_work():
                    return True

    def _drive(self):
        while True:
            # one span from the end of a round to the start of the
            # next, however many waits an idle server makes in it
            with self.obs.timed("serve/drive_wait", cat="serve",
                                track="serve/phases"):
                if not self._wait_for_work():
                    return
            try:
                self.step()
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # terminal failure already recorded
                with self._lock:
                    if self.failed is None:
                        self._fail(e)
                return

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful drain: stop admitting new submissions, serve what is
        queued and in flight to completion.  Returns True when idle."""
        with self._lock:
            self._draining = True
            self._wake.notify_all()
            threaded = self._thread is not None
        deadline = (time.monotonic() + timeout_s) if timeout_s else None
        if threaded:
            while True:
                with self._lock:
                    idle = not self._has_work() \
                        or self.failed is not None
                if idle:
                    return True
                if deadline is not None and time.monotonic() >= deadline:
                    return False
                time.sleep(0.01)
        while self.step():
            if deadline is not None and time.monotonic() >= deadline:
                return False
        return not self._has_work()

    def shutdown(self, drain: bool = True,
                 timeout_s: Optional[float] = None):
        """Stop the server.  With drain=True queued + in-flight work is
        served first; without, unfinished futures are rejected."""
        if drain:
            self.drain(timeout_s=timeout_s)
        with self._lock:
            self._stop = True
            self._draining = True
            self._wake.notify_all()
            t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            if t.is_alive():
                # a long round (first-install compile, big slice) is
                # still in flight: _stop is set, so the thread exits at
                # the round boundary — keep its handle so a subsequent
                # start() cannot spawn a second driver alongside it
                pass
            else:
                self._thread = None
        with self._lock:
            err = ServeRejected("server shut down")
            for req in list(self._bindings.values()):
                if not req.future.done:
                    self.counters["killed"] += 1   # terminated in flight
                req.future._reject(err)
            self._bindings.clear()
            if self.hv is not None:
                # virtual lanes are admitted in-flight work too: their
                # blobs release and their futures reject like bindings
                for req in self.hv.drop_all():
                    if not req.future.done:
                        self.counters["killed"] += 1
                    req.future._reject(err)
            if self.effects is not None:
                # parked sessions likewise: blobs release, futures
                # reject, streams end so subscribers unblock
                for req in self.effects.drop_all():
                    if not req.future.done:
                        self.counters["killed"] += 1
                    req.future._reject(err)
                    self.effects.close_stream(req.id,
                                              error="server shut down")
            self._free = sorted(set(range(self.lanes)))
            for req in self.queue.pop_all():
                self.counters["rejected"] += 1
                req.future._reject(err)

    def _idle_state(self, fidx: int):
        """A fresh all-idle serving state, placed lane-sharded on the
        mesh when the engine drives one (so the first launch does not
        pay a host->device reshard of every plane)."""
        state = self.recycler.idle_state(fidx)
        mesh = getattr(self.engine, "mesh", None)
        if mesh is not None:
            from wasmedge_tpu.parallel.mesh import shard_batch_state

            state = shard_batch_state(state, mesh)
        return state

    # -- round phases ------------------------------------------------------
    def _expire_queued(self, now: float):
        for req in self.queue.expire(now):
            self.counters["expired"] += 1
            req.future._reject(DeadlineExceeded(
                f"request {req.id} expired before admission"))

    def _admit(self, now: float) -> int:
        if self.hv is not None:
            # hv admission counts the resident-bytes budget and the
            # virtual headroom, not the raw free-lane heap: requests
            # beyond the physical lane count admit as fresh VIRTUAL
            # lanes and install at a boundary rebalance when budget
            # allows (the direct capacity multiplier of ROADMAP #4)
            headroom = self.hv.headroom(self._bindings)
            if headroom <= 0 or not len(self.queue):
                return 0
            picks = self.queue.pop(headroom, self._flight_by_tenant())
            rnd = self.counters["rounds"]
            for req in picks:
                self.hv.admit(req, rnd)
                self.obs.instant("admit_virtual", cat="hv", track="hv",
                                 id=req.id, tenant=req.tenant)
            self.counters["admitted"] += len(picks)
            return len(picks)
        if not self._free or not len(self.queue):
            return 0
        picks = self.queue.pop(len(self._free), self._flight_by_tenant())
        if not picks:
            return 0
        if self.state is None:
            fidx0 = self.recycler.func_idx(picks[0].func_name)
            self.state = self._idle_state(fidx0)
        # group by function so each install is one column-set pass
        by_func: Dict[int, List[ServeRequest]] = {}
        for req in picks:
            by_func.setdefault(self.recycler.func_idx(req.func_name),
                               []).append(req)
        for fidx, reqs in by_func.items():
            lanes = [heapq.heappop(self._free) for _ in reqs]
            nargs = max((len(r.args) for r in reqs), default=0)
            args_rows = [[(r.args[i] if i < len(r.args) else 0)
                          for r in reqs] for i in range(nargs)]
            self.state = self.recycler.install(self.state, lanes, fidx,
                                               args_rows)
            for lane, req in zip(lanes, reqs):
                self._bindings[lane] = req
                if self._served_before[lane]:
                    self.counters["recycled_lanes"] += 1
                self._served_before[lane] = True
                self.obs.observe_admission(now - req.t_submit)
                self.obs.instant("admit", cat="serve", track="serve",
                                 id=req.id, tenant=req.tenant, lane=lane)
        self.counters["admitted"] += len(picks)
        return len(picks)

    def _hv_boundary(self, now: float) -> int:
        """Lane-virtualization boundary pass (under the lock, before
        the launch slice): expire deadline-passed virtual lanes, then
        rebalance — install waiting virtual lanes into free physical
        lanes within the resident budget, evicting LRU victims to keep
        rotating when the device is full.  Returns the number of
        installs (progress, for the stall check)."""
        moved = 0
        for req in self.hv.expire(now):
            # a virtual lane is ADMITTED work: its deadline kill counts
            # like an in-flight kill, not a queued expiry
            self.counters["killed"] += 1
            moved += 1
            req.future._reject(DeadlineExceeded(
                f"request {req.id} exceeded its deadline while "
                f"swapped out"))
        if not self.hv.waiting:
            return moved
        if self.state is None:
            v0 = next(iter(self.hv.waiting.values()))
            fidx0 = self.recycler.func_idx(v0.req.func_name)
            self.state = self._idle_state(fidx0)
        before = len(self._bindings)
        swaps0 = self.hv.counters["swaps_in"] \
            + self.hv.counters["swaps_out"]
        self.state = self.hv.rebalance(self.state, self._bindings,
                                       self._free, now, self.total,
                                       self.counters["rounds"])
        swapped = (self.hv.counters["swaps_in"]
                   + self.hv.counters["swaps_out"]) - swaps0
        return moved + max(len(self._bindings) - before, 0) + swapped

    def _compact_round(self):
        """Lane-compaction boundary pass (under the lock, before the
        launch slice — batch/compact.py): when the policy fires, ONE
        jitted gather-permutation groups live lanes by (divergence
        bias, pc) and every lane-keyed server structure follows its
        lane through the permutation — bindings, pending kills, the
        free heap, recycling history, hv residency tracking, and the
        exactly-once stdout cursor (permuted by the compactor itself).
        The binding journal is remapped in the same locked section, so
        any checkpoint snapshots a consistent (state, journal) pair."""
        comp = self._compactor
        if self.state is None:
            return
        t0 = self.obs.now()
        plan = comp.plan_boundary(self.engine, self.state)
        if plan is None:
            return
        d, perm = plan
        self.state = comp.permute_state(self.engine, self.state, perm)
        inv = np.empty(perm.size, np.int64)
        inv[perm] = np.arange(perm.size)
        self._bindings = {int(inv[lane]): req
                          for lane, req in self._bindings.items()}
        self._kills = {int(inv[lane]): exc
                       for lane, exc in self._kills.items()}
        self._served_before = self._served_before[perm]
        self._free = sorted(int(inv[lane]) for lane in self._free)
        self._planes = None   # stale mirrors must never feed a harvest
        if self.hv is not None:
            hv = self.hv
            hv._last_retired = hv._last_retired[perm]
            hv._last_trap = hv._last_trap[perm]
            hv._resident_since = {int(inv[lane]): v for lane, v
                                  in hv._resident_since.items()}
            hv._last_progress = {int(inv[lane]): v for lane, v
                                 in hv._last_progress.items()}
        comp.fired(d)
        self._snap_stdout()   # cursor permuted with the state
        self.obs.observe_compaction(self.obs.now() - t0)
        self.obs.instant("compact", cat="compact", track="compact",
                         live=d.nlive, breaks_before=d.breaks,
                         breaks_ideal=d.ideal_breaks,
                         unique_pcs=d.unique_pcs,
                         in_flight=len(self._bindings))

    def _effects_boundary(self, now: float):
        """Suspend/resume wake pass (under the lock, before admission):
        drain queued HTTP wakes + due timers, kill timer-parked
        sessions whose deadline lapsed, and route install-ready
        sessions back toward a physical lane — as swapped virtual
        lanes through hv.waiting on an hv server (the ordinary
        boundary swap-in re-installs them), or directly through the
        shared column-install pass otherwise."""
        eff = self.effects
        ready, expired = eff.process_wakes(now)
        for req in expired:
            # a parked session is ADMITTED work: its deadline kill
            # counts like an in-flight kill, not a queued expiry
            self.counters["killed"] += 1
            req.future._reject(DeadlineExceeded(
                f"request {req.id} exceeded its deadline while parked"))
            eff.close_stream(req.id, error="deadline exceeded")
        if self.hv is not None:
            from wasmedge_tpu.hv.manager import VirtualLane

            for ps in eff.handoff_woken():
                v = VirtualLane(ps.req, key=ps.key,
                                stdout_pos=ps.stdout_pos)
                v.swaps = ps.swaps   # a swap-in continuation, not a
                #                      fresh install (note_installed
                #                      re-arms the paused deadline)
                self.hv.waiting[ps.req.id] = v
        elif eff.has_woken():
            if self.state is None:
                self.state = self._idle_state(0)
            if self._free:
                self.state = eff.install_woken(
                    self.engine, self.state, self._free,
                    self._bindings,
                    install_cb=self._effects_on_install)

    def _effects_on_free(self, lane: int, req):
        """Park hook EffectsRuntime.park_boundary calls for every lane
        it freed — returns the physical lane to the pool exactly like
        a harvest does."""
        heapq.heappush(self._free, lane)
        if self.hv is not None:
            self.hv.on_free(lane)

    def _effects_on_install(self, lane: int, req):
        """Install hook for a woken session landing on a lane (non-hv
        path): a resume is a continuation, not a new occupancy — no
        admission observation, but the lane is recycled-marked."""
        self._served_before[lane] = True

    def wake(self, request_id: int,
             payload: Optional[bytes] = None) -> str:
        """External wake for a request blocked in `await_event` (the
        gateway's POST /v1/requests/<id>/wake): queues the payload and
        nudges the serving loop.  Returns "parked" when the id is a
        parked session right now, "pending" when it is otherwise in
        flight (the payload pre-delivers at the request's next
        await_event), "unknown" otherwise — the wake still queues
        either way, so a wake racing the park is never lost."""
        if self.effects is None:
            raise WasmError(ErrCode.Terminated,
                            "effects subsystem is off "
                            "(Configure.effects.suspend)")
        rid = int(request_id)
        self.effects.wake(rid, payload)
        with self._lock:
            self._wake.notify_all()
            if rid in self.effects.parked_ids():
                return "parked"
            if any(req.id == rid for req in self._bindings.values()) \
                    or (self.hv is not None
                        and rid in self.hv.waiting):
                return "pending"
            return "unknown"

    def session_stats(self) -> Optional[dict]:
        """Parked-session occupancy/counters snapshot (None when the
        effects subsystem is off) — the /v1/status "sessions" block
        and the wasmedge_session_* Prometheus series read this."""
        if self.effects is None:
            return None
        return self.effects.stats()

    def stream_of(self, request_id: int):
        """The request's stdout StreamBuf (None when effects are off
        or the request never produced output) — the gateway's
        GET /v1/requests/<id>/stream reads it."""
        if self.effects is None:
            return None
        return self.effects.stream_of(int(request_id))

    def _hv_on_install(self, lane: int, req, first: bool):
        """Install hook the LaneVirtualizer calls for every lane it
        (re)initializes — keeps the recycled_lanes counter and the
        admission-latency histogram identical to the non-hv path.
        `first` marks a FRESH install (the request's first time on a
        device lane): only those count as recycling and observe
        admission latency — a swap-in is a continuation, not a new
        occupancy (it has its own swaps_in counter)."""
        if self.effects is not None:
            # a handed-off parked session landing through swap-in:
            # re-arm its paused deadline + observe the park duration
            # (no-op for ordinary hv lanes)
            self.effects.note_installed(req)
        if first:
            if self._served_before[lane]:
                self.counters["recycled_lanes"] += 1
            self.obs.observe_admission(time.monotonic() - req.t_submit)
            self.obs.instant("admit", cat="serve", track="serve",
                             id=req.id, tenant=req.tenant, lane=lane)
        self._served_before[lane] = True

    def _hv_on_lost(self, req):
        self.counters["killed"] += 1

    def hv_stats(self) -> Optional[dict]:
        """Lane-virtualization occupancy/counters snapshot (None when
        hv is off) — the /v1/status "hv" block and the Prometheus
        wasmedge_hv_* series read this."""
        if self.hv is None:
            return None
        with self._lock:
            return self.hv.stats(self._bindings)

    def _autotune_observe(self, t_launch: float, stats0: dict):
        """Feed the slice's wall time + tier-1 drain volume to the
        steps_per_launch tuner (Configure.serve.autotune)."""
        tuner = getattr(self, "_tuner", None)
        if tuner is None:
            from wasmedge_tpu.serve.autotune import ChunkAutotuner

            tuner = self._tuner = ChunkAutotuner(self.engine, self.k,
                                                 self.obs)
        parked = self.engine.hostcall_stats["tier1_calls"] \
            - stats0.get("tier1_calls", 0)
        tuner.observe(t_launch, parked)

    def _enforce(self, now: float):
        """Deadline + per-request step-budget enforcement on in-flight
        lanes: over-budget lanes are terminated in the state plane and
        their futures rejected at harvest."""
        if not self._bindings:
            return
        trap = np.asarray(self.state.trap).copy()
        retired = np.asarray(self.state.retired, np.int64)
        cap = int(self.k.max_steps_per_request)
        kill_lanes, kill_codes = [], []
        for lane, req in self._bindings.items():
            if trap[lane] != 0:
                continue
            if req.deadline is not None and now >= req.deadline:
                kill_lanes.append(lane)
                kill_codes.append(int(ErrCode.Terminated))
                self._kills[lane] = DeadlineExceeded(
                    f"request {req.id} exceeded its deadline in flight")
            elif retired[lane] >= cap:
                kill_lanes.append(lane)
                kill_codes.append(int(ErrCode.CostLimitExceeded))
                self._kills[lane] = WasmError(
                    ErrCode.CostLimitExceeded,
                    f"request {req.id} exceeded max_steps_per_request")
        if kill_lanes:
            import jax.numpy as jnp

            # "killed" is counted at harvest under the first-completion
            # guard — a restore can replay a kill, and the replayed
            # request must not count twice
            self.state = self.state._replace(
                trap=self.state.trap.at[jnp.asarray(
                    np.asarray(kill_lanes, np.int64))].set(
                    jnp.asarray(np.asarray(kill_codes, np.int32))))
            trap[np.asarray(kill_lanes, np.int64)] = kill_codes
        # hand the host mirrors (kills applied) to _harvest: the planes
        # are unchanged until the next launch, so the harvest phase must
        # not pay a second device->host sync for them
        self._planes = (trap, retired)
        if self.hv is not None:
            # LRU bookkeeping rides the mirrors this round already paid
            # for: lanes whose retired count advanced are recently-used
            self.hv.note_progress(trap, retired, self.total)

    def _harvest(self) -> int:
        """Resolve futures of every bound lane that stopped; park and
        free the lanes (the recycling half of continuous batching)."""
        planes, self._planes = self._planes, None
        if not self._bindings or self.state is None:
            return 0
        if planes is not None:
            trap, retired = planes
        else:  # defensive: a harvest not preceded by _enforce this round
            trap = np.asarray(self.state.trap)
            retired = np.asarray(self.state.retired, np.int64)
        # TRAP_PARKED lanes stopped but did not FINISH: they belong to
        # the effects park boundary, not the harvest
        done = [lane for lane in self._bindings
                if trap[lane] != 0 and trap[lane] != TRAP_PARKED]
        if not done:
            return 0
        by_func: Dict[int, List[int]] = {}
        for lane in done:
            by_func.setdefault(
                self.recycler.func_idx(self._bindings[lane].func_name),
                []).append(lane)
        for fidx, lanes in by_func.items():
            cells = self.recycler.harvest_cells(self.state, lanes, fidx)
            for col, lane in enumerate(lanes):
                req = self._bindings.pop(lane)
                code = int(trap[lane])
                # a crash-restore replay can re-complete an already
                # resolved request (future resolution is first-wins);
                # count and trace only the first completion
                first = not req.future.done
                if code == int(TRAP_DONE):
                    req.future._resolve(
                        [int(cells[r, col]) for r in range(cells.shape[0])])
                    if first:
                        self.counters["completed"] += 1
                else:
                    exc = self._kills.pop(lane, None)
                    if exc is None:
                        # a genuine guest trap
                        if first:
                            self.counters["trapped"] += 1
                        exc = WasmError(ErrCode(code)
                                        if code in ErrCode._value2member_map_
                                        else ErrCode.ExecutionFailed)
                    elif first:
                        self.counters["killed"] += 1
                    req.future._reject(exc)
                if self.effects is not None:
                    self.effects.close_stream(
                        req.id, error=None if code == int(TRAP_DONE)
                        else "request failed")
                if first:
                    # install() resets the lane's retired plane, so this
                    # is the REQUEST's retired count (true-utilization
                    # occupancy: retired / (total steps * lanes))
                    self.counters["retired_instructions"] += \
                        int(retired[lane])
                    self.obs.span(f"request/{req.tenant}", req.t_submit,
                                  cat="serve", track="serve", id=req.id,
                                  func=req.func_name, trap=code,
                                  retired=int(retired[lane]))
        self.state = self.recycler.park(self.state, done)
        for lane in done:
            heapq.heappush(self._free, lane)
            if self.hv is not None:
                self.hv.on_free(lane)
        return len(done)

    # -- live resharding (r21) ---------------------------------------------
    def reshard(self, devices=None) -> dict:
        """Live device-set change: rebuild the jitted shard chunk over
        a NEW mesh at a launch boundary and reinstall every resident
        lane's plane columns — no drain, no request re-queue.

        The lane pool only ever pads UP from its current width
        (padded_lanes over the new device count), so every resident
        lane keeps its GLOBAL index and its column verbatim: results
        are bit-identical to the unresharded run by construction.
        hv-parked virtual lanes are keyed by request id and ride
        through; a compaction permutation already applied is part of
        the running state and moves with it (the compactor itself is
        rebuilt over the new geometry).  A device SHRINK keeps the
        width and re-splits it across fewer devices.

        Blocks while a launch slice is in flight (the jitted chunk
        donates the pre-launch state's buffers — same hazard as
        checkpoint()).  The `reshard_install` fault seam fires BEFORE
        any mutation, and every failure mid-move rolls the old mesh,
        state, and bookkeeping back intact."""
        from wasmedge_tpu.parallel.mesh import (
            lane_mesh, normalize_devices, shard_batch_state)
        from wasmedge_tpu.parallel.shard_drive import (
            padded_lanes, regrow_state)

        devs = normalize_devices(devices) if devices is not None else []
        n_dev = max(len(devs), 1)
        # mesh construction validates the device set up front — a bad
        # set fails HERE, before the lock and before any mutation
        new_mesh = lane_mesh(devices=devs) if len(devs) > 1 else None
        with self._lock:
            while self._inflight and self.failed is None:
                self._wake.wait(timeout=0.1)
            if self.failed is not None:
                raise self.failed
            eng = self.engine
            old_lanes = self.lanes
            old_mesh = getattr(eng, "mesh", None)
            old_ndev = int(old_mesh.devices.size) \
                if old_mesh is not None else 1
            new_lanes = padded_lanes(old_lanes, n_dev)
            old = dict(run_chunk=eng._run_chunk, step=eng._step,
                       state=self.state, free=list(self._free),
                       served=self._served_before,
                       planes=self._planes,
                       compactor=self._compactor,
                       cursor=getattr(eng, "_stdout_cursor", None),
                       snap=self._stdout_snap,
                       rec_lanes=self.recycler.lanes)
            hv_old = None
            if self.hv is not None:
                hv = self.hv
                hv_old = (hv.lanes, hv.resident_cap, hv.virtual_cap,
                          dict(hv.tenant_caps), hv._last_retired,
                          hv._last_trap, hv._install_jit)
            try:
                if self.faults is not None:
                    self.faults.fire("reshard_install",
                                     old_devices=old_ndev,
                                     new_devices=n_dev,
                                     old_lanes=old_lanes,
                                     lanes=new_lanes)
                eng.lanes = new_lanes
                eng.mesh = new_mesh
                eng._run_chunk = None   # full retrace over the new mesh
                eng._step = None
                # the recycler must see the new width BEFORE building
                # the idle template (its column capture skips planes
                # whose trailing dim mismatches self.lanes)
                self.recycler.lanes = new_lanes
                if self.state is not None:
                    idle = self.recycler.idle_state(0)
                    host = regrow_state(old["state"], old_lanes, idle,
                                        new_lanes)
                    # the new tail lanes are born parked TRAP_DONE
                    # (the idle template), exactly like the pad lanes
                    # of an uneven split — free capacity, not work
                    self.state = shard_batch_state(host, new_mesh) \
                        if new_mesh is not None else host
                # exactly-once stdout: the hostcall layer REPLACES a
                # size-mismatched cursor with zeros — pad-extend it
                # instead, or every resident lane's flushed prefix
                # would replay
                cur = old["cursor"]
                if cur is not None and cur[0].size == old_lanes \
                        and new_lanes != old_lanes:
                    pad = np.zeros(new_lanes - old_lanes, cur[0].dtype)
                    eng._stdout_cursor = (
                        np.concatenate([cur[0], pad]),
                        np.concatenate([cur[1], pad.copy()]))
                if self.hv is not None:
                    self.hv.resize(new_lanes)
                if self.effects is not None:
                    # parked sessions are keyed by request id and ride
                    # through; the install pass retraces at new shapes
                    self.effects.resize(new_lanes)
                if self._compactor is not None:
                    from wasmedge_tpu.batch.compact import LaneCompactor

                    self._compactor = LaneCompactor(eng, narrow=False)
                self.lanes = new_lanes
                for lane in range(old_lanes, new_lanes):
                    heapq.heappush(self._free, lane)
                if new_lanes != old_lanes:
                    self._served_before = np.concatenate(
                        [self._served_before,
                         np.zeros(new_lanes - old_lanes, bool)])
                self._planes = None   # stale mirrors never feed a
                #                       harvest across the move
                self._snap_stdout()
                eng._build()   # eager: a mesh/compile-setup failure
                #                surfaces NOW, inside the rollback
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:
                eng.lanes = old_lanes
                eng.mesh = old_mesh
                eng._run_chunk = old["run_chunk"]
                eng._step = old["step"]
                eng._stdout_cursor = old["cursor"]
                self.recycler.lanes = old["rec_lanes"]
                self.state = old["state"]
                self._free = old["free"]
                self._served_before = old["served"]
                self._planes = old["planes"]
                self._compactor = old["compactor"]
                self._stdout_snap = old["snap"]
                if hv_old is not None:
                    hv = self.hv
                    (hv.lanes, hv.resident_cap, hv.virtual_cap,
                     hv.tenant_caps, hv._last_retired, hv._last_trap,
                     hv._install_jit) = hv_old
                if self.effects is not None:
                    self.effects.resize(old_lanes)
                self.lanes = old_lanes
                self._record("reshard", e)
                raise
            self.counters["reshards"] += 1
            resident = len(self._bindings)
        self.obs.instant("reshard", cat="serve", track="serve",
                         old_devices=old_ndev, devices=n_dev,
                         old_lanes=old_lanes, lanes=new_lanes,
                         resident=resident)
        return {"ok": True, "devices": n_dev, "old_devices": old_ndev,
                "lanes": new_lanes, "old_lanes": old_lanes,
                "resident": resident}

    # -- supervision -------------------------------------------------------
    def _snap_stdout(self):
        """Capture the stdout cursor positions consistent with the
        CURRENT self.state (called under the lock at every point the
        state/cursor pairing is known-consistent; see _stdout_snap)."""
        cur = getattr(self.engine, "_stdout_cursor", None)
        self._stdout_snap = np.zeros(self.lanes, np.int64) \
            if cur is None else cur[0].copy()

    def _record(self, fault_class: str, exc, checkpoint=None):
        rec = FailureRecord(
            fault_class=fault_class,
            error="" if exc is None else repr(exc),
            retry=self.retries, checkpoint=checkpoint,
            tier="serve").stamp()
        self.failures.append(rec)
        self.obs.failure(rec)
        if self.stats is not None:
            self.stats.add_failure(rec)
        else:
            record_failure(rec)

    def _recover(self, exc: BaseException):
        """Launch/serve failure: restore the newest good checkpoint (or
        scratch), re-queue requests the snapshot doesn't cover, back
        off, and keep serving — in-flight requests survive the crash."""
        self.retries += 1
        self._consecutive += 1
        point = getattr(exc, "point", None) or "launch"
        cls = "integrity" if point == "integrity" \
            else ("serve" if point == "serve" else "launch")
        self._record(cls, exc)
        self.obs.instant("retry", cat="serve", track="serve",
                         retry=self.retries,
                         consecutive=self._consecutive, point=str(point))
        if self._consecutive > int(self.k.max_retries):
            self._fail(EngineFailure(
                f"serving loop failed {self._consecutive} times: {exc!r}",
                self.failures))
            raise self.failed
        old_bindings = dict(self._bindings)
        old_virtual: Dict[int, ServeRequest] = {}
        if self.hv is not None:
            old_virtual = {rid: v.req
                           for rid, v in self.hv.waiting.items()}
        old_parked: Dict[int, ServeRequest] = {}
        if self.effects is not None:
            old_parked = {req.id: req
                          for req in self.effects.parked_requests()}
        state = total = None
        bindings: Dict[int, ServeRequest] = {}
        hv_triples: list = []
        blobs: Dict[str, bytes] = {}
        eff_pairs: list = []
        eff_blobs: Dict[str, bytes] = {}
        from wasmedge_tpu.batch import checkpoint

        def load(m):
            if self.faults is not None:
                self.faults.fire("checkpoint_load", path=m.path)
            st, tot = checkpoint.load(m.path, self.engine)
            payload = m.payload or {}
            if isinstance(payload, dict) and "bindings" in payload:
                b = dict(payload.get("bindings") or {})
                triples = list(payload.get("hv") or [])
                pairs = list(payload.get("effects") or [])
            else:   # pre-hv payload shape: the bindings dict itself
                b = dict(payload)
                triples = []
                pairs = []
            bl = {}
            if any(k is not None for _, k, _ in triples):
                raw = checkpoint.read_extra_arrays(m.path, "hvblob_")
                bl = {name[len("hvblob_"):]: arr.tobytes()
                      for name, arr in raw.items()}
            ebl = {}
            if pairs:
                raw = checkpoint.read_extra_arrays(m.path, "effblob_")
                ebl = {name[len("effblob_"):]: arr.tobytes()
                       for name, arr in raw.items()}
            return st, tot, b, triples, bl, pairs, ebl

        got = self._lineage.walk_newest(
            load, lambda e, m: self._record("checkpoint", e,
                                            checkpoint=m.path))
        if got is not None:
            (state, total, bindings, hv_triples, blobs,
             eff_pairs, eff_blobs) = got
        if state is None:
            # no surviving snapshot: restore an all-idle state and send
            # EVERY in-flight request back to the head of the queue
            from wasmedge_tpu.batch.hostcall import stdout_cursor_reset

            if old_bindings or self.state is not None:
                fidx0 = next(iter(
                    self.recycler.func_idx(r.func_name)
                    for r in old_bindings.values()), 0) \
                    if old_bindings else 0
                state = self._idle_state(fidx0)
            total = 0
            stdout_cursor_reset(self.engine)
        # Serving-layer stdout is AT-LEAST-once across a crash restore:
        # unlike the supervisor's fixed cohort, recovery may re-admit a
        # re-queued request onto a DIFFERENT lane, so the per-lane
        # high-water mark no longer describes the lane's future stream —
        # keeping it would silently swallow a later request's first
        # bytes (loss is worse than duplication).  Collapse it to the
        # restored logical position; replayed post-snapshot output may
        # duplicate, nothing is ever dropped.
        cur = getattr(self.engine, "_stdout_cursor", None)
        if cur is not None:
            cur[1][:] = cur[0]
        self.state, self.total = state, total
        self._bindings = bindings
        if self.hv is not None:
            self.hv.reset_residency(bindings, self.counters["rounds"],
                                    self.total)
        self._planes = None
        self._snap_stdout()   # restored state + collapsed cursor pair up
        # submission order (monotonic request id), not lane order: lanes
        # are reassigned on admission, so lane order would invert a
        # tenant's FIFO across the restore
        covered = {req.id for req in bindings.values()}
        candidates: Dict[int, ServeRequest] = {}
        for req in old_bindings.values():
            candidates[req.id] = req
        for rid, req in old_virtual.items():
            candidates[rid] = req
        for rid, req in old_parked.items():
            candidates[rid] = req
        if self.hv is not None:
            # the snapshot's virtual table is authoritative: swapped
            # blobs re-adopt from the npz-embedded copies; entries
            # whose blob is corrupt/missing come back as `lost` and
            # re-run from scratch (at-least-once, like any uncovered
            # in-flight request)
            lost = self.hv.restore(hv_triples, blobs, covered)
            covered |= {v.req.id
                        for v in self.hv.waiting.values()}
            for req in lost:
                candidates[req.id] = req
        if self.effects is not None:
            # the snapshot's parked table is authoritative too: parked
            # blobs re-adopt from the npz-embedded copies, corrupt or
            # missing entries come back as `lost` and re-run from
            # scratch (at-least-once)
            for req in self.effects.restore(eff_pairs, eff_blobs,
                                            covered):
                candidates[req.id] = req
            covered |= set(self.effects.parked_ids())
        elif eff_pairs:
            # this process runs with effects OFF: journaled parked
            # sessions re-queue as fresh requests rather than vanish
            for req, _entry in eff_pairs:
                candidates[req.id] = req
        requeue = sorted((req for req in candidates.values()
                          if req.id not in covered
                          and not req.future.done),
                         key=lambda r: r.id)
        self.queue.push_front(requeue)
        self._free = sorted(set(range(self.lanes)) - set(bindings))
        self._kills.clear()
        # the sleep itself happens in step() AFTER the lock is released
        # — a background-thread server must not freeze submit()/shutdown
        # for the whole backoff window
        from wasmedge_tpu.batch.supervisor import backoff_seconds

        self._pending_backoff = backoff_seconds(self.k, self._consecutive)
        # SDC incident: after the rollback is complete, drain the
        # divergence->eject ladder — the restored state re-executes the
        # slice either way (masking a transient flip); a device past the
        # quarantine threshold leaves the mesh before it can diverge
        # again
        if cls == "integrity":
            self._quarantine_eject()

    def _quarantine_eject(self):
        """Eject devices past the quarantine threshold through the r21
        reshard path (every resident lane survives — the same machinery
        a planned scale-down uses).  Single-device engines have nowhere
        to eject to: the candidate is marked (so the ladder stops
        re-firing) and counted, and serving continues on the retry
        ladder.  Attribution counts for surviving devices reset with
        the mesh indices after an eject — conservative, never silent."""
        aud = self.auditor
        if aud is None:
            return
        q = aud.quarantine
        pending = q.pending_ejects()
        if not pending:
            return
        eng = self.engine
        counted = 0
        if eng.mesh is not None:
            devs = list(eng.mesh.devices.flat)
            bad = set(pending)
            remaining = [d for i, d in enumerate(devs) if i not in bad]
            if remaining:
                try:
                    self.reshard(devices=remaining)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception:
                    # reshard records its own failure and rolls back
                    # onto the old mesh; the ladder re-fires on the
                    # next divergence
                    return
                for d in pending:
                    q.mark_ejected(d)
                counted = len(pending)
            # an eject that would empty the mesh is refused: keep
            # serving degraded, the retry ladder still masks incidents
        else:
            for d in pending:
                q.mark_ejected(d)
            counted = len(pending)
        if counted:
            self.counters["quarantined_devices"] = \
                self.counters.get("quarantined_devices", 0) + counted
            self.obs.instant("device_quarantined", cat="integrity",
                             track="serve", devices=list(pending))

    def integrity_stats(self):
        """Audit/quarantine counters for /v1/status + Prometheus (None
        when the auditor is off)."""
        if self.auditor is None:
            return None
        return {"audit": dict(self.auditor.stats),
                "quarantine": self.auditor.quarantine.snapshot()}

    def _fail(self, exc: BaseException):
        self.failed = exc
        # keep the counters reconcilable (submitted == completed +
        # trapped + expired + killed + rejected) even on terminal failure
        for req in list(self._bindings.values()):
            if not req.future.done:
                self.counters["killed"] += 1
            req.future._reject(exc)
        self._bindings.clear()
        if self.hv is not None:
            for req in self.hv.drop_all():
                if not req.future.done:
                    self.counters["killed"] += 1
                req.future._reject(exc)
        if self.effects is not None:
            for req in self.effects.drop_all():
                if not req.future.done:
                    self.counters["killed"] += 1
                req.future._reject(exc)
                self.effects.close_stream(req.id,
                                          error="server failed")
        for req in self.queue.pop_all():
            if not req.future.done:
                self.counters["rejected"] += 1
            req.future._reject(exc)

    def _maybe_checkpoint(self):
        every = self.k.checkpoint_every_rounds
        if not every or self.state is None:
            return
        if self.counters["rounds"] % int(every):
            return
        # idle rounds don't advance total: re-snapshotting the same
        # step count would stack duplicate paths in the lineage and the
        # prune pass would unlink the file it just wrote.  EXCEPT when
        # the parked-session table changed — a park/wake is durable
        # state even at a standstill step count (same total -> same
        # path, so Lineage.add replaces the member instead of stacking)
        newest = self._lineage.newest()
        if newest is not None and newest.steps == self.total:
            if self.effects is None \
                    or self.effects.parked_ids() == self._eff_snap_ids:
                return
        self.checkpoint()

    def checkpoint(self) -> Optional[str]:
        """Snapshot the serving state + the lane->request binding
        journal; returns the path (None when saving failed — a failed
        snapshot never kills a healthy server).  Locked: an on-demand
        call from another thread must see a state/journal pair from the
        same round, or a restore could resolve the wrong request.

        Blocks while a launch slice is in flight: the jitted chunk
        donates the pre-launch state's device buffers, so reading them
        mid-slice would hit deleted arrays — the wait bounds at one
        round's wall time and lands on the post-launch pairing."""
        with self._lock:
            while self._inflight and self.failed is None:
                self._wake.wait(timeout=0.1)
            return self._checkpoint_locked()

    def _checkpoint_locked(self) -> Optional[str]:
        if self.state is None:
            return None
        import os
        import tempfile

        from wasmedge_tpu.batch import checkpoint

        if self.checkpoint_dir is None:
            self.checkpoint_dir = tempfile.mkdtemp(prefix="wasmedge-serve-")
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        path = os.path.join(self.checkpoint_dir,
                            f"serve-{self.total:012d}.npz")
        journal = [dict(lane=lane, **req.asdict())
                   for lane, req in sorted(self._bindings.items())]
        invocation = {"serve_bindings": journal}
        extra = None
        payload = dict(self._bindings)
        if self.hv is not None:
            # the virtual table journals alongside the bindings, and
            # swapped blobs embed in the npz straight from the
            # SwapStore — the snapshot never faults a cold lane onto
            # the device, and a restore never depends on store
            # retention
            invocation["hv_lanes"] = self.hv.journal_entries()
            extra = self.hv.blob_arrays()
            payload = {"bindings": dict(self._bindings),
                       "hv": self.hv.snapshot_payload()}
        if self.effects is not None:
            # parked sessions journal alongside the bindings, their
            # blobs embed in the npz straight from the SwapStore —
            # exactly the hv discipline: a restore never depends on
            # store retention
            invocation["parked_sessions"] = \
                self.effects.journal_entries()
            eff_extra = self.effects.blob_arrays()
            if eff_extra:
                extra = dict(extra or {}, **eff_extra)
            if not (isinstance(payload, dict) and "bindings" in payload):
                payload = {"bindings": dict(self._bindings), "hv": []}
            payload["effects"] = self.effects.snapshot_payload()
        t0 = self.obs.now()
        try:
            if self.faults is not None:
                self.faults.fire("checkpoint_save", path=path)
            checkpoint.save(path, self.engine, self.state, self.total,
                            invocation=invocation,
                            stdout_pos=self._stdout_snap,
                            extra_arrays=extra)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            self.checkpoint_fail_streak += 1
            self.last_checkpoint_error = e
            self._record("checkpoint", e, checkpoint=path)
            return None
        self.checkpoint_fail_streak = 0
        self.last_checkpoint_error = None
        if self.effects is not None:
            self._eff_snap_ids = self.effects.parked_ids()
        self.obs.span("checkpoint_save", t0, cat="serve", track="serve",
                      checkpoint=path, steps=int(self.total),
                      in_flight=len(self._bindings))
        # same total -> same path: Lineage.add replaces the entry (the
        # state/journal may still differ via admissions) instead of
        # stacking duplicates the prune pass would unlink while
        # surviving entries still reference the file
        self._lineage.add(path, self.total, payload)
        self._lineage.prune(self.k.keep_checkpoints)
        return path

    def _adopt_lineage(self):
        """Cross-process resume: newest loadable serve-*.npz plus its
        binding journal (shared newest-good-member walk,
        batch/lineage.py); adopted requests get fresh futures
        (`self.adopted[id]`)."""
        from wasmedge_tpu.batch import checkpoint

        lin = self._lineage
        lin.install(Lineage.scan(self.checkpoint_dir,
                                 r"serve-(\d+)\.npz"))

        def load(m):
            state, total = checkpoint.load(m.path, self.engine)
            inv = checkpoint.read_meta(m.path).get("invocation", {})
            return (state, total, inv.get("serve_bindings", []),
                    inv.get("hv_lanes", []),
                    inv.get("parked_sessions", []))

        got = lin.walk_newest(
            load, lambda e, m: self._record("checkpoint", e,
                                            checkpoint=m.path))
        if got is None:
            return
        state, total, journal, hv_journal, eff_journal = got
        self.state, self.total = state, total
        self._snap_stdout()   # load() rewound the cursor in place
        from wasmedge_tpu.serve.queue import advance_request_ids

        for entry in journal:
            req = ServeRequest.from_journal(entry)
            req.t_submit = time.monotonic()
            self._bindings[int(entry["lane"])] = req
            self.adopted[req.id] = req.future
            advance_request_ids(req.id)
        self._adopt_hv(hv_journal, lin.members[-1].path)
        self._adopt_effects(eff_journal, lin.members[-1].path)
        self._free = sorted(set(range(self.lanes))
                            - set(self._bindings))
        self._served_before[list(self._bindings)] = True
        if self.hv is not None:
            self.hv.reset_residency(self._bindings, 0, self.total)
        # the full surviving lineage stays installed (like the
        # supervisor's twin adoption): older members remain usable as
        # _recover fallbacks, and the prune pass below keeps
        # crash/resume cycles from accumulating serve-*.npz forever.
        # Older journals reuse the adopted request objects by id so a
        # fallback restore resolves the futures callers hold.
        byid = {r.id: r for r in self._bindings.values()}
        if self.hv is not None:
            for v in self.hv.waiting.values():
                byid[v.req.id] = v.req
        if self.effects is not None:
            for r in self.effects.parked_requests():
                byid[r.id] = r
        survivors = []
        for m in lin.members[:-1]:
            try:
                inv2 = checkpoint.read_meta(m.path).get(
                    "invocation", {})
                j2 = inv2.get("serve_bindings", [])
                hv2 = inv2.get("hv_lanes", [])
                eff2 = inv2.get("parked_sessions", [])
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                self._record("checkpoint", e, checkpoint=m.path)
                continue
            snap2 = {}
            for e2 in j2:
                req2 = byid.get(int(e2["id"]))
                if req2 is None:
                    req2 = ServeRequest.from_journal(e2)
                    advance_request_ids(req2.id)
                snap2[int(e2["lane"])] = req2
            triples2 = []
            for e2 in hv2:
                req2 = byid.get(int(e2["id"]))
                if req2 is None:
                    req2 = ServeRequest.from_journal(e2)
                    advance_request_ids(req2.id)
                triples2.append((req2, e2.get("key"),
                                 int(e2.get("stdout_pos", 0))))
            pairs2 = []
            for e2 in eff2:
                req2 = byid.get(int(e2["id"]))
                if req2 is None:
                    req2 = ServeRequest.from_journal(e2)
                    advance_request_ids(req2.id)
                pairs2.append((req2, e2))
            if self.hv is not None or triples2 \
                    or self.effects is not None or pairs2:
                m.payload = {"bindings": snap2, "hv": triples2,
                             "effects": pairs2}
            else:
                m.payload = snap2
            survivors.append(m)
        newest = lin.members[-1]
        if self.hv is not None or self.effects is not None:
            newest.payload = {
                "bindings": dict(self._bindings),
                "hv": (self.hv.snapshot_payload()
                       if self.hv is not None else []),
                "effects": (self.effects.snapshot_payload()
                            if self.effects is not None else [])}
        else:
            newest.payload = dict(self._bindings)
        lin.members = survivors + [newest]
        lin.prune(self.k.keep_checkpoints)
        self.obs.instant("resume_adopted", cat="serve", track="serve",
                         checkpoint=newest.path, steps=int(total),
                         in_flight=len(self._bindings))

    def _adopt_hv(self, hv_journal, path: str):
        """Cross-process adoption of the virtual-lane table: swapped
        entries re-seed the SwapStore from the snapshot-embedded blobs;
        corrupt/missing blobs (and every entry when this process runs
        with hv OFF) re-queue at the front as fresh requests (at-least-
        once) — a journaled virtual lane is never silently lost.
        Adopted virtual requests get fresh futures like bindings do."""
        if not hv_journal:
            return
        from wasmedge_tpu.batch import checkpoint
        from wasmedge_tpu.serve.queue import advance_request_ids

        triples = []
        fallback = []
        for e in hv_journal:
            req = ServeRequest.from_journal(e)
            req.t_submit = time.monotonic()
            advance_request_ids(req.id)
            self.adopted[req.id] = req.future
            if self.hv is None:
                fallback.append(req)
            else:
                triples.append((req, e.get("key"),
                                int(e.get("stdout_pos", 0))))
        if self.hv is not None:
            try:
                raw = checkpoint.read_extra_arrays(path, "hvblob_")
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                self._record("checkpoint", e, checkpoint=path)
                raw = {}
            blobs = {name[len("hvblob_"):]: arr.tobytes()
                     for name, arr in raw.items()}
            covered = {r.id for r in self._bindings.values()}
            fallback.extend(self.hv.restore(triples, blobs, covered))
        self.queue.push_front(sorted(fallback, key=lambda r: r.id))

    def _adopt_effects(self, eff_journal, path: str):
        """Cross-process adoption of the parked-session table: entries
        re-seed the SwapStore from the snapshot-embedded effblob_
        arrays; corrupt/missing blobs (and every entry when this
        process runs with effects OFF) re-queue at the front as fresh
        requests (at-least-once) — a journaled parked session is never
        silently lost.  Adopted sessions get fresh futures like
        bindings do, their wake condition (pending payloads, remaining
        timer) re-armed from the journal — a wake posted before the
        crash still resumes the session exactly once."""
        if not eff_journal:
            return
        from wasmedge_tpu.batch import checkpoint
        from wasmedge_tpu.serve.queue import advance_request_ids

        pairs = []
        fallback = []
        for e in eff_journal:
            req = ServeRequest.from_journal(e)
            req.t_submit = time.monotonic()
            advance_request_ids(req.id)
            self.adopted[req.id] = req.future
            if self.effects is None:
                fallback.append(req)
            else:
                pairs.append((req, e))
        if self.effects is not None:
            try:
                raw = checkpoint.read_extra_arrays(path, "effblob_")
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                self._record("checkpoint", e, checkpoint=path)
                raw = {}
            blobs = {name[len("effblob_"):]: arr.tobytes()
                     for name, arr in raw.items()}
            covered = {r.id for r in self._bindings.values()}
            if self.hv is not None:
                covered |= {v.req.id
                            for v in self.hv.waiting.values()}
            fallback.extend(self.effects.restore(pairs, blobs,
                                                 covered))
        self.queue.push_front(sorted(fallback, key=lambda r: r.id))
