"""GatewayService: multi-tenant serving generations over BatchServer.

The long-lived core the HTTP layer (gateway/http.py) is a thin skin
over.  One *generation* = one `MultiModuleBatchEngine` (the
concatenated image of every registered module, batch/multitenant.py)
driven by one `BatchServer` on a background thread.  Runtime module
registration is a **generation swap**:

    POST /v1/modules
      -> registry.add_wasm()       (loader -> validator -> image, 400s
                                    on bad/unbatchable wasm)
      -> build generation N+1      (image rebuilt WITH the new module;
                                    freed lanes recycle onto the new
                                    function via the LaneRecycler /
                                    initial_state template seam)
      -> atomic pointer swap       (new submissions -> generation N+1)
      -> generation N drains       (in-flight AND queued requests
                                    finish on the OLD image — results
                                    stay bit-identical to solo runs —
                                    then the old server shuts down at
                                    its launch boundary)

The swap is wait-free for submitters: the swap holds the submit lock
only for the pointer write; the expensive parts (validation, image
concatenation) happen outside it, and the new engine's first jit
compile happens on its serving thread's first launch.

Request lifecycle: `submit()` stamps a GatewayRequest into the stash
keyed by the process-global request id (shared with ServeFuture), so
`202 Accepted` clients poll `GET /v1/requests/<id>` against the same
object the sync path waits on.  Resolved requests are kept for
`result_cache` completions and then pruned oldest-first.

Observability (off by default, like every other obs track): a
`gateway/<tenant>` span per request (receive -> resolve, with the
module/func/outcome in args) on the shared flight recorder, plus
`wasmedge_gateway_http_requests_total{code}` counters in the
Prometheus export fed by the HTTP layer's `count_http`.

r13 made the front door crash-survivable and self-degrading:

  durability    `state_dir=` attaches a gateway/durable.py DurableStore
                (module blobs + manifest + async-request journal, all
                crash-atomic); `resume=True` re-registers the stored
                module set under ONE boot generation, adopts the
                previous generation's BatchServer checkpoint lineage,
                replays resolved ids from the durable result cache and
                re-queues the rest under their ORIGINAL ids — a
                polling client's 202 id survives the restart
  swap safety   generation builds run against a build timeout on a
                worker thread; a build/swap that fails or times out
                rolls back ATOMICALLY (registry stash kept for the
                retry, submit pointer untouched, prior generation
                keeps serving bit-identically) and the registration
                returns a retryable GenerationBuildFailed (HTTP 503)
  health        `health()` (gateway/health.py) is the truthful
                /healthz: driver liveness, last-swap outcome, queue
                saturation, checkpoint/journal write health -> one of
                healthy / degraded / unhealthy
  shedding      while degraded, submissions from the lowest-weight
                tenant tier reject up front with a retryable 429
                (ShedLoad) instead of queueing into a timeout
  chaos seams   a testing/faults.py FaultInjector handed in as
                `faults=` arms gateway_register / generation_build /
                generation_swap / journal_write (plus the engine-tier
                launch/serve/checkpoint seams on every generation's
                BatchServer); `kill()` is the supported simulated
                SIGKILL the chaos harness restarts from
"""

from __future__ import annotations

import copy
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from wasmedge_tpu.common.errors import EngineFailure, ErrCode, WasmError
from wasmedge_tpu.gateway.durable import (
    DurabilityError,
    DurableStore,
    _resolved_entry,
    resolved_error,
)
from wasmedge_tpu.gateway.health import HealthGate
from wasmedge_tpu.gateway.registry import ModuleRegistry
from wasmedge_tpu.gateway.tenants import GatewayTenants

# ids remembered as "pruned" (vs never-issued) for the distinct 404
# detail; bounded so a long-lived gateway can't grow it forever
_PRUNED_MEMORY = 65536


class GenerationBuildFailed(EngineFailure):
    """A serving-generation build or swap failed (or exceeded the build
    timeout) and was rolled back: the PRIOR generation kept serving and
    nothing was half-swapped.  Retryable — the lowered module is
    stashed in the registry's probe cache, so a re-POST of the same
    bytes skips the lowering and retries only the build."""

    retryable = True

    def __init__(self, msg: str):
        super().__init__(msg)
        self.retry_after_s = 1.0


class GatewayClosed(WasmError):
    """The gateway is shutting down — distinct from a tenant's
    permanent admission block (both ride ErrCode.Terminated): the HTTP
    layer maps THIS to 503 (restarting service, come back) and the
    admission block to 403 (your policy forbids it, don't).
    Retryable: the SAME request is welcome at the restarted gateway,
    so the 503 carries Retry-After like the other transient classes."""

    retryable = True

    def __init__(self):
        super().__init__(ErrCode.Terminated, "gateway shut down")
        self.retry_after_s = 1.0


class GatewayRequest:
    """Stash entry for one gateway request (sync waiters and async
    pollers share it).  `args`/`deadline_s` ride along for the durable
    journal — a re-queued request must be re-executable verbatim."""

    __slots__ = ("id", "tenant", "module", "func", "future", "t_recv",
                 "gen_id", "finalized", "args", "deadline_s", "edge")

    def __init__(self, future, tenant, module, func, gen_id, t_recv,
                 args=(), deadline_s=None, edge=None):
        self.id = future.request_id
        self.future = future
        self.tenant = tenant
        self.module = module
        self.func = func
        self.gen_id = gen_id
        self.t_recv = t_recv
        self.finalized = False
        self.args = tuple(int(a) for a in args)
        self.deadline_s = deadline_s
        # fleet routing: the peer that ACCEPTED this request (its 202
        # came from there) when it differs from the executing gateway —
        # journaled so failover adoption can tell "the edge re-queues
        # its own forward" from "nobody is left to re-queue this"
        self.edge = edge


class _Generation:
    __slots__ = ("gen_id", "engine", "server", "modules", "serve_dir")

    def __init__(self, gen_id, engine, server, modules, serve_dir=None):
        self.gen_id = gen_id
        self.engine = engine
        self.server = server
        self.modules = tuple(modules)
        self.serve_dir = serve_dir


class GatewayService:
    """The gateway's engine room (transport-free; see gateway/http.py).

    `conf` is the template Configure every generation deep-copies (the
    BatchServer mutates serve knobs on its copy); `tenants` the edge
    policy table; `lanes` the per-generation serving pool width."""

    def __init__(self, conf=None, lanes: int = 64,
                 tenants: Optional[GatewayTenants] = None,
                 result_cache: int = 4096,
                 sync_wait_s: float = 60.0,
                 sink_stdout: bool = True,
                 faults=None,
                 state_dir: Optional[str] = None,
                 resume: bool = False,
                 build_timeout_s: Optional[float] = 120.0,
                 shed_on_degraded: bool = True,
                 devices=None,
                 fleet=None,
                 autoscale=None):
        from wasmedge_tpu.common.configure import Configure
        from wasmedge_tpu.obs.recorder import recorder_of

        self.template = conf or Configure()
        # instantiate the shared ring BEFORE any generation deepcopies
        # its Configure, so every generation reports into ONE recorder
        self.obs = recorder_of(self.template)
        self.lanes = int(lanes)
        # mesh-tier serving (ROADMAP #1): every generation's engine is
        # built over this lane-sharded device mesh and driven by the
        # single-program shard drive; the pool rounds up to a device
        # multiple (MultiModuleBatchEngine does the rounding)
        self.devices = None
        if devices is not None:
            from wasmedge_tpu.parallel.mesh import normalize_devices

            self.devices = normalize_devices(devices)
        self.tenants = tenants or GatewayTenants()
        self.registry = ModuleRegistry(conf=self.template,
                                       sink_stdout=sink_stdout)
        self.result_cache = int(result_cache)
        self.sync_wait_s = float(sync_wait_s)
        self.faults = faults
        self.build_timeout_s = build_timeout_s
        self.shed_on_degraded = bool(shed_on_degraded)
        self.force_degraded = False   # operator/test switch
        self._lock = threading.RLock()
        self._reg_lock = threading.Lock()   # one registration at a time
        self._gens: List[_Generation] = []  # current is last
        self._gen_seq = 0
        self._reapers: List[threading.Thread] = []
        self._requests: Dict[int, GatewayRequest] = {}
        self._resolved = deque()
        self._pruned: "deque[int]" = deque(maxlen=_PRUNED_MEMORY)
        self._pruned_set = set()
        self._closed = False
        self.http_counts: Dict[str, int] = {}
        self.last_swap: Optional[dict] = None
        self.shed_counts: Dict[str, int] = {}
        self.counters = {
            "received": 0, "completed": 0, "failed": 0, "deadline": 0,
            "rejected": 0, "rate_limited": 0, "registered_modules": 0,
            "generations": 0, "policy_rejected": 0,
            "restarts": 0, "rollbacks": 0, "shed": 0,
            "journal_errors": 0, "resumed": 0,
        }
        # static-analysis admission summary (obs/metrics.py renders it
        # as wasmedge_analysis_* counters): verdicts of every module
        # that reached the policy gate + rejections it issued
        self.analysis_counts = {"bounded": 0, "unbounded": 0,
                                "policy_rejected": 0}
        # durable result cache mirrored to the journal: finalized
        # request outcomes a resumed gateway replays verbatim.  Capped
        # below the (in-memory) stash depth — every journal write
        # serializes this list, so its size is hot-path cost, and the
        # ISSUE contract is a SMALL durable cache with older ids
        # degrading to the pruned-404 answer
        self._durable_cache_depth = min(max(self.result_cache, 1), 512)
        self._result_cache = deque(maxlen=self._durable_cache_depth)
        self._journal_fail_streak = 0
        self._manifest_dirty = False
        # serializes snapshot->write so an older journal snapshot can
        # never land a NEWER sequence number (which would make it the
        # authoritative journal and lose a durably-accepted id)
        self._journal_mutex = threading.Lock()
        # replication sequence (drawn under _journal_mutex): stamps
        # fleet journal pushes so a receiver can discard an older
        # snapshot that arrives after a newer one — which frees the
        # peer HTTP to run OUTSIDE the mutex
        self._repl_seq = 0
        # ids at/below this were issued by a pre-crash process: an
        # unknown id under the floor answers the pruned 404 detail,
        # not "never existed" (journaled as max_id)
        self._resume_floor = 0
        # id range THIS gateway ever stashed — journaled as
        # min_id/max_id, the resumed process's pruned-404 window.
        # Deliberately not the process-global counter: the fleet's
        # id-space rebase (and any sibling gateway in-process) pushes
        # the global high-water far past ids this gateway issued —
        # journaling the global counter (or assuming ids start near 1)
        # would make a resumed gateway answer the pruned 404 for ids
        # it never accepted
        self._max_issued = 0
        self._min_issued = 0   # 0 = nothing issued yet
        self._resume_min = 1   # legacy journals: ids start near 1
        # pending serve-lineage adoption consumed by the next
        # generation build (set only during _resume_from_disk)
        self._pending_resume: Optional[str] = None
        self.durable = DurableStore(
            state_dir, faults=faults,
            result_cache=self._durable_cache_depth) \
            if state_dir else None
        # imagestore (r22): segmented device images, the persistent
        # compile cache, and pre-initialized lane snapshots.  All three
        # knobs default off, leaving this block inert — the registry's
        # segment_cache stays None, the compile cache's persistent tier
        # never enables, no snapshot store exists — so the default
        # gateway is bit-identical r21 by construction.
        self.snapshot_store = None
        self.snapshot_counts: Dict[str, int] = {}
        ist = getattr(self.template, "imagestore", None)
        self.imagestore_enabled = bool(ist is not None and ist.active)
        if self.imagestore_enabled:
            # the cache_read fault seam fires through the registry's
            # cache; wire the gateway's injector in
            self.registry.compile_cache.faults = faults
            if ist.segmented:
                from wasmedge_tpu.imagestore import SegmentCache

                self.registry.segment_cache = SegmentCache()
            if ist.compile_cache:
                cc_dir = ist.compile_cache_dir or \
                    (self.durable.compile_cache_dir()
                     if self.durable is not None else None)
                self.registry.compile_cache.enable(cc_dir)
            if ist.snapshots:
                from wasmedge_tpu.hv.swapstore import SwapStore

                self.snapshot_store = SwapStore(dir=ist.snapshot_dir,
                                                faults=faults)
        # fleet federation (wasmedge_tpu/fleet/, r16): `fleet` is a
        # FleetConfig or a plain list of "host:port" peers.  The
        # controller starts when the HTTP layer binds (Gateway.start
        # knows the port); a fleet with no peers is inert — the submit
        # path and id sequence stay bit-identical to a non-federated
        # gateway.
        self.fleet = None
        if fleet is not None:
            from wasmedge_tpu.fleet import FleetConfig, FleetController

            cfg = fleet if isinstance(fleet, FleetConfig) \
                else FleetConfig(peers=list(fleet))
            self.fleet = FleetController(self, cfg)
        # live resharding (r21): reshards currently installing (health
        # reports them as churn, not degradation) + per-direction
        # totals (wasmedge_reshards_total{direction})
        self._resharding = 0
        self.reshard_counts: Dict[str, int] = {}
        # traffic-driven autoscale (r21): `autoscale` is an
        # AutoscaleConfig; the default (None / enabled=False) builds
        # no controller — behaviorally identical to r16
        self.autoscale = None
        if autoscale is not None:
            from wasmedge_tpu.gateway.autoscale import (AutoscaleConfig,
                                                        AutoscaleController)

            acfg = autoscale if isinstance(autoscale, AutoscaleConfig) \
                else AutoscaleConfig(**dict(autoscale))
            if acfg.enabled:
                self.autoscale = AutoscaleController(self, acfg).start()
        # integrity (r24): the at-rest scrubber re-verifies every
        # content-addressed byte this gateway holds — parked-session
        # swap blobs, compile-cache entries, checkpoint lineage
        # members — repairing from fleet peer replicas where it can
        # and evicting (forcing a fresh lower / older-member restore)
        # where it cannot.  Default off: the scrubber object does not
        # exist and no byte of behavior changes.
        self.scrubber = None
        integ = getattr(self.template, "integrity", None)
        if integ is not None and integ.scrub:
            from wasmedge_tpu.integrity import Scrubber

            self.scrubber = Scrubber(
                integ, obs=self.obs, faults=faults,
                swap_stores=self._scrub_swap_stores,
                checkpoints=self._scrub_checkpoints,
                compile_cache=lambda: (
                    self.registry.compile_cache
                    if self.imagestore_enabled
                    and self.registry.compile_cache.enabled else None),
                fetch_blob=lambda key: (
                    self.fleet.fetch_blob(key)
                    if self.fleet is not None else None),
                fetch_cache_entry=lambda sha: (
                    self.fleet.fetch_cache_entry(sha)
                    if self.fleet is not None else None))
            self.scrubber.start()   # inert unless scrub_interval_s > 0
        self._health = HealthGate(self)
        if resume:
            if self.durable is None:
                raise ValueError("resume=True requires a state_dir")
            self._resume_from_disk()

    # -- generations -------------------------------------------------------
    @property
    def current(self) -> Optional[_Generation]:
        with self._lock:
            return self._gens[-1] if self._gens else None

    @property
    def generation(self) -> int:
        with self._lock:
            return self._gens[-1].gen_id if self._gens else 0

    def device_info(self) -> dict:
        """Where the serving state lives (serve/server.py device_info);
        with no generation built yet, where its state will land."""
        from wasmedge_tpu.serve.server import device_info

        gen = self.current
        if gen is not None:
            return gen.server.device_info()
        return device_info(self.devices)

    def _make_generation(self, gen_id: int, serve_dir: Optional[str],
                         resume: bool) -> _Generation:
        """Pure build of generation `gen_id` (no shared-state commit
        and NO disk mutation — the timed wrapper may abandon this work
        on timeout, and the retry reuses `gen_id`, so an abandoned
        thread must not be able to touch the retry's live
        serve-checkpoint directory)."""
        from wasmedge_tpu.serve.server import BatchServer

        if self.faults is not None:
            self.faults.fire("generation_build", generation=gen_id,
                             modules=self.registry.names)
        conf = copy.deepcopy(self.template)
        if conf.serve.autotune:
            # the tuner reads the drain-latency histograms: the flag
            # must flip BEFORE the engine captures its recorder, or
            # the engine holds NULL_RECORDER forever and autotune is a
            # silent no-op (the injected-engine path cannot fix this
            # up afterwards the way BatchServer's own build can)
            conf.obs.enabled = True
        if serve_dir is not None \
                and conf.serve.checkpoint_every_rounds is None:
            # durability implies a checkpoint cadence — resume has
            # nothing to adopt otherwise
            conf.serve.checkpoint_every_rounds = 1
        init_overlays = None
        snapshot_counts = None
        if self.snapshot_store is not None:
            # decode every registered module's post-init snapshot into
            # a plane overlay for this generation's initial_state; a
            # faulted/corrupt entry drops to template init replay for
            # that module (counted, never wrong state)
            from wasmedge_tpu.imagestore import decode_overlay

            snapshot_counts = self.snapshot_counts
            init_overlays = {}
            for rm in self.registry.modules_snapshot():
                if rm.snapshot is None:
                    continue
                ov = decode_overlay(rm, self.snapshot_store,
                                    faults=self.faults,
                                    counts=self.snapshot_counts)
                if ov is not None:
                    init_overlays[rm.name] = ov
        engine = self.registry.build_engine(
            conf, self.lanes, devices=self.devices,
            init_overlays=init_overlays,
            snapshot_counts=snapshot_counts)
        server = BatchServer(engine=engine,
                             weights=self.tenants.weights(),
                             quotas=self.tenants.quotas(),
                             faults=self.faults,
                             checkpoint_dir=serve_dir,
                             resume=resume,
                             resident_budgets=self.tenants
                             .resident_budgets())
        return _Generation(gen_id, engine, server, self.registry.names,
                           serve_dir=serve_dir)

    def _build_generation_timed(self) -> _Generation:
        """Build the next generation against `build_timeout_s` on a
        worker thread, so one wedged compile cannot hold the
        registration lock forever.  A timed-out build is abandoned
        (daemon thread; it commits nothing and mutates no disk state —
        the serve-dir wipe happens HERE, on the caller thread, before
        the worker starts) and surfaces as a retryable
        GenerationBuildFailed; only a build that returns in time
        commits the generation counters."""
        gen_id = self._gen_seq + 1   # under _reg_lock: race-free
        serve_dir = None
        resume = False
        if self._pending_resume is not None:
            # the resume boot generation adopts the previous process's
            # serve-checkpoint lineage (in-flight requests come back)
            serve_dir, resume = self._pending_resume, True
        elif self.durable is not None:
            serve_dir = self.durable.serve_dir_for(gen_id)
            # a non-resume generation owns a FRESH lineage: stale
            # serve-*.npz from an earlier process in this slot would
            # otherwise be adoptable by the NEXT resume as phantom state
            import shutil

            shutil.rmtree(serve_dir, ignore_errors=True)
        timeout = self.build_timeout_s
        if timeout is None:
            gen = self._make_generation(gen_id, serve_dir, resume)
        else:
            box: dict = {}
            done = threading.Event()

            def build():
                try:
                    box["gen"] = self._make_generation(gen_id,
                                                       serve_dir,
                                                       resume)
                except BaseException as e:
                    box["err"] = e
                finally:
                    done.set()

            t = threading.Thread(target=build, daemon=True,
                                 name=f"gw-build-gen{gen_id}")
            t.start()
            if not done.wait(float(timeout)):
                raise GenerationBuildFailed(
                    f"generation {gen_id} build exceeded the "
                    f"{timeout}s build timeout")
            err = box.get("err")
            if err is not None:
                if isinstance(err, (KeyboardInterrupt, SystemExit)):
                    raise err
                raise GenerationBuildFailed(
                    f"generation {gen_id} build failed: {err!r}") from err
            gen = box["gen"]
        self._gen_seq = gen_id
        self.counters["generations"] += 1
        return gen

    def _swap_in(self, gen: _Generation):
        """Install `gen` as current; the displaced generation drains in
        the background (its in-flight lanes finish on the old image at
        their own launch boundaries) and is reaped once idle.  The
        `generation_swap` fault seam fires BEFORE the server starts or
        the pointer moves — an injected swap fault rolls back with the
        submit pointer untouched, never half-swapped."""
        if self.faults is not None:
            self.faults.fire("generation_swap", generation=gen.gen_id,
                             modules=list(gen.modules))
        gen.server.start()
        with self._lock:
            old = self._gens[-1] if self._gens else None
            self._gens.append(gen)
        if old is not None:
            t = threading.Thread(target=self._drain_old, args=(old,),
                                 name=f"gw-drain-gen{old.gen_id}",
                                 daemon=True)
            t.start()
            self._reapers.append(t)
        self.obs.instant("generation_swap", cat="gateway",
                         track="gateway", generation=gen.gen_id,
                         modules=list(gen.modules))

    def _drain_old(self, old: _Generation):
        try:
            old.server.shutdown(drain=True)
        finally:
            with self._lock:
                if old in self._gens:
                    self._gens.remove(old)
            if self.durable is not None and old.serve_dir \
                    and not any(g.serve_dir == old.serve_dir
                                for g in self._gens):
                self.durable.drop_serve_dir(old.serve_dir)

    # -- module registration ----------------------------------------------
    def register_module(self, name: str, wasm_bytes: Optional[bytes] = None,
                        inst=None, store=None,
                        source: str = "http",
                        tenant: Optional[str] = None) -> dict:
        """Register a module and swap in a fresh generation.  Either
        raw `wasm_bytes` (the HTTP path: full validation pipeline) or a
        pre-instantiated (inst, store) pair (the VM/CLI boot path).
        `tenant` selects the static-analysis admission policy (the
        tenant's own, else the file-level default)."""
        return self._register([(name, wasm_bytes, inst, store, tenant)],
                              source=source, vet_tenant=tenant)

    def preload(self, entries, source: str = "boot") -> dict:
        """Register several modules with ONE generation build — the
        boot path (`--module a=.. --module b=..`) must not pay for and
        immediately drain N-1 throwaway generations.  `entries` is
        [(name, wasm_bytes)]."""
        return self._register(
            [(n, b, None, None, None) for n, b in entries],
            source=source)

    def _vet(self, rm, tenant: Optional[str]) -> List[dict]:
        """Static-analysis admission: evaluate the already-built
        image's ModuleAnalysis (one lowering — shared with the
        batchability probe) against the registering tenant's policy.
        Raises AnalysisRejection in enforce mode; returns the
        violation list in flag mode (surfaced as analysis_warnings).

        Boot/preload registrations (tenant None — the CLI --module
        set, VM.gateway()) are operator-trusted and only COUNTED, never
        policy-gated: a strict file-level default aimed at runtime
        HTTP registrants must not abort gateway startup on the
        operator's own modules."""
        from wasmedge_tpu.analysis.policy import AnalysisRejection

        analysis = getattr(rm.engine.img, "analysis", None)
        with self._lock:
            if analysis is not None:
                key = "bounded" if analysis.bounded else "unbounded"
                self.analysis_counts[key] += 1
        if tenant is None:
            return []
        policy = self.tenants.admission_policy(tenant)
        if policy is None:
            return []
        violations = policy.evaluate(analysis)
        if violations and policy.enforce:
            with self._lock:
                self.counters["policy_rejected"] += 1
                self.analysis_counts["policy_rejected"] += 1
            raise AnalysisRejection(rm.name, violations)
        return violations

    def _register(self, entries, source: str,
                  vet_tenant: Optional[str] = None) -> dict:
        """One registration transaction: add -> vet -> timed build ->
        swap -> persist.  Every failure before the pointer swap rolls
        back ATOMICALLY (registry stash kept, prior generation serving
        bit-identically); build/swap infrastructure failures surface
        as a retryable GenerationBuildFailed (HTTP 503), while the
        wasm/policy taxonomy of the add/vet phase passes through
        unchanged (400s)."""
        with self._reg_lock:
            if self._closed:
                raise GatewayClosed()
            if self.faults is not None:
                self.faults.fire("gateway_register",
                                 names=[e[0] for e in entries])
            added = []
            warnings: List[dict] = []
            try:
                for name, wasm_bytes, inst, store, owner in entries:
                    if wasm_bytes is not None:
                        rm = self.registry.add_wasm(name, wasm_bytes,
                                                    source=source,
                                                    tenant=owner)
                    else:
                        rm = self.registry.add_instance(name, inst,
                                                        store,
                                                        source=source)
                    added.append((rm, wasm_bytes))
                    warnings.extend(self._vet(rm, vet_tenant))
            except BaseException:
                # never leave a module registered that no generation
                # serves — the registry and the serving set must agree.
                # stash=True parks the already-lowered engine in the
                # registry's probe cache: a re-POST of the same bytes
                # (fixed policy, different tenant/name) reuses it
                # instead of lowering twice
                for rm, _ in added:
                    self.registry.remove(rm.name, stash=True)
                raise
            if self.snapshot_store is not None:
                # one-time init run per freshly-added module: capture
                # the post-_start plane columns as a content-addressed
                # snapshot (imagestore/snapshot.py).  Best-effort — a
                # module with no init export, a parked/trapped init, or
                # a store failure just admits through template init.
                # A probe-cache re-adoption keeps its earlier capture.
                from wasmedge_tpu.imagestore import capture_snapshot

                ist = self.template.imagestore
                for rm, _ in added:
                    if rm.snapshot is not None:
                        continue
                    try:
                        rm.snapshot = capture_snapshot(
                            rm, self.snapshot_store,
                            self.snapshot_counts,
                            max_steps=ist.snapshot_init_max_steps)
                    except (KeyboardInterrupt, SystemExit):
                        raise
                    except Exception:
                        self.snapshot_counts["skipped"] = \
                            self.snapshot_counts.get("skipped", 0) + 1
            try:
                gen = self._build_generation_timed()
                self._swap_in(gen)
            except BaseException as e:
                for rm, _ in added:
                    self.registry.remove(rm.name, stash=True)
                self._note_rollback(e)
                if isinstance(e, (KeyboardInterrupt, SystemExit,
                                  GatewayClosed, GenerationBuildFailed)):
                    raise
                raise GenerationBuildFailed(
                    f"generation swap failed: {e!r}") from e
            self.last_swap = {"ok": True, "generation": gen.gen_id,
                              "error": None, "t": time.monotonic()}
            durable_ok = self._persist_registration(added, gen)
            if self.fleet is not None:
                # keep blob bytes servable to peers (non-durable
                # gateways have no disk copy to answer
                # GET /v1/fleet/modules/<sha> from)
                self.fleet.note_modules(added)
        with self._lock:
            self.counters["registered_modules"] += len(added)
        last = added[-1][0]
        out = {
            "module": last.name,
            "sha256": last.sha256,
            "exports": last.exported_funcs(),
            "generation": gen.gen_id,
            "modules": list(gen.modules),
        }
        if self.durable is not None:
            out["durable"] = durable_ok
        analysis = getattr(last.engine.img, "analysis", None)
        if analysis is not None:
            out["analysis"] = analysis.summary()
        if warnings:
            # flag-mode policy (enforce=false): registered, but the
            # violations ride the 201 body so operators can see them
            out["analysis_warnings"] = warnings
        return out

    def _note_rollback(self, exc: BaseException):
        with self._lock:
            self.counters["rollbacks"] += 1
        self.last_swap = {"ok": False, "generation": self.generation,
                          "error": repr(exc), "t": time.monotonic()}
        self.obs.instant("generation_rollback", cat="gateway",
                         track="gateway", error=repr(exc),
                         serving_generation=self.generation)

    # -- durability --------------------------------------------------------
    def _persist_registration(self, added, gen: _Generation) -> bool:
        """Module blobs + manifest, written BEFORE the 201 returns.  A
        failed write degrades health (and the body says durable:false)
        but does not un-swap the generation — the next successful
        durable write self-heals via the dirty flag, since every
        manifest is a full-set snapshot."""
        if self.durable is None:
            return True
        try:
            for rm, data in added:
                if data is not None and rm.sha256:
                    self.durable.save_module_bytes(rm.sha256,
                                                   bytes(data))
            self._write_manifest(gen)
            return True
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException:
            with self._lock:
                self.counters["journal_errors"] += 1
                self._journal_fail_streak += 1
                self._manifest_dirty = True
            return False

    def _write_manifest(self, gen: _Generation):
        mods = [{"name": rm.name, "sha256": rm.sha256,
                 "tenant": rm.tenant, "source": rm.source}
                for rm in self.registry.modules_snapshot()
                if rm.sha256]   # instance-registered modules (VM boot
        #                         path) have no bytes to restore from
        rel = os.path.relpath(gen.serve_dir, self.durable.dir) \
            if gen.serve_dir else None
        self.durable.write_manifest(mods, gen.gen_id, rel,
                                    self.counters["restarts"])
        self._manifest_dirty = False

    def _journal_snapshot(self):
        with self._lock:
            unresolved = []
            for r in self._requests.values():
                if r.future.done:
                    continue
                entry = {"id": r.id, "tenant": r.tenant,
                         "module": r.module, "func": r.func,
                         "args": list(r.args),
                         "deadline_s": r.deadline_s}
                if r.edge:
                    entry["edge"] = r.edge
                unresolved.append(entry)
            resolved = list(self._result_cache)
            # a resolved-but-not-yet-finalized async id (nobody polled
            # it HERE — its client may be polling a fleet peer) must
            # not vanish from the journal: it is no longer unresolved,
            # and without its outcome in the resolved cache a peer
            # adopting this journal after our death would answer 404
            # for an id we actually completed.  Include the outcome
            # inline; finalize() later re-appends it to the capped
            # cache idempotently (replay installs guard by id).
            seen = {e.get("id") for e in resolved}
            for r in self._requests.values():
                if r.future.done and not r.finalized \
                        and r.id not in seen:
                    try:
                        resolved.append(_resolved_entry(r))
                    except Exception:
                        pass
            max_id = max([self._resume_floor, self._max_issued]
                         + [r.id for r in self._requests.values()])
            # lower edge of the pruned-404 window: the smallest id
            # this gateway (or the lineage it resumed) ever issued
            mins = [self._min_issued]
            if self._resume_floor:
                mins.append(self._resume_min)
            min_id = min([m for m in mins if m] or [0])
        return unresolved, resolved, max_id, min_id

    def _journal_sync(self, strict_req: Optional[GatewayRequest] = None):
        """Write the request journal (and a dirty manifest, if one is
        owed).  With `strict_req`, a failed write WITHDRAWS that
        request's acceptance — pulled back out of the serving queue,
        its future rejected, and a retryable DurabilityError raised —
        so the gateway never issues a 202 id that would not survive a
        restart (and never burns a lane on work it disowned).  Without
        it (the finalize path), failures only degrade health.

        `_journal_mutex` serializes snapshot->write: two concurrent
        syncs could otherwise snapshot in one order and acquire the
        store's sequence numbers in the other, making an OLDER
        snapshot the authoritative (newest) journal and losing a
        durably-accepted id across a crash."""
        fleet = self.fleet if self.fleet is not None \
            and self.fleet.started else None
        if self.durable is None and fleet is None:
            return
        try:
            with self._journal_mutex:
                unresolved, resolved, max_id, min_id = \
                    self._journal_snapshot()
                if self.durable is not None:
                    if self._manifest_dirty:
                        cur = self.current
                        if cur is not None:
                            self._write_manifest(cur)
                    self.durable.write_journal(unresolved, resolved,
                                               max_id=max_id,
                                               min_id=min_id)
                self._repl_seq += 1
                seq = self._repl_seq
            if fleet is not None:
                # cross-host durability: a STRICT sync (the 202 path)
                # must land the snapshot on >=1 alive peer — total
                # failure raises and the acceptance is withdrawn
                # below, exactly like a failed local journal write.
                # The peer HTTP happens OUTSIDE _journal_mutex (one
                # slow peer must not stall every accept behind the
                # mutex); `seq` — drawn under the mutex, so ordered
                # like the disk writes — lets receivers discard an
                # out-of-order older snapshot (fleet on_journal)
                fleet.replicate(unresolved, resolved, max_id,
                                strict=strict_req is not None,
                                seq=seq)
            with self._lock:
                self._journal_fail_streak = 0
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:
            with self._lock:
                self.counters["journal_errors"] += 1
                self._journal_fail_streak += 1
            if strict_req is not None:
                self._withdraw(strict_req)
                err = DurabilityError(
                    f"request journal write failed: {e!r}")
                strict_req.future._reject(err)
                raise err from e

    def _withdraw(self, req: GatewayRequest):
        """Take back an acceptance that could not be made durable: the
        request comes OUT of the serving queue (if not yet admitted —
        the guest must not run work whose id the client was told never
        existed), out of the stash, and out of the received tally."""
        with self._lock:
            gen = next((g for g in self._gens
                        if g.gen_id == req.gen_id), None)
            if self._requests.pop(req.id, None) is not None:
                self.counters["received"] -= 1
        if gen is not None:
            gen.server.withdraw(req.id)

    def _resume_from_disk(self):
        """Crash/restart resume: re-register the stored module set
        under ONE boot generation (adopting the previous generation's
        serve-checkpoint lineage), then re-install the async-request
        journal — resolved ids replay from the durable result cache
        (exactly-once), everything else re-queues under its original id
        (at-least-once, README table)."""
        manifest, journal = self.durable.load()
        self.counters["restarts"] = \
            int((manifest or {}).get("restarts", 0)) + 1
        mods = (manifest or {}).get("modules") or []
        gen = None
        if mods:
            # continue the generation numbering so a fresh generation
            # in this process can never collide with (and later adopt)
            # a dead process's serve-checkpoint slot
            self._gen_seq = max(int(manifest.get("generation", 0)),
                                self._gen_seq)
            entries = []
            for m in mods:
                entries.append((m["name"],
                                self.durable.module_bytes(m["sha256"]),
                                None, None, m.get("tenant")))
            rel = manifest.get("serve_dir")
            self._pending_resume = \
                os.path.join(self.durable.dir, rel) if rel else None
            try:
                self._register(entries, source="resume")
            finally:
                self._pending_resume = None
            gen = self.current
        else:
            # nothing to restore; still make the restart count durable
            self.durable.write_manifest([], 0, None,
                                        self.counters["restarts"])
        self._restore_journal(journal or {}, gen)
        self.obs.instant("gateway_resume", cat="gateway",
                         track="gateway",
                         restarts=self.counters["restarts"],
                         modules=[m["name"] for m in mods],
                         resumed_requests=self.counters["resumed"])
        self._journal_sync()

    def _restore_journal(self, journal: dict, gen: Optional[_Generation]):
        from wasmedge_tpu.serve.queue import advance_request_ids

        floor = int(journal.get("max_id", 0))
        self._resume_min = max(int(journal.get("min_id", 0) or 1), 1)
        if floor:
            # every id at/below the floor was issued by a dead
            # process: unknown ones answer the pruned 404 detail, and
            # fresh ids must allocate above them
            self._resume_floor = floor
            advance_request_ids(floor)
        for entry in journal.get("resolved", []):
            # durable result cache: replay verbatim so a poll of an id
            # resolved before the crash is exactly-once observable
            self._result_cache.append(entry)
            self._install_replay(entry, gen)
        if gen is None:
            return
        adopted = dict(gen.server.adopted)
        with gen.server._lock:
            bind_by_id = {r.id: r
                          for r in gen.server._bindings.values()}
        for entry in journal.get("unresolved", []):
            rid = int(entry["id"])
            with self._lock:
                if rid in self._requests:
                    continue
            tenant = entry.get("tenant", "default")
            module = entry.get("module")
            func = entry.get("func", "")
            args = entry.get("args", [])
            fut = adopted.pop(rid, None)
            if fut is None:
                # accepted but not covered by the serve checkpoint:
                # re-queue under the ORIGINAL id.  At-least-once — the
                # guest may have partially run before the crash.  The
                # journaled deadline restarts its clock here: after a
                # restart, completing late beats expiring work the
                # client is still polling for.
                try:
                    fut = gen.server.submit(
                        func, args, tenant=tenant,
                        deadline_s=entry.get("deadline_s"),
                        request_id=rid)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    # unservable after resume (export gone from the
                    # restored set): machine-readable rejection, never
                    # a silently-lost id
                    from wasmedge_tpu.serve.queue import (
                        ServeFuture,
                        ServeRejected,
                    )

                    fut = ServeFuture(rid)
                    fut._reject(ServeRejected(
                        f"request {rid} could not be re-queued after "
                        f"gateway restart: {e}"))
                    advance_request_ids(rid)
            req = GatewayRequest(fut, tenant, module, func, gen.gen_id,
                                 time.monotonic(), args=args,
                                 deadline_s=entry.get("deadline_s"))
            with self._lock:
                self._requests[req.id] = req
                self._note_issued(req.id)
                self.counters["received"] += 1
                self.counters["resumed"] += 1
        # adopted serve-checkpoint requests the journal missed (a
        # faulted journal write raced the snapshot): wrap them too —
        # their futures resolve as the resumed serving loop finishes
        for rid, fut in adopted.items():
            with self._lock:
                if rid in self._requests:
                    continue
            sr = bind_by_id.get(rid)
            req = GatewayRequest(
                fut, sr.tenant if sr else "default", None,
                sr.func_name if sr else "", gen.gen_id,
                time.monotonic(),
                args=(sr.args if sr else ()))
            with self._lock:
                self._requests[req.id] = req
                self._note_issued(req.id)
                self.counters["received"] += 1
                self.counters["resumed"] += 1

    def _install_replay(self, entry: dict, gen: Optional[_Generation]):
        from wasmedge_tpu.serve.queue import ServeFuture, \
            advance_request_ids

        rid = int(entry["id"])
        with self._lock:
            if rid in self._requests:
                return
        fut = ServeFuture(rid)
        if entry.get("ok"):
            fut._resolve([int(c) for c in entry.get("result", [])])
        else:
            fut._reject(resolved_error(entry))
        advance_request_ids(rid)
        req = GatewayRequest(fut, entry.get("tenant", "default"), None,
                             entry.get("func", ""),
                             gen.gen_id if gen else 0, time.monotonic())
        # outcome counted by the PREVIOUS process; replay only
        req.finalized = True
        with self._lock:
            self._requests[rid] = req
            self._note_issued(rid)
            self._resolved.append(rid)

    # -- requests ----------------------------------------------------------
    def submit(self, func: str, args, module: Optional[str] = None,
               tenant: str = "default",
               deadline_s: Optional[float] = None) -> GatewayRequest:
        """Edge admission: rate limit, degraded-mode shedding, then the
        current generation's BatchServer.  Raises RateLimited,
        ShedLoad / QueueSaturated (retryable), KeyError (unknown
        module/func), DurabilityError (journal write failed — the id
        was never accepted), or the serving taxonomy."""
        from wasmedge_tpu.gateway.health import ShedLoad
        from wasmedge_tpu.gateway.tenants import RateLimited

        try:
            self.tenants.check_rate(tenant)
        except RateLimited:
            with self._lock:
                self.counters["rate_limited"] += 1
            raise
        try:
            self._health.maybe_shed(tenant)
        except ShedLoad:
            with self._lock:
                self.counters["shed"] += 1
                self.shed_counts[tenant] = \
                    self.shed_counts.get(tenant, 0) + 1
            self.obs.instant("shed", cat="gateway", track="gateway",
                             tenant=tenant)
            raise
        if self.fleet is not None and self.fleet.started:
            # consistent fleet routing (rendezvous hash on the request
            # id): the owner executes; a suspect owner refuses
            # retryably; no remote available falls through to the
            # plain local path (solo fallback, bit-identical)
            try:
                routed = self.fleet.maybe_route(
                    func, args, module=module, tenant=tenant,
                    deadline_s=deadline_s)
            except WasmError:
                with self._lock:
                    self.counters["rejected"] += 1
                raise
            if routed is not None:
                self.obs.instant("gateway_receive", cat="gateway",
                                 track="gateway", id=routed.id,
                                 tenant=tenant, func=routed.func)
                return routed
        return self._submit_local(func, args, module=module,
                                  tenant=tenant, deadline_s=deadline_s)

    def _submit_local(self, func: str, args,
                      module: Optional[str] = None,
                      tenant: str = "default",
                      deadline_s: Optional[float] = None,
                      request_id: Optional[int] = None,
                      edge: Optional[str] = None) -> GatewayRequest:
        """Queue on the LOCAL serving generation (edge policy already
        applied by submit(); the fleet's execute route calls this
        directly — the edge peer enforced its own policy before
        forwarding).  `request_id` submits under a fleet-allocated or
        forwarded ORIGINAL id; `edge` journals the accepting peer."""
        with self._lock:
            if self._closed:
                raise GatewayClosed()
            gen = self._gens[-1] if self._gens else None
        if gen is None:
            raise KeyError("no modules registered")
        qualified = f"{module}:{func}" if module else func
        t_recv = time.monotonic()
        while True:
            try:
                fut = gen.server.submit(qualified, args, tenant=tenant,
                                        deadline_s=deadline_s,
                                        request_id=request_id)
                break
            except WasmError:
                # a submit can race a generation swap: the generation
                # captured above starts DRAINING the moment its
                # successor is installed, and rejects submissions with
                # a permanent (non-retryable) error.  That rejection
                # belongs to the stale generation, not the request —
                # re-resolve and retry on the successor.  Only a
                # still-current generation's rejection is authoritative.
                with self._lock:
                    cur = self._gens[-1] if self._gens else None
                    closed = self._closed
                if cur is gen or cur is None:
                    with self._lock:
                        self.counters["rejected"] += 1
                    if closed:
                        # the generation rejected because the GATEWAY
                        # is going down, not because of the tenant's
                        # policy — surface the lifecycle class (503)
                        raise GatewayClosed() from None
                    raise
                gen = cur
            except BaseException:
                with self._lock:
                    self.counters["rejected"] += 1
                raise
        req = GatewayRequest(fut, tenant, module, qualified, gen.gen_id,
                             t_recv, args=args, deadline_s=deadline_s,
                             edge=edge)
        with self._lock:
            self.counters["received"] += 1
            self._requests[req.id] = req
            self._note_issued(req.id)
        # the acceptance is not real until it is durable: a journal
        # write failure rejects THIS request retryably (the id was
        # never handed out, so a restart owes nothing for it)
        self._journal_sync(strict_req=req)
        self.obs.instant("gateway_receive", cat="gateway",
                         track="gateway", id=req.id, tenant=tenant,
                         func=qualified)
        return req

    def _note_issued(self, rid: int):
        """Track the id range this gateway has stashed (callers hold
        self._lock); journaled so the resumed pruned-404 window is
        exactly [min_id, max_id], not 'everything below the counter'."""
        rid = int(rid)
        self._max_issued = max(self._max_issued, rid)
        if self._min_issued == 0 or rid < self._min_issued:
            self._min_issued = rid

    # -- fleet seams (wasmedge_tpu/fleet/federation.py) --------------------
    def _stash_request(self, fut, tenant, module, qualified, args,
                       deadline_s, edge=None) -> GatewayRequest:
        """Register an acceptance whose EXECUTION lives elsewhere (a
        forwarded request): same stash/counters as a local submit, no
        server involvement."""
        req = GatewayRequest(fut, tenant, module, qualified,
                             self.generation, time.monotonic(),
                             args=args, deadline_s=deadline_s,
                             edge=edge)
        with self._lock:
            self.counters["received"] += 1
            self._requests[req.id] = req
            self._note_issued(req.id)
        return req

    def _relink_future(self, req: GatewayRequest, fut):
        """Bridge a fresh server future into the future the client's
        202 was issued against (fleet local-fallback: the re-queued
        request resolves the ORIGINAL handle)."""
        fut.mirror(req.future)

    def _wrap_foreign(self, fut, entry: dict, gen) -> GatewayRequest:
        """Stash a request adopted from a peer (migration/execute):
        polls against THIS gateway answer for it from now on."""
        req = GatewayRequest(fut, entry.get("tenant", "default"), None,
                             entry.get("func", ""),
                             gen.gen_id if gen else 0,
                             time.monotonic(),
                             args=tuple(entry.get("args", ())),
                             deadline_s=entry.get("deadline_s"),
                             edge=entry.get("edge"))
        with self._lock:
            if req.id in self._requests:
                return self._requests[req.id]
            self.counters["received"] += 1
            self._requests[req.id] = req
            self._note_issued(req.id)
        return req

    def adopt_foreign(self, entry: dict, src: str = "") -> GatewayRequest:
        """Failover adoption of one unresolved journal entry from a
        DEAD peer: re-queue under the ORIGINAL id (at-least-once — the
        dead peer may have partially run it).  Unservable entries
        reject machine-readably; an id is never silently lost."""
        from wasmedge_tpu.serve.queue import (
            ServeFuture,
            ServeRejected,
            advance_request_ids,
        )

        rid = int(entry["id"])
        with self._lock:
            if rid in self._requests:
                return self._requests[rid]
        gen = self.current
        fut = None
        if gen is not None:
            try:
                fut = gen.server.submit(
                    entry.get("func", ""), entry.get("args", []),
                    tenant=entry.get("tenant", "default"),
                    deadline_s=entry.get("deadline_s"),
                    request_id=rid)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception:
                fut = None
        if fut is None:
            fut = ServeFuture(rid)
            fut._reject(ServeRejected(
                f"request {rid} adopted from dead peer {src!r} could "
                f"not be re-queued"))
            advance_request_ids(rid)
        req = self._wrap_foreign(fut, entry, gen)
        with self._lock:
            self.counters["resumed"] += 1
        return req

    def get_request(self, request_id: int) -> Optional[GatewayRequest]:
        with self._lock:
            req = self._requests.get(int(request_id))
        if req is not None:
            self.finalize(req)
        return req

    def request_state(self, request_id: int):
        """('ok', req) for a live/stash-resident id, ('pruned', None)
        for an id whose resolved entry aged out of the result cache
        (the HTTP layer's distinct 404 detail — a client that cached a
        202 can tell "aged out" from "never existed"), ('unknown',
        None) otherwise."""
        rid = int(request_id)
        with self._lock:
            req = self._requests.get(rid)
            # ids inside the resumed [min_id, max_id] window were
            # issued by a pre-crash process: anything unknown there
            # has aged out, it did not "never exist".  The window has
            # a LOWER edge too — fleet id-space rebasing means ids do
            # not start near 1, and an id below everything this
            # lineage ever issued really is unknown
            pruned = req is None and (
                rid in self._pruned_set
                or self._resume_min <= rid <= self._resume_floor)
        if req is not None:
            self.finalize(req)
            return "ok", req
        return ("pruned" if pruned else "unknown"), None

    def wake(self, request_id: int,
             payload: Optional[bytes] = None,
             _forward: bool = True) -> dict:
        """Deliver an external wake to a (possibly parked) request —
        the POST /v1/requests/<id>/wake body rides to the guest's
        await_event return buffer.  At-least-once: the wake queues
        even when the id is not currently parked (it pre-delivers at
        the request's next await_event), so a wake racing the park is
        never lost.

        Fleet-routed (r24): when this member does not know the id and
        a fleet is active, the wake forwards to the id's rendezvous
        owner over the r16 routing table — any member is a valid edge
        for POST /v1/requests/<id>/wake.  `_forward=False` marks an
        already-forwarded arrival (FleetController.on_wake) so a
        misrouted wake can never loop."""
        rid = int(request_id)
        gen = self.current
        if gen is None:
            raise KeyError(f"no serving generation to wake request "
                           f"{rid}")
        state = gen.server.wake(rid, payload)
        if state == "unknown" and _forward and self.fleet is not None:
            fwd = self.fleet.route_wake(rid, payload)
            if fwd is not None:
                self.obs.instant("gateway_wake", cat="gateway",
                                 track="gateway", id=rid,
                                 state="forwarded",
                                 owner=fwd.get("owner"),
                                 nbytes=len(payload or b""))
                return fwd
        self.obs.instant("gateway_wake", cat="gateway",
                         track="gateway", id=rid, state=state,
                         nbytes=len(payload or b""))
        return {"ok": True, "request_id": rid, "state": state}

    def stream_of(self, request_id: int):
        """The request's stdout StreamBuf (None when the effects
        subsystem is off or no generation serves) — the
        GET /v1/requests/<id>/stream handler blocks on it."""
        gen = self.current
        if gen is None:
            return None
        return gen.server.stream_of(int(request_id))

    def wait(self, req: GatewayRequest,
             timeout_s: Optional[float] = None) -> bool:
        """Block on the request's future (the sync-invoke path); the
        gateway-level cap applies when the caller sets none."""
        done = req.future.wait(self.sync_wait_s if timeout_s is None
                               else timeout_s)
        if done:
            self.finalize(req)
        return done

    def finalize(self, req: GatewayRequest, journal: bool = True):
        """Account + trace a completed request exactly once (called
        from every path that observes completion, and by the pruning
        sweep for never-polled async requests).  `journal=False` lets
        a batch caller (sweep) coalesce many resolutions into one
        durable write."""
        if req.finalized or not req.future.done:
            return
        with self._lock:
            if req.finalized:
                return
            req.finalized = True
            self._resolved.append(req.id)
            err = req.future.error
            from wasmedge_tpu.serve.queue import DeadlineExceeded

            if err is None:
                self.counters["completed"] += 1
            elif isinstance(err, DeadlineExceeded):
                self.counters["deadline"] += 1
            else:
                self.counters["failed"] += 1
            if self.durable is not None or self.fleet is not None:
                # the durable result cache also feeds the FLEET's
                # replicated journal: peers replay these exactly-once
                # when this gateway dies, so fleet-only (no state_dir)
                # gateways populate it too
                try:
                    self._result_cache.append(_resolved_entry(req))
                except Exception:
                    pass   # an unserializable outcome never blocks
                #            finalization; the entry just isn't cached
            while len(self._resolved) > self.result_cache:
                pruned_id = self._resolved.popleft()
                self._requests.pop(pruned_id, None)
                # remember the id as PRUNED (bounded memory) so a late
                # poll draws the distinct 404 detail, not "unknown id"
                if len(self._pruned) == self._pruned.maxlen:
                    self._pruned_set.discard(self._pruned[0])
                self._pruned.append(pruned_id)
                self._pruned_set.add(pruned_id)
        # journal the resolution (never strict: a completed request's
        # durability failure degrades health, it cannot un-complete)
        if journal:
            self._journal_sync()
        self.obs.span(f"gateway/{req.tenant}", req.t_recv,
                      cat="gateway", track="gateway", id=req.id,
                      func=req.func, generation=req.gen_id,
                      ok=req.future.error is None)

    def sweep(self):
        """Finalize any resolved-but-unpolled async requests (keeps the
        gateway spans/counters complete without a per-future callback
        seam; called from status/metrics)."""
        with self._lock:
            pending = [r for r in self._requests.values()
                       if not r.finalized and r.future.done]
        for r in pending:
            self.finalize(r, journal=False)
        if pending:
            self._journal_sync()   # one durable write for the batch

    # -- edge accounting ---------------------------------------------------
    def count_http(self, code: int):
        with self._lock:
            key = str(int(code))
            self.http_counts[key] = self.http_counts.get(key, 0) + 1

    # -- integrity (r24) ---------------------------------------------------
    def _scrub_swap_stores(self):
        """(kind, store, evict_on_fail) triples for the scrubber.  The
        hv/effects stores never evict: their get() already refuses rot
        and checkpoints embed payload copies, so an unrepairable entry
        is counted and left for the restore path to route around.  The
        snapshot store DOES evict — a rotted pre-initialized snapshot
        silently poisons every lane built from it, and eviction just
        costs one init replay."""
        out, seen = [], set()
        gen = self.current
        if gen is not None:
            srv = gen.server
            if srv.hv is not None and srv.hv.store is not None:
                out.append(("hv", srv.hv.store, False))
                seen.add(id(srv.hv.store))
            if srv.effects is not None \
                    and srv.effects.store is not None \
                    and id(srv.effects.store) not in seen:
                out.append(("effects", srv.effects.store, False))
                seen.add(id(srv.effects.store))
        if self.snapshot_store is not None \
                and id(self.snapshot_store) not in seen:
            out.append(("snapshot", self.snapshot_store, True))
        return out

    def _scrub_checkpoints(self):
        """Checkpoint lineage member paths of the current generation
        (real on-disk files only)."""
        gen = self.current
        if gen is None:
            return []
        with gen.server._lock:
            members = list(gen.server._lineage.members)
        return [m.path for m in members
                if isinstance(m.path, (str, os.PathLike))
                and os.path.isfile(m.path)]

    def scrub_once(self) -> Optional[dict]:
        """One synchronous at-rest scrub pass (the cadence thread runs
        the same walk); None when the scrubber is off."""
        if self.scrubber is None:
            return None
        return self.scrubber.scrub_once()

    def integrity_stats(self) -> Optional[dict]:
        """The /v1/status "integrity" block: shadow-audit verdicts +
        device quarantine from the serving generation, scrub totals
        from the gateway-wide scrubber.  None when the whole subsystem
        is off — the default status body is bit-identical r23."""
        out = {}
        gen = self.current
        if gen is not None:
            audit = gen.server.integrity_stats()
            if audit is not None:
                out.update(audit)
        if self.scrubber is not None:
            out["scrub"] = self.scrubber.snapshot()
        return out or None

    # -- introspection -----------------------------------------------------
    def reshard(self, n_devices: Optional[int] = None,
                devices=None) -> dict:
        """Live-reshard the CURRENT generation onto a new device set
        (r21 tentpole leg b) — no drain, no re-queue: resident lanes
        ride through with their state bit-identical (grow-only lane
        pool; a device SHRINK keeps the lane width and re-splits it
        across fewer devices).  Future generations build at the new
        geometry too.  A mid-install fault rolls the server back onto
        the old mesh and this raises — the gateway keeps serving at
        the OLD geometry."""
        import jax

        from wasmedge_tpu.parallel.mesh import normalize_devices

        if devices is not None:
            devs = normalize_devices(devices)
        else:
            n = 1 if n_devices is None else int(n_devices)
            if n < 1:
                raise ValueError("n_devices must be positive")
            avail = jax.devices()
            if n > len(avail):
                raise ValueError(
                    f"reshard wants {n} devices, only {len(avail)} "
                    f"visible")
            devs = normalize_devices(avail[:n])
        gen = self.current
        if gen is None:
            raise RuntimeError("no serving generation to reshard")
        old_ndev = len(self.devices) if self.devices else 1
        # health surfaces in-flight reshards as churn (not
        # degradation) while the install runs
        with self._lock:
            self._resharding += 1
        try:
            out = gen.server.reshard(devices=devs)
        finally:
            with self._lock:
                self._resharding -= 1
        direction = "grow" if len(devs) >= old_ndev else "shrink"
        with self._lock:
            # future generations (module registrations trigger a fresh
            # build) inherit the new geometry
            self.devices = devs if len(devs) > 1 else None
            self.lanes = int(out["lanes"])
            self.reshard_counts[direction] = \
                self.reshard_counts.get(direction, 0) + 1
        self.obs.instant("gateway_reshard", cat="gateway",
                         track="gateway", direction=direction,
                         devices=len(devs), old_devices=old_ndev,
                         lanes=out["lanes"], generation=gen.gen_id)
        return dict(out, direction=direction, generation=gen.gen_id)

    def health(self, fresh: bool = True) -> dict:
        """The truthful /healthz body (gateway/health.py): driver
        liveness, last-swap outcome, queue saturation, checkpoint +
        journal write health -> healthy / degraded / unhealthy."""
        return self._health.health(fresh=fresh)

    def status(self) -> dict:
        self.sweep()
        with self._lock:
            gen = self._gens[-1] if self._gens else None
            draining = max(len(self._gens) - 1, 0)
            out = {
                "generation": gen.gen_id if gen else 0,
                "modules": {
                    name: self.registry.get(name).exported_funcs()
                    for name in (gen.modules if gen else ())},
                "lanes": self.lanes,
                "draining_generations": draining,
                "gateway": dict(self.counters),
                "analysis": dict(self.analysis_counts),
                "http": dict(self.http_counts),
                "tenants": sorted(self.tenants.policies),
                "shed": dict(self.shed_counts),
                "last_swap": dict(self.last_swap)
                if self.last_swap else None,
                "durable": self.durable is not None,
                "devices": len(self.devices) if self.devices else 1,
                "reshards": dict(self.reshard_counts),
                "resharding": self._resharding,
            }
            if gen is not None:
                out["queue_depth"] = len(gen.server.queue)
                out["in_flight"] = gen.server.in_flight
                out["serve"] = dict(gen.server.counters)
        out["device"] = self.device_info()
        if self.fleet is not None:
            out["fleet"] = dict(self.fleet.stats(),
                                peer_states=self.fleet.peer_states())
        if gen is not None:
            # resident/virtual occupancy (lane virtualization, hv/) —
            # absent when the gateway runs without oversubscription
            hv = gen.server.hv_stats()
            if hv is not None:
                out["hv"] = hv
            # parked-session occupancy (effects/) — absent when the
            # suspend subsystem is off, so the default status body
            # stays bit-identical to the pre-effects gateway
            sessions = gen.server.session_stats()
            if sessions is not None:
                out["sessions"] = sessions
        if self.autoscale is not None:
            out["autoscale"] = self.autoscale.stats()
        if self.imagestore_enabled:
            # cold-start telemetry (r22): present only when a knob is
            # on, so the default status body stays bit-identical r21
            sc = self.registry.segment_cache
            out["coldstart"] = {
                "compile_cache": self.registry.compile_cache.stats(),
                "segments": sc.stats() if sc is not None else None,
                "snapshots": dict(self.snapshot_counts),
                "lowered_count": self.registry.lowered_count,
            }
        integ = self.integrity_stats()
        if integ is not None:
            # integrity telemetry (r24): absent unless audit/scrub is
            # on, so the default status body stays bit-identical r23
            out["integrity"] = integ
        out["health"] = self.health()
        return out

    def metrics_text(self) -> str:
        self.sweep()
        from wasmedge_tpu.obs.metrics import render_prometheus

        gen = self.current
        with self._lock:
            gateway_counts = {
                "restarts": self.counters["restarts"],
                "rollbacks": self.counters["rollbacks"],
            }
            shed_counts = dict(self.shed_counts)
            reshard_counts = dict(self.reshard_counts)
        return render_prometheus(
            recorder=self.obs if self.obs.enabled else None,
            hostcall_stats=gen.engine.hostcall_stats if gen else None,
            http_requests=dict(self.http_counts),
            analysis_counts=dict(self.analysis_counts),
            gateway_counts=gateway_counts,
            shed_counts=shed_counts,
            hv_stats=gen.server.hv_stats() if gen else None,
            session_stats=gen.server.session_stats() if gen else None,
            fleet_stats=self.fleet.stats()
            if self.fleet is not None else None,
            reshard_counts=reshard_counts or None,
            autoscale_actions=dict(self.autoscale.actions)
            if self.autoscale is not None else None,
            compile_cache_counts=dict(self.registry.compile_cache.counts)
            if self.imagestore_enabled else None,
            snapshot_counts=dict(self.snapshot_counts)
            if self.snapshot_store is not None else None,
            integrity_stats=self.integrity_stats())

    # -- lifecycle ---------------------------------------------------------
    def shutdown(self, drain: bool = True,
                 timeout_s: Optional[float] = None):
        # _reg_lock first: an in-flight registration finishes its swap
        # (its generation lands in the snapshot below) and later ones
        # see _closed — otherwise a generation swapped in after the
        # snapshot would keep serving on registry fds close() is about
        # to invalidate, while shutdown() reports a clean stop
        with self._reg_lock:
            with self._lock:
                self._closed = True
                gens = list(self._gens)
        if self.autoscale is not None:
            self.autoscale.stop()
        if self.scrubber is not None:
            self.scrubber.stop()
        if self.fleet is not None:
            self.fleet.stop()
        for g in gens:
            g.server.shutdown(drain=drain, timeout_s=timeout_s)
        for t in self._reapers:
            t.join(timeout=5.0)
        self.sweep()
        self._journal_sync()   # the journal reflects the final state
        self.registry.close()

    def kill(self):
        """Simulated SIGKILL (the chaos harness's supported in-process
        crash): stop every serving thread WITHOUT draining, rejecting
        futures, or flushing the journal — exactly the state a real
        kill -9 leaves on disk, so `GatewayService(resume=True)` over
        the same state_dir is the honest recovery test.  Registry fds
        are closed (a real dead process drops them too)."""
        with self._lock:
            self._closed = True   # later registrations see it and stop
        if self.autoscale is not None:
            self.autoscale.stop()
        if self.scrubber is not None:
            self.scrubber.stop()
        if self.fleet is not None:
            # a killed process's heartbeats just STOP (no goodbye, no
            # final replication) — peers discover the death the honest
            # way, through the suspect→dead state machine
            self.fleet.stop()
        with self._reg_lock:
            pass   # let an in-flight registration's swap finish or fail
        with self._lock:
            gens = list(self._gens)
        for g in gens:
            srv = g.server
            with srv._lock:
                srv._stop = True
                srv._draining = True
                srv._wake.notify_all()
            t = srv._thread
            if t is not None:
                t.join(timeout=30.0)
        self.registry.close()
