"""Configure: proposals, host registrations, engine selection, runtime knobs.

Mirrors the reference Configure (/root/reference/include/common/configure.h:
173-260): a proposal bitset with the same defaults, host-registration set,
and sub-configs. The TPU-native addition is `EngineKind` — the engine-switch
seam the north star requires (interpreter / batch TPU / native scalar),
playing the role of the reference's interpreter/AOT selection.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional


class Proposal(enum.Enum):
    ImportExportMutGlobals = "mutable-globals"
    NonTrapFloatToIntConversions = "nontrap-f2i"
    SignExtensionOperators = "sign-extension"
    MultiValue = "multi-value"
    BulkMemoryOperations = "bulk-memory"
    ReferenceTypes = "reference-types"
    SIMD = "simd"
    TailCall = "tail-call"
    MultiMemories = "multi-memories"
    Annotations = "annotations"
    Memory64 = "memory64"
    ExceptionHandling = "exception-handling"
    Threads = "threads"
    FunctionReferences = "function-references"

    @property
    def gate_name(self) -> str:
        return self.value


# Defaults match the reference (configure.h:175-183).
DEFAULT_PROPOSALS = frozenset(
    {
        Proposal.ImportExportMutGlobals,
        Proposal.NonTrapFloatToIntConversions,
        Proposal.SignExtensionOperators,
        Proposal.MultiValue,
        Proposal.BulkMemoryOperations,
        Proposal.ReferenceTypes,
        Proposal.SIMD,
    }
)


class HostRegistration(enum.Enum):
    Wasi = "wasi"
    WasmEdgeProcess = "wasmedge_process"


class EngineKind(enum.Enum):
    SCALAR = "scalar"  # Python reference interpreter (oracle)
    NATIVE = "native"  # C++ scalar engine over the lowered image
    TPU_BATCH = "tpu_batch"  # SIMT lockstep JAX/Pallas engine
    AUTO = "auto"  # batch when module is batchable, else native/scalar


@dataclasses.dataclass
class RuntimeConfigure:
    max_memory_pages: int = 65536
    max_call_depth: int = 2048
    max_value_stack: int = 65536


@dataclasses.dataclass
class StatisticsConfigure:
    instr_counting: bool = False
    cost_measuring: bool = False
    time_measuring: bool = False
    cost_limit: int = (1 << 64) - 1


@dataclasses.dataclass
class BatchConfigure:
    """Knobs for the tpu_batch engine (no analog in the reference)."""

    lanes: int = 4096  # instances per chip
    value_stack_depth: int = 1024  # 64-bit slots per lane
    call_stack_depth: int = 512  # frames per lane
    memory_pages_per_lane: int = 1  # 64 KiB pages of linear memory per lane
    # table.grow capacity cap per lane (like memory_pages_per_lane: a
    # static HBM ceiling; grow beyond it returns -1, which the spec
    # allows at any size)
    table_elems_per_lane: int = 4096
    steps_per_launch: int = 1024  # device steps per host-loop iteration
    fuel_per_launch: Optional[int] = None  # per-lane fuel budget (gas analog)
    # per-opcode gas weights (Statistics cost-table bridge, set by the
    # VM/C-API batch entries when cost measuring is on; None = flat 1)
    cost_table: Optional[tuple] = None
    uniform: bool = True  # converged-lane fast path (scalar PC dispatch)
    interpret: bool = False  # run Pallas kernels in interpreter mode
    # Pallas warp-interpreter selection: None = auto (on whenever the
    # backend is TPU and the module fits the kernel's geometry), True =
    # force (interpret-mode on CPU), False = always per-step XLA.
    use_pallas: Optional[bool] = None
    # Pallas linear-memory placement: None = auto (HBM-resident plane +
    # VMEM window cache whenever that enlarges the lane block), True/False
    # force.  Only meaningful for modules with a memory.
    mem_hbm: Optional[bool] = None
    # Optimistic convergence (lane-0 decisions + canary validation at
    # commit points instead of per-instruction cross-lane reductions).
    # None = on; False forces the per-step-checked ("careful") kernel.
    optimistic: Optional[bool] = None
    # --- SIMT-tier superinstruction fusion (batch/fuse.py) ---
    # Rewrite the analyzer's top straight-line candidates into fused
    # dispatch cells at image-build time: ONE _make_step dispatch
    # retires the whole run's stack effects (each constituent op keeps
    # its op_id for gas/opcode-histogram attribution).  Off compiles
    # the bit-identical seed per-op step; results are bit-identical
    # either way (pinned against the scalar engine and the unfused
    # SIMT build, tests/test_fuse.py).
    fuse_superinstructions: bool = True
    # How many ranked analyzer candidates the translation pass consumes
    # (ModuleAnalysis.superinstructions order: saved_dispatches).
    fuse_top_k: int = 12
    # Distinct fused (class, sub) cell patterns compiled into one step
    # function (each pattern is a specialized straight-line handler;
    # more patterns = bigger traced step).
    fuse_max_patterns: int = 8
    # Down-weight fusion candidates whose occurrences sit in
    # high-divergence blocks (the analyzer's r12 per-block scores):
    # ranking key becomes saved_dispatches / (1 + bias * block_score).
    # 0.0 (the default) is bit-identical to unbiased planning.
    fuse_divergence_bias: float = 0.0
    # --- memory-run fusion (r19, batch/fuse.py + analysis/absint.py) ---
    # Fuse straight-line runs CONTAINING loads/stores whose every
    # access the abstract interpreter licensed (proven in-bounds
    # against the module's minimum memory and word-aligned — the run
    # can never trap): the fused cell does one gather/scatter per
    # access instead of the per-op three-word RMW window, and one
    # dispatch retires the whole run.  Unlicensed sites always stay on
    # the per-op path; results are bit-identical either way
    # (tests/test_memfuse.py).
    fuse_memory_runs: bool = True
    # Distinct fused memory-run patterns per image (on top of
    # fuse_max_patterns for the pure tier), and the per-run cell cap.
    memfuse_max_patterns: int = 8
    memfuse_max_run: int = 24
    # --- whole-function tier-up compilation (r20, batch/tierup.py) ---
    # Promote the hottest COMPILABLE whole functions out of the any-lane
    # dispatch switch: each promoted function becomes a lane-masked
    # jitted CFG body (block dispatch inside a bounded lax.while_loop,
    # trip bounds licensed by the r19 abstract interpreter) so a call
    # costs ONE dispatch instead of one per retired op.  Promotion is
    # conservative — leaf functions whose every op is pure-eligible or
    # an absint-licensed load, with a finite analyzer cost bound — and
    # unpromoted code keeps the per-op/fused path.  Off compiles the
    # bit-identical seed step by construction; results are bit-identical
    # either way (tests/test_tierup.py).
    tierup: bool = True
    # How many verdict-passing functions the planner promotes, ranked
    # hottest-first (realized fusion-run weight, then cost bound).
    tierup_top_k: int = 4
    # Compiled-body size caps: candidates whose CFG exceeds either cap
    # keep the interpreted path (bigger bodies = bigger traced step).
    tierup_max_blocks: int = 16
    tierup_max_ops: int = 128
    # --- divergence-aware lane compaction (batch/compact.py) ---
    # Sort/permute live lanes by (divergence-score bias, pc) at launch
    # boundaries via one jitted gather-permutation, packing live lanes
    # to a contiguous prefix (retired lanes stop occupying dispatch
    # width on fixed-cohort runs — the step loop narrows to the live
    # prefix).  Off (the default) compiles and executes the exact seed
    # path; results are bit-identical either way for lane-placement-
    # independent guests (tier-0 random_get keys on the physical lane
    # index — the recycling/hv scoping caveat).
    compact: bool = False
    # Anti-thrash quantum: at least this many launch boundaries between
    # compactions (the hv min_resident_rounds shape).
    compact_min_interval: int = 2
    # Sorting trigger: adjacent-key breaks removable by a sort must
    # exceed this fraction of the live lanes.
    compact_trigger: float = 0.05
    # Cost model: the estimated win (removable breaks x steps per
    # launch) must exceed factor x lane-width copy cost; 0 fires on
    # every eligible boundary (tests).
    compact_cost_factor: float = 4.0
    # Live-prefix dispatch-width narrowing (fixed-cohort runs, single
    # device): retraces the step per power-of-two width, so the floor
    # bounds compile count and the smallest useful slice.
    compact_narrow: bool = True
    compact_width_floor: int = 64
    # --- three-tier hostcall pipeline knobs (batch/hostcall.py) ---
    # Tier 0: service pure WASI calls (clock_time_get / random_get /
    # sched_yield / proc_exit / fd_write-to-buffered-stdout) directly in
    # the SIMT kernel — they cost a dispatch slot, not a device<->host
    # round trip.  False parks every hostcall on the outcall channel.
    tier0_hostcalls: bool = True
    # Seed for the in-kernel counter-PRNG behind tier-0 random_get
    # (deterministic per (seed, lane, call, word)).  None (the default)
    # draws fresh entropy once per Configure, so guests get
    # unpredictable bytes run-to-run like the os.urandom-backed scalar
    # and tier-1 paths; set an explicit seed for reproducible runs.
    rng_seed: Optional[int] = None
    # Per-lane in-device stdout record buffer, in 4-byte words (tier-0
    # fd_write appends records here; the host drains them at flush
    # points).  Writes that would overflow the buffer park on the
    # tier-1 channel instead (after a flush they fit again).
    stdout_buffer_words: int = 2048
    # Max bytes of one tier-0 fd_write iovec / random_get request the
    # kernel services inline; longer requests park on tier 1.
    tier0_write_max: int = 256
    tier0_random_max: int = 64
    # Tier-1 vectorized drain: group parked lanes by hostcall and serve
    # each group with SoA-vectorized NumPy WASI implementations
    # (host/wasi/vectorized.py) instead of the per-lane Python loop.
    vectorized_hostcalls: bool = True
    # v128 SIMT-residue quarantine (batch/scheduler.py): the XLA
    # per-step v128 fallback is known to fault TPU workers on very long
    # runs, so a divergent v128 tenant's residue is capped at this many
    # further steps; lanes still running at the cap re-run on the
    # scalar engine when side-effect-free, else trap CostLimitExceeded.
    # None disables the cap.
    v128_residue_step_cap: Optional[int] = 1_000_000


@dataclasses.dataclass
class ObsConfigure:
    """Knobs for the batch observability subsystem (wasmedge_tpu/obs/).

    When `enabled` is False every instrumentation seam holds the no-op
    NULL_RECORDER guard object — hot loops pay no per-step Python
    branching and no allocation (the bit-identical-output contract with
    the seed engines is pinned by tests/test_obs.py)."""

    # Master switch: create a FlightRecorder and report launch/serve/
    # split/checkpoint/failure events + hostcall latency histograms.
    enabled: bool = False
    # Bounded event ring capacity (oldest events dropped beyond it;
    # the drop count is exported).
    ring_capacity: int = 65536
    # Device-side per-opcode histogram plane (SIMT engine): one extra
    # [code_len] int32 plane scatter-incremented per step, folded into
    # per-opcode retired counts (Statistics cost_table domain) on sync.
    # Costs one scatter-add per step — leave off unless attributing
    # hot opcodes.
    opcode_histogram: bool = False
    # Export paths applied by VM.execute_batch / the CLI after a run
    # (None = no file export; the recorder stays queryable in-process).
    trace_out: Optional[str] = None
    metrics_out: Optional[str] = None
    # Lazily-created shared FlightRecorder (obs/recorder.py
    # recorder_of); identity is preserved across Configure deepcopies.
    _recorder: object = dataclasses.field(
        default=None, init=False, repr=False, compare=False)


@dataclasses.dataclass
class SupervisorConfigure:
    """Knobs for supervised batch execution (batch/supervisor.py).

    The supervisor wraps long-lived batch runs with automatic
    checkpointing, retry-with-backoff, and an engine-degradation ladder
    (Pallas -> jit SIMT -> gas-metered scalar); structured
    FailureRecords land in common/statistics.py."""

    # --- checkpoint cadence (batch/checkpoint.py snapshots) ---
    # Take a checkpoint every N retired-step slice boundary (rounded up
    # to whole steps_per_launch chunks).  None = no step cadence.
    checkpoint_every_steps: Optional[int] = None
    # ... or every S seconds of wall clock, whichever fires first.
    checkpoint_every_s: Optional[float] = None
    # Where snapshots land ("ckpt-<steps>.npz", written atomically via a
    # temp file + os.replace).  None with a cadence set auto-creates a
    # temp directory (recorded on the supervisor as .checkpoint_dir).
    checkpoint_dir: Optional[str] = None
    # Lineage depth: older snapshots beyond this count are pruned.  A
    # corrupted newest snapshot falls back to the next in the lineage.
    keep_checkpoints: int = 2
    # --- retry / backoff ---
    # Consecutive failed attempts (no forward progress) before the
    # current engine tier is abandoned and the run demotes a tier.
    max_retries: int = 3
    # Exponential backoff between retries: min(backoff_max_s,
    # backoff_base_s * backoff_factor**(attempt-1)).
    backoff_base_s: float = 0.05
    backoff_factor: float = 2.0
    backoff_max_s: float = 2.0
    # --- per-lane quarantine ---
    # A failure attributed to a concrete lane set (exceptions carrying a
    # .lanes attribute, e.g. from the fault-injection harness) repeating
    # this many times quarantines those lanes — demoted to the scalar
    # engine when the module is side-effect-free, else terminated with
    # ErrCode.Terminated — instead of sinking the whole batch.
    poison_lane_retries: int = 2
    # A lane still running after retiring this many instructions is
    # terminated (ErrCode.Terminated) and recorded as a "runaway" —
    # the generalization of the r6 v128_residue_step_cap quarantine.
    # None disables the cap.
    lane_step_cap: Optional[int] = None
    # --- ladder gates ---
    # Attempt the Pallas/BlockScheduler kernel tier first when eligible
    # (single-module, pallas enabled).  Checkpoint cadence only applies
    # on the SIMT tier, whose BatchState the checkpoint layer snapshots.
    use_kernel_tier: bool = True
    # Allow the bottom rung: whole-batch gas-metered scalar re-execution
    # (side-effect-free single-module batches only).
    allow_scalar_tier: bool = True
    # --- cross-process resume ---
    # Adopt an existing checkpoint_dir lineage at startup: scan for
    # ckpt-*.npz members, pick the newest that loads cleanly, and
    # record skipped/corrupt members as FailureRecord("checkpoint").
    # The run then continues from that snapshot on the SIMT tier (the
    # kernel tier cannot resume mid-state).  CLI: --resume.
    resume: bool = False
    # Attempt the single-program shard drive first on supervised mesh
    # runs (parallel/shard_drive.py: ONE jitted program over the named
    # mesh, lane planes sharded on the `lanes` axis).  Any shard-drive
    # failure demotes to the threaded per-device rungs below it;
    # cadence-configured (checkpointing) and resumed runs skip straight
    # to the per-device SIMT tier, whose states the coordinated
    # checkpoints snapshot.
    use_shard_drive: bool = True
    # --- mesh-level fault tolerance (parallel/supervisor.py) ---
    # Consecutive failed slices on ONE device of a supervised sharded
    # drive before that device is ejected from the mesh (its lanes
    # migrate to surviving devices).  Retries between failures back off
    # with the shared backoff_* formula above.
    max_device_retries: int = 2
    # Elastic shrink: eject a repeatedly-failing device and migrate its
    # lanes onto survivors.  False = fail fast instead — the whole mesh
    # run cancels cooperatively (sibling devices stop at their next
    # launch boundary) and raises with per-device attribution; some
    # operators prefer visible capacity loss over silent shrink.
    eject_devices: bool = True


@dataclasses.dataclass
class ServeConfigure:
    """Knobs for the continuous-batching serving layer (wasmedge_tpu/serve/).

    A BatchServer owns a bounded request queue, packs queued requests
    into device lanes, and recycles lanes the moment they retire
    instead of waiting for batch drain; per-tenant weighted-fair
    admission, deadlines, and backpressure live here."""

    # Bounded request queue: submit() beyond this many QUEUED (not yet
    # admitted) requests is rejected with QueueSaturated (ErrCode
    # backpressure, never silent drops).
    queue_capacity: int = 65536
    # Per-request retired-instruction budget: a request still running
    # past it is terminated with CostLimitExceeded (runaway guard; the
    # serving loop has no natural max_steps to drain to).
    max_steps_per_request: int = 10_000_000
    # Checkpoint the serving state every N serving rounds (the server's
    # analog of SupervisorConfigure cadence; each round is one
    # steps_per_launch slice).  None = only on demand.
    checkpoint_every_rounds: Optional[int] = None
    checkpoint_dir: Optional[str] = None
    keep_checkpoints: int = 2
    # Retry budget for launch/serve failures before the server gives up
    # and fails the in-flight futures (restores from the newest good
    # checkpoint, else re-queues the in-flight requests from scratch).
    max_retries: int = 3
    backoff_base_s: float = 0.05
    backoff_factor: float = 2.0
    backoff_max_s: float = 2.0
    # --- steps_per_launch auto-tuning (serve/autotune.py) ---
    # Feedback rule driven by the tier-1 hostcall drain-latency
    # histograms (obs/): expensive drains relative to device launch
    # time grow the chunk (amortize serve overhead), cheap drains with
    # parked lanes shrink it (serve sooner).  Off by default; every
    # adjustment is logged to the flight recorder as an "autotune"
    # instant.  Changing the chunk rebuilds the jitted step loop, so
    # adjustments are power-of-two quantized and bounded.
    autotune: bool = False
    autotune_min_chunk: int = 64
    autotune_max_chunk: int = 1 << 20


@dataclasses.dataclass
class HvConfigure:
    """Knobs for lane-memory virtualization (wasmedge_tpu/hv/).

    The serving layer's hypervisor mode: admitted requests beyond the
    physical lane count (or beyond the resident-bytes budget) wait as
    VIRTUAL lanes whose state lives host-side, swapping onto free
    physical lanes at launch boundaries.  Off (the default: both
    capacity knobs None) the BatchServer behaves exactly as before —
    admission is the free-lane heap, nothing ever swaps."""

    # Concurrent admitted requests (resident + virtual).  None = the
    # physical lane count (no oversubscription).  CLI:
    # --max-virtual-lanes.
    max_virtual_lanes: Optional[int] = None
    # Device bytes the resident population may hold: admission installs
    # at most floor(budget / effective-lane-bytes) physical lanes
    # (effective bytes seeded from DeviceImage.analysis footprint
    # bounds when the analyzer proved them, else the allocated plane
    # geometry — hv/policy.py).  None = every physical lane may be
    # resident.  CLI: --resident-budget-bytes.
    resident_budget_bytes: Optional[int] = None
    # SwapStore spill directory (content-addressed .lane blobs, crash-
    # atomic writes).  None keeps blobs in host memory only — serve
    # checkpoints still embed them, so crash/resume does not depend on
    # this knob.
    swap_dir: Optional[str] = None
    # Anti-thrash: a lane must have held the device for this many
    # serving rounds (launch slices) before it is evictable.
    min_resident_rounds: int = 1
    # Evictions per boundary rebalance (None = up to the lane count).
    max_swaps_per_round: Optional[int] = None

    @property
    def active(self) -> bool:
        return self.max_virtual_lanes is not None \
            or self.resident_budget_bytes is not None


@dataclasses.dataclass
class EffectsConfigure:
    """Knobs for the suspend/resume effect subsystem
    (wasmedge_tpu/effects/, r23).

    Off (the default) the serving stack runs the exact r22 path:
    blocking hostcalls (`poll_oneoff` sleeps, `await_event`) are served
    in place by the host layer and nothing ever parks, so behavior is
    bit-identical by construction."""

    # Master switch: lower blocking hostcalls into a PARKED effect —
    # the lane serializes through the SwapStore at the next launch
    # boundary (zero resident cost) and resumes on wake.  CLI:
    # --effects.
    suspend: bool = False
    # Park a pure-clock poll_oneoff only when its minimum relative
    # timeout is at least this many seconds; shorter sleeps are served
    # in place (parking round-trip would dominate).
    min_park_timeout_s: float = 0.0
    # SwapStore spill directory for parked-session blobs.  None shares
    # the hv store when hv is active, else keeps blobs in host memory —
    # serve checkpoints embed them either way, so crash/resume does not
    # depend on this knob.
    swap_dir: Optional[str] = None
    # Per-session stdout stream replay buffer cap in bytes (the
    # gateway's GET /v1/requests/<id>/stream seam); oldest bytes fall
    # off first once exceeded.
    stream_buffer_bytes: int = 1 << 20

    @property
    def active(self) -> bool:
        return bool(self.suspend)


@dataclasses.dataclass
class ImagestoreConfigure:
    """Knobs for the segmented-image / compile-cache / snapshot
    subsystem (wasmedge_tpu/imagestore/, r22).

    All three default OFF: the off configuration runs the exact r21
    code path (concat_images builds every segment inline, the registry
    consults no disk cache, initial_state carries no overlays), so
    behavior is bit-identical by construction."""

    # Memoize per-module image segments across generation builds: a
    # generation swap re-uses every already-built segment verbatim and
    # only builds the new module's (the indirection table is the bases
    # list).  CLI: --imagestore-segmented.
    segmented: bool = False
    # Persistent cross-process compile cache: registration consults a
    # sha256-keyed serialized-image cache before lowering, and stores
    # fresh lowerings back.  Entries fleet-replicate alongside module
    # blobs (GET /v1/fleet/cache/<sha>).  CLI: --compile-cache.
    compile_cache: bool = False
    # Cache directory.  None + a gateway state_dir -> <state_dir>/
    # compilecache; None without one -> in-memory only (still unifies
    # the probe tier and serves fleet replication, but does not
    # survive a process restart).
    compile_cache_dir: Optional[str] = None
    # Pre-initialized lane snapshots: run a module's _initialize/_start
    # once at registration, capture the post-init plane columns
    # (content-addressed SwapStore entry sized by the r19 page-touch
    # bound), and install that snapshot into admitted lanes through the
    # existing jitted column-set pass.  CLI: --snapshots.
    snapshots: bool = False
    # Snapshot SwapStore spill directory (None = host memory only).
    snapshot_dir: Optional[str] = None
    # Step budget for the one-time registration init run; a module
    # whose init exceeds it (or traps) simply gets no snapshot and
    # admits through the r21 template path.
    snapshot_init_max_steps: int = 2_000_000

    @property
    def active(self) -> bool:
        return self.segmented or self.compile_cache or self.snapshots


@dataclasses.dataclass
class IntegrityConfigure:
    """Knobs for the silent-data-corruption defense subsystem
    (wasmedge_tpu/integrity/, r24).

    Both legs default OFF: with neither the shadow auditor nor the
    scrubber enabled no hook is installed anywhere on the launch path
    and no background thread starts, so behavior is bit-identical to
    r23 by construction."""

    # Shadow-audit lanes: at seeded launch boundaries, export a small
    # lane subset's pre-slice planes, re-execute the identical slice
    # through a reference re-trace of the same step program at the
    # sampled width, and compare the post-slice planes bit-exact.  A
    # divergence raises an SDC incident (FailureRecord "integrity",
    # rollback to the newest good checkpoint, per-device attribution).
    # CLI: --integrity-audit.
    audit: bool = False
    # Seed for the boundary/lane sampler (deterministic given the seed
    # and the boundary index).
    audit_seed: int = 0
    # Audit roughly one in this many launch boundaries (1 = every
    # boundary; the sampler hashes seed+boundary so the audited set is
    # stable, not periodic).
    audit_every: int = 16
    # Lanes sampled per audited boundary.
    audit_lanes: int = 2
    # Divergences attributed to one device before the quarantine
    # ladder ejects it through the r21 reshard path.
    quarantine_threshold: int = 3
    # At-rest scrubber: re-verify sha256 over SwapStore entries
    # (parked r23 sessions included), checkpoint lineage members, and
    # WTIC compile-cache entries before a wake/restore needs them.
    # CLI: --integrity-scrub.
    scrub: bool = False
    # Background scrub cadence in seconds; 0 disables the thread
    # (scrub_once() stays callable — tests and the bench drive it
    # manually).
    scrub_interval_s: float = 0.0
    # Repair a failed local copy from fleet peer replicas
    # (GET /v1/fleet/cache/<sha> for compile-cache entries,
    # GET /v1/fleet/blob/<key> for swap blobs) before falling back to
    # evict + fresh-lower / init-replay.
    scrub_repair: bool = True

    @property
    def active(self) -> bool:
        return bool(self.audit or self.scrub)


@dataclasses.dataclass
class CompilerConfigure:
    """AOT-compiler knobs (reference: CompilerConfigure,
    include/common/configure.h:28-106).  The optimization level and
    native-output knobs are accepted for API parity; the tpu.aot
    artifact path (wasmedge_tpu.aot) is the compiler they configure —
    its universal artifact corresponds to OutputFormat "Universal", and
    "Native" has no TPU analog (XLA owns native codegen), so setting it
    is recorded but compile_module always emits universal twasm."""

    optimization_level: str = "O3"   # O0|O1|O2|O3|Os|Oz
    output_format: str = "Universal"  # Universal | Native
    dump_ir: bool = False
    generic_binary: bool = False
    interruptible: bool = False


@dataclasses.dataclass
class Configure:
    proposals: set = dataclasses.field(default_factory=lambda: set(DEFAULT_PROPOSALS))
    host_registrations: set = dataclasses.field(default_factory=set)
    engine: EngineKind = EngineKind.AUTO
    runtime: RuntimeConfigure = dataclasses.field(default_factory=RuntimeConfigure)
    statistics: StatisticsConfigure = dataclasses.field(default_factory=StatisticsConfigure)
    batch: BatchConfigure = dataclasses.field(default_factory=BatchConfigure)
    supervisor: SupervisorConfigure = dataclasses.field(
        default_factory=SupervisorConfigure)
    obs: ObsConfigure = dataclasses.field(default_factory=ObsConfigure)
    serve: ServeConfigure = dataclasses.field(default_factory=ServeConfigure)
    hv: HvConfigure = dataclasses.field(default_factory=HvConfigure)
    effects: EffectsConfigure = dataclasses.field(
        default_factory=EffectsConfigure)
    imagestore: ImagestoreConfigure = dataclasses.field(
        default_factory=ImagestoreConfigure)
    integrity: IntegrityConfigure = dataclasses.field(
        default_factory=IntegrityConfigure)
    compiler: CompilerConfigure = dataclasses.field(default_factory=CompilerConfigure)

    def add_proposal(self, p: Proposal) -> "Configure":
        self.proposals.add(p)
        return self

    def remove_proposal(self, p: Proposal) -> "Configure":
        self.proposals.discard(p)
        return self

    def has_proposal(self, p: Proposal) -> bool:
        return p in self.proposals

    def proposal_gates(self) -> frozenset:
        """Set of gate-name strings for loader/validator opcode gating."""
        return frozenset(p.gate_name for p in self.proposals)
