"""Block scheduler: divergence as a scheduling problem, not a kernel one.

SURVEY.md §7 step 8 prescribes "batching by (module, PC) buckets;
retire/refill lanes from a host queue" for heterogeneous/divergent
execution.  This module is that scheduler.  The Pallas warp-interpreter
(batch/pallas_engine.py) is deliberately *uniform* — every lane in a
block shares one pc/sp/fp, which is what keeps its dispatch loop free of
per-lane gathers (the TPU has no per-lane addressing across sublanes).
Divergence is handled here, outside the kernel:

- **Entry grouping**: lanes are sorted by their argument tuples before
  packing into lane blocks, so lanes that will follow the same control
  path (Wasm instances are deterministic share-nothing state machines)
  land in the same block and never diverge at all.  Groups are padded to
  whole blocks with cloned lanes; pads compute redundantly and are
  dropped at harvest.
- **Split on divergence**: when a block stops at a data-dependent branch
  whose condition disagrees (status=DIVERGED), the splitter evaluates
  that ONE instruction per lane on the host, partitions the lanes by
  outcome, and installs each side as a new control-uniform block — the
  moral equivalent of a GPU warp scheduler's divergence stack, with
  re-packing explicit and amortized.  For fib(n) with mixed n this fires
  once per mixed block; afterwards every block is converged forever.
  The surgery is two compiled programs a child (`_surgery_fns`): one
  gathers its columns out of every plane at a power-of-two width, one
  sets them into a free slot's columns of the donated planes.
- **SIMT residue**: anything the splitter can't express (per-lane
  divergent memory addressing, growth beyond the watermark plane)
  queues its lanes for one final pass on the
  per-lane-pc SIMT engine; everything else keeps running on the kernel.

The reference runs every instance on the same dispatch loop
(/root/reference/lib/executor/engine/engine.cpp:68-1641) one thread at a
time; here 'threads' are lane blocks and 'context switches' are block
installs: one compiled, donated column-block set over all planes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import numpy as np

from wasmedge_tpu.common.errors import ErrCode
from wasmedge_tpu.batch.image import TRAP_DONE
from wasmedge_tpu.batch.pallas_engine import (
    H_BR_TABLE,
    H_BRNZ,
    H_BRZ,
    H_CALL_INDIRECT,
    H_BLOCK_BASE,
    H_MEMGROW,
    ST_DIVERGED,
    ST_DONE,
    ST_HOSTCALL,
    ST_RECHECK,
    ST_REGROW,
    ST_RUNNING,
    ST_TRAPPED_BASE,
    indirect_column,
    _C_CD,
    _C_SNAP,
    _C_CHUNK,
    _C_DISPATCHES,
    _C_SIMD,
    _C_SOFTFLOAT,
    _C_FP,
    _C_FUEL,
    _C_OB,
    _C_PAGES,
    _C_PC,
    _C_SP,
    _C_STATUS,
    _C_STEPS,
    _C_WACCESSES,
    _C_WFILLS,
    _C_WWBS,
    _FUEL_OFF,
    _PAGE_WORDS,
    HostLink,
    PallasUniformEngine,
    lanes_of,
    put_cols,
    take_cols,
)

# host-side block slot states
_B_FREE = 0
_B_LIVE = 1     # installed in the device state (any kernel status)

_PLANE_IDX = {"slo": 2, "shi": 3, "glo": 4, "ghi": 5, "mem": 6, "trap": 7}
# v128 e2/e3 planes sit AFTER the 6 rollback shadows (indices 8-13) so
# every non-simd index stays stable
_PLANE_IDX_SIMD = dict(_PLANE_IDX, se2=14, se3=15)


def _u32(x):
    return np.asarray(x).astype(np.int64) & 0xFFFFFFFF


def _pad_width(n: int, lblk: int) -> int:
    """Columns a child of `n` lanes is carried at: the next power of
    two, so under 2 n, and never over the block."""
    return min(1 << max(n - 1, 0).bit_length(), lblk)


def _clone_pad(n: int, width: int) -> np.ndarray:
    """int32[width]: 0..n-1, then repeats of 0 (pads clone the first
    column: they compute redundantly and are dropped at harvest)."""
    idx = np.zeros(width, np.int32)
    idx[:n] = np.arange(n, dtype=np.int32)
    return idx


def _surgery_fns():
    """-> (extract, install), the two compiled programs of block
    surgery.  Both take the planes of `_plane_idx` as one tuple, so one
    pair serves every guest (with a memory, with v128) and jit keys its
    variants by the planes' shapes and the child's width alone: at most
    log2(Lblk) + 1 each a geometry.

    extract(planes, idx[w]) -> the columns `idx` of every plane, each
    [rows, w] whatever the planes' layout (`plane_shape`).
    install(planes, cols, sel[Lblk], lo) -> the planes with columns
    lo..lo+Lblk set to `cols[:, sel]`, in place: the planes are donated
    (`jit_in_place`; the caller rebinds them)."""
    import jax

    from wasmedge_tpu.batch import jit_in_place

    def extract(planes, idx):
        return tuple(take_cols(p, idx) for p in planes)

    def install(planes, cols, sel, lo):
        return tuple(put_cols(p, c[:, sel], lo * 0, lo)
                     for p, c in zip(planes, cols))

    return jax.jit(extract), jit_in_place(install, 0)


class _Rows:
    """Lazy row-sliced view of a device plane: downloads one row's block
    columns at a time through `d2h` (HostLink.d2h), cached."""

    def __init__(self, d2h, arr, lo: int, n: int):
        # a plane in the kernel's layout (`plane_shape`) is cut by
        # stripes: a block's lanes are whole ones
        p = arr.shape[2] if arr.ndim == 3 else 1
        self._d2h, self._arr, self._lo, self._n = d2h, arr, lo // p, n // p
        self._c = {}

    def __getitem__(self, key):
        if isinstance(key, tuple):
            row, cols = key
            return self[row][cols]
        r = int(key)
        if r not in self._c:
            self._c[r] = self._d2h(
                "rows", self._arr,
                np.s_[r, self._lo:self._lo + self._n]).reshape(-1)
        return self._c[r]


@dataclasses.dataclass
class _Pending:
    """A control-uniform lane group waiting for a free block slot."""

    ctrl: np.ndarray              # [eng.ctrl_width] int32
    frames: np.ndarray            # [3, CD] int32
    cols: Dict[str, np.ndarray]   # plane name -> [rows, w] device columns,
    #                               w = _pad_width(n): the first n real
    lane_ids: np.ndarray          # [n] original lane ids (no pads)
    steps0: int = 0               # instructions already retired
    pages: np.ndarray = None      # [n] per-lane page counts when a host
    #                               outcall grew memory (else ctrl value)


class _SnapPolicy:
    """Every value the host gives a block's snapshot interval
    (`_C_SNAP`: the steps between the optimistic kernel's commits, 0
    read as the engine's full `SNAP_STEPS`, here `full`; the kernel
    hands the column back as it got it).  A block that rolls back gets
    half its interval, down to `MIN`, so the run-up a genuinely
    divergent block discards shrinks geometrically; a clean launch
    doubles it back up to `full`; a split's child starts at `full`,
    never at its parent's."""

    MIN = 256
    # `commit_due`'s first interval after a launch (the kernel's own
    # min(512, snap_steps)): short, to bound that run-up
    FIRST = 512

    def __init__(self, full: int):
        self.full = full

    def ran(self, snap):
        """The interval each block of the column `snap` ran under."""
        return np.where(snap > 0, snap, self.full)

    def rolled_back(self, snap, rolled):
        """-> (chunk, snap), the columns of a careful recheck of the
        blocks `rolled`: the careful kernel replays the interval a block
        ran under and 64 steps past it, and the block goes on at half
        that interval; the other blocks run no step and keep theirs."""
        ran = self.ran(snap)
        return (np.where(rolled, ran + 64, 0),
                np.where(rolled, np.maximum(ran // 2, self.MIN), ran))

    def grown(self, snap, clean):
        """The column after a launch the blocks `clean` ran without a
        rollback: a halved interval doubles, up to `full`."""
        grow = clean & (snap > 0) & (snap < self.full)
        return np.where(grow, np.minimum(snap * 2, self.full), snap)

    def restores(self, snap) -> int:
        """1 where a child's `full` interval (the value itself, not the
        0 the kernel reads as it) gives back a halved `snap`."""
        return int(0 < snap < self.full)

    def commits(self, steps, snap) -> int:
        """The periodic commits a launch of `steps` under the intervals
        `snap` implies, by `commit_due`'s rule: the first falls due
        after min(FIRST, interval) steps, the others an interval
        apart."""
        snap = self.ran(snap.astype(np.int64))
        first = np.minimum(min(self.FIRST, self.full), snap)
        return int(np.where(steps < first, 0,
                            1 + (steps - first) // snap).sum())


class BlockScheduler:
    """Drives one module's batch through the Pallas kernel with entry
    grouping, divergence splitting, and a SIMT residue pass."""

    # don't pre-group when the median group is this small — the SIMT
    # engine is the right tool for fully-heterogeneous inputs
    MIN_GROUP_LANES = 8

    def __init__(self, outer: PallasUniformEngine, func_name: str,
                 args_lanes: List, max_steps: int):
        self.outer = outer
        # flight recorder shared with the outer engine (obs/): the
        # scheduler reports launches, serves, splits, frees, residue
        # handoffs and live-lane occupancy; NULL_RECORDER when off
        self.obs = outer.obs
        # per-device trace attribution (parallel/mesh.py sets obs_track
        # on each device's engine so multi-chip runs keep their devices'
        # events on separate tracks instead of one interleaved "pallas")
        self._track = getattr(outer, "obs_track", "pallas")
        self._track_simt = "simt" if self._track == "pallas" \
            else self._track
        # phase spans (obs.timed): under a caller's open span they land
        # on its track; a device thread of a mesh drive has none open
        self._phase = functools.partial(
            self.obs.timed, cat="scheduler",
            track=None if self._track == "pallas"
            else self._track + "/phases")
        # every transfer and every call of a compiled program below goes
        # through the link, so each lies in a leaf span and is counted:
        # no np.asarray / jnp.asarray of a plane or a mirror beside it.
        # (It holds the recorder's `timed`, not this scheduler: a cycle
        # would keep a job's planes on the device until the next gc.)
        self.link = HostLink(self._phase)
        with self._phase("batch/plan"):
            self._setup(func_name, args_lanes, max_steps)

    def _setup(self, func_name, args_lanes, max_steps):
        """Entry grouping and the initial planes on the device."""
        outer = self.outer
        self.inst = outer.inst
        self.cfg = outer.cfg
        self.func_name = func_name
        self.max_steps = max_steps
        self.lanes = outer.lanes
        ex = self.inst.exports.get(func_name)
        if ex is None or ex[0] != 0:
            raise KeyError(f"no exported function {func_name}")
        self.func_idx = ex[1]
        self.nres = int(self.inst.lowered.funcs[self.func_idx].nresults)
        self.args = []
        for a in args_lanes:
            arr = np.asarray(a, np.int64)
            if arr.ndim == 0:
                arr = np.full(self.lanes, arr, np.int64)
            if arr.shape != (self.lanes,):
                raise ValueError(
                    f"arg: expected shape ({self.lanes},) or scalar, "
                    f"got {arr.shape}")
            self.args.append(arr)
        # results in original lane order
        self.res_lo = np.zeros((max(self.nres, 1), self.lanes), np.int32)
        self.res_hi = np.zeros((max(self.nres, 1), self.lanes), np.int32)
        self.trap = np.zeros(self.lanes, np.int32)
        self.retired = np.zeros(self.lanes, np.int64)
        self.fell_back_to_simt = False
        self.splits = 0
        # this run's launches of the optimistic kernel, rounds of the
        # careful one, and the block-steps the careful rounds retired
        self.launches = 0
        self.rechecks = 0
        self.careful_steps = 0
        # compiled surgery calls: one extract and one install a child
        self.surgery_programs = 0
        # children given the full snapshot interval back in place of
        # their parent's halved one, and the periodic commits the
        # launches' intervals imply (computed from steps and interval at
        # each sync, not counted by the kernel)
        self.snap_restored = 0
        self.snap_commits = 0
        # blocks a hostcall serve re-armed as DIVERGED: the kernel had
        # counted their call when it parked them
        self._served_stops = set()
        # the hbm_window kernel's DMA counts and the accesses it
        # resolved against the window, summed over blocks and launches
        # (zero in every other memory mode)
        self.window_fills = 0
        self.window_writebacks = 0
        self.window_accesses = 0
        # handlers the kernels dispatched and the block-steps they
        # retired doing it, summed over blocks and launches (the
        # careful recheck's too)
        self.dispatches = 0
        self.kernel_steps = 0
        # the softfloat routines they ran (a kernel whose image holds a
        # binary64 ALU op counts them; zero for any other)
        self.softfloat_ops = 0
        # and the instructions of a v128 class (a kernel whose image has
        # v128 counts them in the column only its ctrl rows have)
        self.simd_ops = 0
        # and the br_table and call_indirect (a kernel whose image holds
        # one counts them, in the row's last column)
        self.indirect_ops = 0
        self.quarantined = 0
        self._t_launch = 0.0
        self._plane_idx = _PLANE_IDX_SIMD if outer.img.has_simd \
            else _PLANE_IDX
        with self._phase("batch/group"):
            self._plan()
        with self._phase("batch/initial_state"):
            self._build_initial_state()

    # -- entry packing -----------------------------------------------------
    def _plan(self):
        """Choose (L_sched, Lblk), pack the lanes into blocks and find
        (first build) the engine of that geometry: host work only."""
        outer = self.outer
        if self.args:
            order = np.lexsort(tuple(self.args))
            keys = np.stack(self.args, axis=0)[:, order]
            starts = np.concatenate((
                [0],
                np.flatnonzero(np.any(keys[:, 1:] != keys[:, :-1],
                                      axis=0)) + 1))
            sizes = np.diff(np.concatenate((starts, [self.lanes])))
        else:
            order = np.arange(self.lanes)
            sizes = np.array([self.lanes])
        lblk_max = outer._lane_block()
        align = 1 if outer._interpret() else 128
        med = int(np.median(sizes))
        if len(sizes) == 1 or med < self.MIN_GROUP_LANES:
            # uniform batch (no grouping needed) or hopelessly shattered
            # (grouping can't help): one geometry, identity packing
            lblk = lblk_max
            self.order = np.arange(self.lanes)
            group_sizes = [self.lanes]
        else:
            # Smallest block covering the typical group: throughput is
            # Lblk x step-rate and blocks serialize on the core, so a
            # group split across two blocks runs its program twice.
            # Padding a block out to the group size is free by comparison
            # (pad lanes ride along in otherwise-idle vector lanes).
            lblk = align
            while lblk < med and lblk * 2 <= lblk_max:
                lblk *= 2
            self.order = order
            group_sizes = [int(s) for s in sizes]
            # guard: per-group padding must not inflate the packed state
            # unboundedly (hundreds of sub-align groups would each claim
            # a full block of HBM planes and a serialized block slot) —
            # past 2x the caller's lanes, identity packing + in-flight
            # splitting degrades more gracefully
            padded = sum(-(-g // lblk) * lblk for g in group_sizes)
            if padded > 2 * self.lanes:
                lblk = lblk_max
                self.order = np.arange(self.lanes)
                group_sizes = [self.lanes]
        blocks: List[np.ndarray] = []   # each [lblk] lane ids (-1 = pad)
        pos = 0
        for g in group_sizes:
            ids = self.order[pos:pos + g]
            pos += g
            for off in range(0, g, lblk):
                chunk = ids[off:off + lblk].astype(np.int64)
                if len(chunk) < lblk:
                    chunk = np.concatenate(
                        [chunk, np.full(lblk - len(chunk), -1, np.int64)])
                blocks.append(chunk)
        self.Lblk = lblk
        self.nblk = len(blocks)
        L = self.nblk * lblk
        # splits that outgrow this budget route to SIMT instead of
        # thrashing the host with block surgery
        self.split_budget = 4 * self.nblk + 16
        # internal engine at the scheduler's geometry and the two
        # surgery programs, cached on the long-lived SIMT engine per
        # (L, Lblk) so repeated run() calls reuse the image, the fused
        # tables, the jitted kernel and the surgery
        owner = outer.simt
        if not hasattr(owner, "_sched_cache"):
            owner._sched_cache, owner._surgery_cache = {}, {}
        key = (L, lblk)
        eng = owner._sched_cache.get(key)
        if eng is None:
            from wasmedge_tpu.batch.engine import BatchEngine

            simt = BatchEngine(self.inst, store=owner.store,
                               conf=owner.conf, lanes=L, img=outer.img)
            eng = PallasUniformEngine(self.inst, simt=simt,
                                      interpret=outer.interpret,
                                      blk_cap=lblk)
            if not eng.eligible:
                raise RuntimeError(
                    f"scheduler geometry ineligible: "
                    f"{eng.ineligible_reason}")
            eng._build()
            assert eng._geom[3] == lblk, (eng._geom, lblk)
            owner._sched_cache[key] = eng
            owner._surgery_cache[key] = _surgery_fns()
        self.eng = eng
        self._surgery = owner._surgery_cache[key]
        self._snap = _SnapPolicy(eng.SNAP_STEPS)
        # a hostcall serve counts into its engine's SIMT twin: this
        # run's dict, not the cached engine's own growing one
        eng.simt.hostcall_stats = outer.simt.hostcall_stats
        self.block_lanes = np.stack(blocks)  # [nblk, lblk]
        self.block_state = np.full(self.nblk, _B_LIVE, np.int32)
        self.block_steps = np.zeros(self.nblk, np.int64)
        self._pending: List[_Pending] = []
        self._simt_queue: List[_Pending] = []
        self._pending_serve = None   # tier-2 deferred hostcall serve
        self._serve_rearms = None
        # Host mirrors of what decides a pass, filled together from the
        # pass record (one download a pass).  THE RULE: a mirror is
        # valid from the record that filled it until the next program
        # that writes its plane (a kernel, the careful kernel, an
        # install, a hostcall serve's finish); that write drops it, and
        # a read that misses downloads the plane itself.
        self._ctrl_cache = None
        self._ctrl_dirty = False
        self._frames_cache = None
        self._frames_dirty = False
        self._drop_plane_mirrors()
        # the record enqueued behind the last launch, until it is read
        self._record = None

    def _build_initial_state(self):
        """Construct the packed state ON DEVICE.  Host->device bandwidth
        is the scarce resource:
        only the argument rows (nargs x L) and the module's memory init
        image (<= W words) are uploaded; the big zero planes are
        jnp.zeros and the per-lane broadcast of mem_init happens
        device-side."""
        import jax.numpy as jnp

        eng = self.eng
        img = eng.img
        D, CD, W, Lblk = eng._geom
        h2d = self.link.h2d
        meta = self.inst.lowered.funcs[self.func_idx]
        # packed column -> original lane (pads clone their block's first
        # valid lane so they run the same program)
        flat = self.block_lanes.reshape(-1).copy()
        for b in range(self.nblk):
            seg = self.block_lanes[b]
            first = seg[seg >= 0][0]
            flat[b * Lblk:(b + 1) * Lblk][seg < 0] = first
        plane = eng._plane      # every plane in the kernel's layout

        def every_lane(what, col):
            """[n, ...] with row i col[i] in every lane."""
            shape = plane(col.shape[0])
            return jnp.broadcast_to(h2d(what, col.reshape(
                (-1,) + (1,) * (len(shape) - 1))), shape)

        stack_lo = jnp.zeros(plane(D), jnp.int32)
        stack_hi = jnp.zeros(plane(D), jnp.int32)
        if self.args:
            arg_m = np.stack([a[flat] for a in self.args])  # [nargs, L]
            lo = (arg_m & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
            hi = ((arg_m >> 32) & 0xFFFFFFFF).astype(np.uint32).view(
                np.int32)
            n = len(self.args)
            stack_lo = stack_lo.at[:n].set(h2d("args_lo",
                                               lo.reshape(plane(n))))
            stack_hi = stack_hi.at[:n].set(h2d("args_hi",
                                               hi.reshape(plane(n))))
        NGp = max(img.globals_lo.shape[0], 1)
        glo = jnp.zeros(plane(NGp), jnp.int32)
        ghi = jnp.zeros(plane(NGp), jnp.int32)
        ng = img.globals_lo.shape[0]
        if ng:
            glo = glo.at[:ng].set(every_lane("globals_lo", img.globals_lo))
            ghi = ghi.at[:ng].set(every_lane("globals_hi", img.globals_hi))
        mem = jnp.zeros(plane(W), jnp.int32)
        if img.mem_init.shape[0] > 1 or img.mem_pages_init:
            n = min(img.mem_init.shape[0], W)
            mem = mem.at[:n].set(every_lane("mem_init", img.mem_init[:n]))
        ctrl = np.zeros((self.nblk, eng.ctrl_width), np.int32)
        ctrl[:, _C_PC] = meta.entry_pc
        ctrl[:, _C_SP] = meta.nlocals
        ctrl[:, _C_OB] = meta.nlocals
        ctrl[:, _C_PAGES] = img.mem_pages_init
        ctrl[:, _C_CHUNK] = self.cfg.steps_per_launch
        fuel = self.cfg.fuel_per_launch
        ctrl[:, _C_FUEL] = _FUEL_OFF if fuel is None else fuel
        # the host built both, so it holds them: no download before the
        # first launch (the mirror is the host's own copy)
        self._ctrl_cache = ctrl.copy()
        self._frames_cache = np.zeros((self.nblk, 3, CD), np.int32)
        self.state = [h2d("ctrl", ctrl),
                      jnp.zeros((self.nblk, 3, CD), jnp.int32),
                      stack_lo, stack_hi, glo, ghi, mem,
                      jnp.zeros(plane(1), jnp.int32)] + eng.shadow_planes()
        if img.has_simd:
            self.state += [jnp.zeros(plane(D), jnp.int32),
                           jnp.zeros(plane(D), jnp.int32)]
            self.state += eng._shadow_simd_planes()

    # -- drive -------------------------------------------------------------
    def run(self):
        """Run to completion; fills result/trap/retired arrays.

        Tier-2 overlap: parked hostcall blocks are captured (device
        reads) during process(), then SERVED on the CPU after the next
        launch() has dispatched — block A's WASI calls drain while
        block B keeps executing on the device.  Re-arms are column
        updates into the live state (no kernel rebuild/relaunch cost);
        the re-armed blocks run from the following launch."""
        # cooperative mesh cancellation (parallel/supervisor.py): a
        # doomed sharded run stops sibling schedulers at their next
        # launch boundary instead of running to completion
        cancel = getattr(self, "cancel_check", None)
        while True:
            if cancel is not None and cancel():
                return
            self.launch()
            if not self.process():
                break
        self._run_simt_residue()
        # PallasUniformEngine.run folds the other counts of a run; these
        # two leave from here (as `eng.pallas.<name>` and in /metrics)
        self.outer.snap_restored = self.snap_restored
        self.outer.snap_commits = self.snap_commits
        self.obs.add_split_counts(snap_restored=self.snap_restored,
                                  snap_commits=self.snap_commits)

    def _finish_pending_serve(self) -> bool:
        """Phase 2 of a deferred hostcall serve: host-side WASI work
        overlapping the in-flight kernel; re-armed ctrl rows are folded
        into the mirror by process() after it syncs on the launch.
        True if it ran: it writes result rows and trap columns into the
        live planes."""
        p = self._pending_serve
        if p is None:
            return False
        self._pending_serve = None
        stats = self.eng.simt.hostcall_stats
        calls, out = stats["tier1_calls"], stats["out_bytes"]
        with self._phase("batch/hostcall_finish",
                         blocks=len(p["blocks"])) as span:
            self.state, rearms = self.eng._serve_hostcalls_finish(
                self.state, p)
            span.set(calls=stats["tier1_calls"] - calls,
                     bytes=stats["out_bytes"] - out)
        self._serve_rearms = rearms
        return True

    def launch(self):
        """Dispatch one kernel round if any block is runnable.  The
        dispatch is asynchronous (JAX): multiple schedulers' launches
        pipeline on the device while hosts process results — the
        latency-hiding seam the multi-tenant driver uses."""
        ctrl_np = self._ctrl()
        live = self.block_state == _B_LIVE
        runnable = live & (ctrl_np[:, _C_STATUS] == ST_RUNNING) & \
            (self.block_steps < self.max_steps)
        self._launched = bool(runnable.any())
        self._launch_blocks = int(runnable.sum())
        with self._phase("batch/launch", blocks=self._launch_blocks):
            if self._ctrl_dirty:
                self.state[0] = self.link.h2d("ctrl", ctrl_np)
                self._ctrl_dirty = False
            self._upload_frames()
            if self._launched:
                self.launches += 1
                # the blocks this launch runs: the kernel hands a row
                # that is not RUNNING back as it got it, the steps and
                # counts of the launch that stopped it included, so a
                # block parked through a launch (a serve that overlaps
                # it) must not be counted again at the sync
                self._ran_at_launch = runnable
                self._t_launch = self.obs.now()
                out = self.link.enqueue(
                    "optimistic", self.eng._fn, *self.eng._tables,
                    self.state[0], self.state[1], *self.state[2:])
                self.state = list(out)
                # the kernel writes every plane: no mirror outlives it.
                # Its pass record is enqueued here, behind it, so the
                # host's part of the call runs while the kernel does
                self._ctrl_cache = self._frames_cache = None
                self._drop_plane_mirrors()
                self._record = self.eng.enqueue_pass_record(
                    self.state, self.nres, self.link)

    def _drop_plane_mirrors(self):
        """A program wrote the trap plane and the stacks."""
        self._trap_full = self._res_lo_full = self._res_hi_full = None

    def _fill_mirrors(self, rec):
        self._ctrl_cache, self._frames_cache = rec.ctrl, rec.frames
        self._ctrl_dirty = self._frames_dirty = False
        self._trap_full = rec.trap
        self._res_lo_full, self._res_hi_full = rec.res_lo, rec.res_hi

    def _take_record(self):
        """Read the last launch's pass record, if it is still out (every
        mirror is empty then): the ONE transfer of a kernel round, which
        waits for the kernel."""
        if self._record is not None:
            rec = self.eng.read_pass_record(self._record, self.nres,
                                            self.link)
            self._record = None
            self._fill_mirrors(rec)

    def _ctrl(self) -> np.ndarray:
        """Host mirror of the ctrl plane.  Every per-block interaction
        below reads/writes this mirror (tiny transfers each pay the host
        link's full round-trip latency)."""
        self._take_record()
        if self._ctrl_cache is None:
            self._ctrl_cache = self.link.d2h("ctrl", self.state[0]).copy()
            self._ctrl_dirty = False
        return self._ctrl_cache

    def _frames(self) -> np.ndarray:
        """Host mirror of the frames plane (same discipline as _ctrl)."""
        self._take_record()
        if self._frames_cache is None:
            self._frames_cache = \
                self.link.d2h("frames", self.state[1]).copy()
            self._frames_dirty = False
        return self._frames_cache

    def _trap(self) -> np.ndarray:
        """Host mirror of the trap plane's row, read-only."""
        self._take_record()
        if self._trap_full is None:
            self._trap_full = self.link.d2h(
                "trap", self.state[7], 0).reshape(-1)
        return self._trap_full

    def _res(self):
        """Host mirrors of the result rows: ([nres, L] lo, hi)."""
        self._take_record()
        if self._res_lo_full is None:
            rows = np.s_[:self.nres]
            self._res_lo_full = lanes_of(
                self.link.d2h("res_lo", self.state[2], rows))
            self._res_hi_full = lanes_of(
                self.link.d2h("res_hi", self.state[3], rows))
        return self._res_lo_full, self._res_hi_full

    def _upload_frames(self):
        """The frames mirror back onto the device, if a child's install
        wrote it."""
        if self._frames_dirty:
            self.state[1] = self.link.h2d("frames", self._frames_cache)
            self._frames_dirty = False

    def process(self) -> bool:
        """Sync on the launch (if any) and handle block statuses.
        Returns False when the kernel side is finished (residue may
        remain for _run_simt_residue)."""
        # phase 2 of a serve captured by the PREVIOUS process(): the
        # host-side WASI work runs now, before we sync on the launch
        # dispatched in between — CPU drain overlapping device compute
        served_now = self._finish_pending_serve()
        with self._phase("batch/sync"):
            ctrl_np = self._ctrl()   # the pass record: waits for the
            #                          launched kernel
        if served_now:
            # the serve's finish wrote after that record was packed
            self._drop_plane_mirrors()
        served = False
        if self._serve_rearms:
            # fold the overlapped serve's re-arms into the fresh mirror
            # (the kernel passed the parked blocks' ctrl rows through)
            for b, row in self._serve_rearms.items():
                ctrl_np[b] = row
                if row[_C_STATUS] == ST_DIVERGED:
                    self._served_stops.add(b)
                # an import's stub is two synthetic instructions, the
                # HOSTCALL the kernel counted when it parked the block
                # and the RETURN it counts once re-armed: neither is
                # the guest's, whose `call` retired already, so a lane's
                # count stays the scalar engine's through every round
                self.block_steps[b] -= \
                    1 if row[_C_STATUS] >= ST_TRAPPED_BASE else 2
            self._serve_rearms = None
            self._ctrl_dirty = True
            served = True
        if self._launched:
            live = self._ran_at_launch
            new_steps = ctrl_np[:, _C_STEPS].astype(np.int64)
            self.block_steps[live] += new_steps[live]
            self._count_kernel(ctrl_np, live)
            self._count_commits(ctrl_np, live)
            obs = self.obs
            if obs.enabled:
                # per-launch span closed at THIS sync point (the ctrl
                # mirror download above is the launch's completion);
                # occupancy counts real (non-pad) lanes of live blocks
                valid = self.block_lanes >= 0
                obs.span(
                    "kernel_round", self._t_launch, cat="scheduler",
                    track=self._track, blocks=self._launch_blocks,
                    retired_delta=int(
                        (new_steps[live] * valid[live].sum(axis=1)).sum()))
                obs.counter("live_lanes", int(
                    valid[self.block_state == _B_LIVE].sum()))
            if (live & (ctrl_np[:, _C_STATUS] == ST_RECHECK)).any():
                with self._phase("batch/statuses", splits=self.splits):
                    ctrl_np = self._run_recheck(live)
            else:
                snap = ctrl_np[:, _C_SNAP]
                grown = self._snap.grown(snap, live)
                if (grown != snap).any():
                    cc = self._ctrl()
                    cc[:, _C_SNAP] = grown
                    self._ctrl_dirty = True
                    ctrl_np = cc
            self._handle_statuses(ctrl_np)
            return True
        if self._handle_statuses(ctrl_np) or served:
            return True
        if self._pending_serve is not None:
            return True  # a captured serve still needs its finish pass
        # starved: pending children with no free slot go to SIMT
        for p in self._pending:
            self._simt_queue.append(p)
        self._pending = []
        return False

    def _count_kernel(self, ctrl_np, blocks):
        """Add what the kernel that just ran counted in `blocks`."""
        self.dispatches += int(ctrl_np[blocks, _C_DISPATCHES].sum())
        self.kernel_steps += int(ctrl_np[blocks, _C_STEPS].sum())
        if self.eng.mem_static.get("mem_mode") == "hbm_window":
            self.window_fills += int(ctrl_np[blocks, _C_WFILLS].sum())
            self.window_writebacks += int(ctrl_np[blocks, _C_WWBS].sum())
            self.window_accesses += int(
                ctrl_np[blocks, _C_WACCESSES].sum())
        if self.eng.counts_softfloat:
            self.softfloat_ops += int(ctrl_np[blocks, _C_SOFTFLOAT].sum())
        if self.eng.img.has_simd:
            self.simd_ops += int(ctrl_np[blocks, _C_SIMD].sum())
        if self.eng.counts_indirect:
            self.indirect_ops += int(ctrl_np[
                blocks, indirect_column(bool(self.eng.img.has_simd))].sum())

    def _count_commits(self, ctrl_np, blocks):
        """Add the periodic commits the launch that just ran implies in
        `blocks` (`_SnapPolicy.commits`), from each block's `_C_STEPS`
        (a rollback rewound it to the last commit) and the `_C_SNAP` it
        ran under; a fused block may overshoot a boundary by its length,
        so a long run can take a commit fewer than this says."""
        if self.eng.optimistic:
            self.snap_commits += self._snap.commits(
                ctrl_np[blocks, _C_STEPS].astype(np.int64),
                ctrl_np[blocks, _C_SNAP])

    def _run_recheck(self, live) -> np.ndarray:
        """Re-run ST_RECHECK blocks on the careful kernel (synchronous,
        `careful_recheck`) with the intervals `_SnapPolicy` gives a
        rollback, then stops with the precise status which
        _handle_statuses splits/serves."""
        ctrl = self._ctrl()
        recheck = live & (ctrl[:, _C_STATUS] == ST_RECHECK)
        with self._phase("batch/recheck", blocks=int(recheck.sum())):
            chunk, snap = self._snap.rolled_back(ctrl[:, _C_SNAP], recheck)
            self._upload_frames()
            self.state, rec = self.eng.careful_recheck(
                self.state, ctrl, chunk, snap, self.link, self.nres)
        ctrl = rec.ctrl
        self.rechecks += 1
        self.careful_steps += int(ctrl[recheck, _C_STEPS].sum())
        self.block_steps += ctrl[:, _C_STEPS].astype(np.int64)
        self._count_kernel(ctrl, recheck)
        # the careful kernel wrote every plane and the record was packed
        # behind it: every mirror is fresh (`ctrl` as uploaded again)
        self._fill_mirrors(rec)
        return ctrl

    def _handle_statuses(self, ctrl_np) -> bool:
        """Harvest/serve/split each live block by its status.  Returns
        True if progress was made that could unblock another pass."""
        with self._phase("batch/statuses", splits=self.splits):
            return self._statuses(ctrl_np)

    def _statuses(self, ctrl_np) -> bool:
        progress = False
        hostcall_blocks = []
        # classify first: every harvest, then every split
        harvests = []
        splits = []
        for b in range(self.nblk):
            if self.block_state[b] != _B_LIVE:
                continue
            status = int(ctrl_np[b, _C_STATUS])
            if status == ST_RUNNING:
                if self.block_steps[b] >= self.max_steps:
                    harvests.append((b, True))
                continue
            if status == ST_DONE or status >= ST_TRAPPED_BASE:
                harvests.append((b, False))
            elif status == ST_HOSTCALL:
                hostcall_blocks.append(b)
            elif status in (ST_DIVERGED, ST_REGROW):
                splits.append((b, status))
        if harvests:
            # out of the mirrors the pass record filled
            with self._phase("batch/harvest", blocks=len(harvests)):
                for b, running in harvests:
                    self._harvest(b, ctrl_np, running=running)
                    progress = True
        for b, status in splits:
            with self._phase("batch/split",
                             pc=int(ctrl_np[b, _C_PC])) as span:
                span.set(children=self._split(b, ctrl_np, status))
            progress = True
        if hostcall_blocks:
            # tier-2 overlap: capture the serve's device reads now (the
            # state arrays are valid pre-launch); the host-side WASI
            # work runs in the NEXT process() after a launch has been
            # dispatched, so block A's calls drain on the CPU while
            # block B executes on the device.  The kernel passes parked
            # (non-RUNNING) blocks through untouched with zero steps,
            # so the deferred writebacks land on unchanged columns.
            valid = {b: self.block_lanes[b] >= 0 for b in hostcall_blocks}
            with self._phase("batch/hostcall_begin",
                             blocks=len(hostcall_blocks),
                             lanes=int(sum(v.sum()
                                           for v in valid.values()))):
                self._pending_serve = self.eng._serve_hostcalls_begin(
                    self.state, ctrl_np, valid_blocks=valid,
                    link=self.link)
            progress = True
        # a prior serve's re-arms may have left per-lane outcomes
        # (folded into ctrl_np by process): DIVERGED/trapped re-armed
        # blocks were already classified by the split/harvest passes
        # above, since they arrive through the normal status scan.
        progress |= self._install_pending()
        return progress

    # -- harvest -----------------------------------------------------------
    def _harvest(self, b: int, ctrl_np, running: bool = False):
        Lblk = self.Lblk
        lo = b * Lblk
        ids = self.block_lanes[b]
        valid = ids >= 0
        vids = ids[valid].astype(np.int64)
        status = int(ctrl_np[b, _C_STATUS])
        if running:
            codes = self._trap()[lo:lo + Lblk].copy()  # 0 = still running
        elif status == ST_DONE:
            codes = np.full(Lblk, TRAP_DONE, np.int32)
            if self.nres:
                res_lo, res_hi = self._res()
                self.res_lo[:self.nres, vids] = \
                    res_lo[:, lo:lo + Lblk][:, valid]
                self.res_hi[:self.nres, vids] = \
                    res_hi[:, lo:lo + Lblk][:, valid]
        else:
            code = status - ST_TRAPPED_BASE
            trap_row = self._trap()[lo:lo + Lblk]
            codes = np.where(trap_row != 0, trap_row, code).astype(np.int32)
        self.trap[vids] = codes[valid]
        self.retired[vids] = self.block_steps[b]
        self._free_block(b)

    def _free_block(self, b: int):
        """Park the slot (host mirror only; uploaded before the next
        launch)."""
        self.block_state[b] = _B_FREE
        self._ctrl()[b, _C_STATUS] = ST_DONE
        self._ctrl_dirty = True
        self.obs.instant("block_free", cat="scheduler", track=self._track,
                         block=b)

    # -- split machinery ---------------------------------------------------
    def _split(self, b: int, ctrl_np, status: int) -> int:
        """Resolve a stopped block: evaluate the divergent instruction
        per lane, partition lanes by outcome, install uniform children.
        Returns the number of children queued for a block slot."""
        eng = self.eng
        ctrl = ctrl_np[b].copy()
        frames = self._frames()[b]
        pages_over = eng._pages_override.pop(b, None)
        self.splits += 1
        self.obs.instant("split", cat="scheduler", track=self._track,
                         block=b, pc=int(ctrl[_C_PC]), status=status,
                         splits=self.splits)
        queued = len(self._pending)
        # The kernel counts no step that ends DIVERGED, whether or not
        # it advanced control.  Where it did advance (a trap-partial
        # site: the trap plane holds the codes) the instruction is
        # retired here; a serve's re-arm sits past a call the kernel
        # had counted when it parked the block.
        lo = b * self.Lblk
        advanced = int(status == ST_DIVERGED
                       and b not in self._served_stops
                       and self._trap()[lo:lo + self.Lblk].any())
        self._served_stops.discard(b)
        if status == ST_REGROW or self.splits > self.split_budget:
            self._to_simt(b, ctrl, frames, pages_over, advanced)
            return 0
        pc = int(ctrl[_C_PC])
        hid = int(eng._np_fused["hid"][pc])
        if hid >= H_BLOCK_BASE:
            # stop at a fused block head (its first op bailed): the
            # operand fields are the original op's, so resolve via the
            # original opcode instead of demoting the lanes to SIMT
            hid = int(eng._np_hid_orig[pc])
        if not self._try_resolve(b, ctrl, frames, hid, pc, pages_over,
                                 advanced):
            self._to_simt(b, ctrl, frames, pages_over, advanced)
        return len(self._pending) - queued

    def _try_resolve(self, b, ctrl, frames, hid, pc, pages_over,
                     advanced=0) -> bool:
        """Dispatch on the stopped instruction.  Returns False when the
        case must go to the SIMT residue.  An instruction evaluated
        here retires with the children (`resolved=1`); `advanced` is 1
        where the kernel advanced past one it left uncounted."""
        fused = self.eng._np_fused
        sp = int(ctrl[_C_SP])
        ob = int(ctrl[_C_OB])
        a = int(fused["a"][pc])
        b_op = int(fused["b"][pc])
        c_op = int(fused["c"][pc])
        Lblk = self.Lblk
        lo = b * Lblk
        # lazy per-row download: the resolver inspects only a handful of
        # stack rows; whole-plane transfers would ride the slow host link
        slo = _Rows(self.link.d2h, self.state[2], lo, Lblk)
        shi = _Rows(self.link.d2h, self.state[3], lo, Lblk)
        trap_row = self._trap()[lo:lo + Lblk]

        # Advanced-with-per-lane-outcomes stops come FIRST, regardless of
        # what instruction ctrl now points at: trap-partial sites (div/rem
        # by zero, partial-OOB memory ops) and served hostcalls advance
        # control uniformly and record per-lane trap codes / grown pages —
        # the divergence IS those outcomes, not the next opcode.  Peel
        # trapped lanes off; the rest resume RUNNING at the current ctrl.
        # (Live blocks otherwise carry all-zero trap planes: every split
        # hands children trap-free columns.)
        if trap_row.any() or pages_over is not None:
            keys = [trap_row.astype(np.int64)]
            if pages_over is not None:
                keys.append(pages_over.astype(np.int64))
            children = []
            for key, cols in self._partition(keys):
                cc = ctrl.copy()
                code = int(key[0])
                cc[_C_STATUS] = (ST_TRAPPED_BASE + code) if code \
                    else ST_RUNNING
                if pages_over is not None:
                    cc[_C_PAGES] = int(key[1])
                children.append((cc, frames.copy(), cols, {}))
            self._install_children(b, children, resolved=advanced)
            return True

        if hid == H_BRZ:
            cond = _u32(slo[sp - 1])
            children = []
            for key, cols in self._partition([(cond == 0).astype(np.int64)]):
                cc = ctrl.copy()
                cc[_C_PC] = a if key[0] else pc + 1
                cc[_C_SP] = sp - 1
                cc[_C_STATUS] = ST_RUNNING
                children.append((cc, frames.copy(), cols, {}))
            self._install_children(b, children, resolved=1)
            return True

        if hid == H_BRNZ:
            cond = _u32(slo[sp - 1])
            tgt_sp = ob + c_op
            children = []
            for key, cols in self._partition([(cond != 0).astype(np.int64)]):
                cc = ctrl.copy()
                writes = {}
                if key[0]:  # taken
                    cc[_C_PC] = a
                    cc[_C_SP] = tgt_sp + b_op
                    if b_op == 1:
                        writes[("stack", tgt_sp)] = (slo[sp - 2, cols],
                                                     shi[sp - 2, cols])
                else:
                    cc[_C_PC] = pc + 1
                    cc[_C_SP] = sp - 1
                cc[_C_STATUS] = ST_RUNNING
                children.append((cc, frames.copy(), cols, writes))
            self._install_children(b, children, resolved=1)
            return True

        if hid == H_BR_TABLE:
            idx = _u32(slo[sp - 1])
            brt = self.eng.img.br_table
            ii = np.minimum(idx, b_op)
            children = []
            for key, cols in self._partition([ii]):
                e = a + int(key[0])
                tgt, nkeep, pop_to = (int(brt[e, 0]), int(brt[e, 1]),
                                     int(brt[e, 2]))
                tgt_sp = ob + pop_to
                cc = ctrl.copy()
                cc[_C_PC] = tgt
                cc[_C_SP] = tgt_sp + nkeep
                cc[_C_STATUS] = ST_RUNNING
                writes = {}
                if nkeep == 1:
                    writes[("stack", tgt_sp)] = (slo[sp - 2, cols],
                                                 shi[sp - 2, cols])
                children.append((cc, frames.copy(), cols, writes))
            self._install_children(b, children, resolved=1)
            return True

        if hid == H_CALL_INDIRECT:
            idx = _u32(slo[sp - 1])
            tbl = self.eng.img.table0
            children = []
            for key, cols in self._partition([idx]):
                i0 = int(key[0])
                cc = ctrl.copy()
                code = 0
                if i0 >= b_op:
                    code = int(ErrCode.UndefinedElement)
                else:
                    h = int(tbl[min(c_op + i0, len(tbl) - 1)])
                    if h == 0:
                        code = int(ErrCode.UninitializedElement)
                    elif int(self.eng.img.f_type[h - 1]) != a:
                        code = int(ErrCode.IndirectCallTypeMismatch)
                if code:
                    cc[_C_STATUS] = ST_TRAPPED_BASE + code
                    children.append((cc, frames.copy(), cols, {}))
                    continue
                cc[_C_SP] = sp - 1
                trip = self._host_call(cc, frames.copy(), h - 1, sp - 1, pc)
                children.append((trip[0], trip[1], cols, trip[2]))
            self._install_children(b, children, resolved=1)
            return True

        if hid == H_MEMGROW:
            delta = slo[sp - 1].astype(np.int64)
            img = self.eng.img
            cap = self.eng._geom[2] // _PAGE_WORDS if img.has_memory else 0
            hard = max(img.mem_pages_max, img.mem_pages_init) \
                if img.has_memory else 0
            pages = int(ctrl[_C_PAGES])
            children = []
            for key, cols in self._partition([delta]):
                d = int(key[0])
                legal = 0 <= d and pages + d <= hard
                if legal and pages + d > cap:
                    return False  # needs the big-plane engine
                cc = ctrl.copy()
                cc[_C_PC] = pc + 1
                cc[_C_PAGES] = pages + d if legal else pages
                cc[_C_STATUS] = ST_RUNNING
                writes = {("stack", sp - 1): (
                    np.full(len(cols), pages if legal else -1, np.int32),
                    np.zeros(len(cols), np.int32))}
                children.append((cc, frames.copy(), cols, writes))
            self._install_children(b, children, resolved=1)
            return True

        # data-divergent loads/stores/copies (no trap codes, control not
        # advanced) need per-lane memory addressing -> SIMT
        return False

    def _host_call(self, cc, frames, callee, sp_eff, pc):
        """Apply _do_call semantics host-side for one uniform side."""
        img = self.eng.img
        D, CD = self.eng._geom[0], self.eng._geom[1]
        nargs = int(img.f_nparams[callee])
        nloc = int(img.f_nlocals[callee])
        cd = int(cc[_C_CD])
        fp_new = sp_eff - nargs
        ob_new = fp_new + nloc
        if cd >= CD - 1:
            cc[_C_STATUS] = ST_TRAPPED_BASE + int(ErrCode.CallStackExhausted)
            return cc, frames, {}
        if fp_new + int(img.f_frame_top[callee]) > D:
            cc[_C_STATUS] = ST_TRAPPED_BASE + int(ErrCode.StackOverflow)
            return cc, frames, {}
        frames[0, cd] = pc + 1
        frames[1, cd] = int(cc[_C_FP])
        frames[2, cd] = int(cc[_C_OB])
        writes = {}
        for k in range(nloc - nargs):
            writes[("stack", fp_new + nargs + k)] = (0, 0)
        cc[_C_PC] = int(img.f_entry[callee])
        cc[_C_SP] = ob_new
        cc[_C_FP] = fp_new
        cc[_C_OB] = ob_new
        cc[_C_CD] = cd + 1
        cc[_C_STATUS] = ST_RUNNING
        return cc, frames, writes

    @staticmethod
    def _partition(keys: List[np.ndarray]):
        """Partition columns by key tuples, first-seen order.  Pads carry
        their clone source's data, so they follow its side and stay
        harmless clones there."""
        rows = np.stack([np.asarray(k, np.int64) for k in keys], axis=1)
        uniq, first, inverse = np.unique(
            rows, axis=0, return_index=True, return_inverse=True)
        inverse = inverse.reshape(-1)
        # a group's columns in ascending order (stable sort), the groups
        # by their first column
        cols = np.split(np.argsort(inverse, kind="stable"),
                        np.cumsum(np.bincount(inverse))[:-1])
        return [(tuple(int(v) for v in uniq[g]), cols[g])
                for g in np.argsort(first, kind="stable")]

    def _install_children(self, b: int, children, resolved: int = 0):
        """Queue child groups; immediately-trapped ones harvest in place.
        `resolved` is 1 where the host evaluated the stopped instruction
        for the children (`_try_resolve`'s five opcodes): it retires
        with them, as it does on every other engine, and `max_steps`
        sees it; or where the kernel advanced past a trap-partial site
        without counting it (`_split`)."""
        ids = self.block_lanes[b]
        steps0 = int(self.block_steps[b]) + resolved
        for (cc, fr, cols, writes) in children:
            lane_ids = ids[cols]
            sel = lane_ids >= 0
            if not sel.any():
                continue  # a pad-only side: drop it
            st = int(cc[_C_STATUS])
            if st >= ST_TRAPPED_BASE:
                vids = lane_ids[sel].astype(np.int64)
                self.trap[vids] = st - ST_TRAPPED_BASE
                self.retired[vids] = steps0
                continue
            vcols = cols[sel]
            child_cols = self._extract_cols(b, vcols, writes, sel)
            cc[_C_CHUNK] = self.cfg.steps_per_launch
            # a child starts fresh, as a block planned at entry does,
            # whatever halvings its parent's row carries.  The kernel's
            # short first interval bounds the run-up if the child
            # diverges again at once.
            self.snap_restored += self._snap.restores(cc[_C_SNAP])
            cc[_C_SNAP] = self._snap.full
            self._pending.append(_Pending(
                ctrl=cc, frames=fr, cols=child_cols,
                lane_ids=lane_ids[sel].astype(np.int64), steps0=steps0))
        self._free_block(b)

    def _extract_cols(self, b: int, cols, writes, sel=None):
        """Snapshot a child's valid columns as DEVICE arrays: one
        compiled gather over every plane (no host transfer), at the
        child's lane count padded to a power of two (`_pad_width`; the
        pads repeat its first column), then the side's writes.

        `writes` values are either (lo, hi) scalars or (lo, hi) arrays
        indexed like the PRE-selection column list; `sel`, which comes
        with them, maps them down to the valid columns.  They are rare (a value carried under
        brnz/br_table, zeroed locals under call_indirect, memory.grow's
        result) and stay eager row sets on the padded child."""
        n = len(cols)
        pad = _clone_pad(n, _pad_width(n, self.Lblk))
        idx = (b * self.Lblk + np.asarray(cols)[pad]).astype(np.int32)
        self.surgery_programs += 1
        out = dict(zip(self._plane_idx, self.link.enqueue(
            "extract", self._surgery[0],
            tuple(self.state[i] for i in self._plane_idx.values()), idx)))
        for key, val in writes.items():
            row = key[1]
            vlo, vhi = (np.asarray(v)[sel][pad] if np.ndim(v) else v
                        for v in val)
            out["slo"] = out["slo"].at[row].set(self.link.h2d("rows", vlo))
            out["shi"] = out["shi"].at[row].set(self.link.h2d("rows", vhi))
        return out

    def _install_pending(self) -> bool:
        """Move queued children into free block slots.  A child is one
        compiled program over all planes (the snapshots are device
        arrays), so no state crosses the host link."""
        if not self._pending:
            return False
        free = [b for b in range(self.nblk)
                if self.block_state[b] == _B_FREE]
        if not free:
            return False
        with self._phase("batch/install",
                         blocks=min(len(free), len(self._pending))):
            self._install(free)
        return True

    def _install(self, free):
        """Set each child's columns, clone-padded to the block width,
        into a free slot's columns of every plane, in place (the planes
        are donated to the program); `ctrl`, `frames` and the block
        tables are host mirrors; the rollback shadows are not written."""
        ctrl = self._ctrl()
        frames = self._frames()
        Lblk = self.Lblk
        planes = list(self._plane_idx.values())
        while self._pending and free:
            p = self._pending.pop(0)
            b = free.pop(0)
            n = len(p.lane_ids)
            self.surgery_programs += 1
            out = self.link.enqueue(
                "install", self._surgery[1],
                tuple(self.state[i] for i in planes),
                tuple(p.cols[name] for name in self._plane_idx),
                _clone_pad(n, Lblk), np.int32(b * Lblk))
            for i, plane in zip(planes, out):
                self.state[i] = plane
            self._drop_plane_mirrors()   # it wrote the slot's columns
            ctrl[b] = p.ctrl
            frames[b] = p.frames
            ids = np.full(Lblk, -1, np.int64)
            ids[:n] = p.lane_ids
            self.block_lanes[b] = ids
            self.block_state[b] = _B_LIVE
            self.block_steps[b] = p.steps0
            self._ctrl_dirty = True
            self._frames_dirty = True

    # -- SIMT residue ------------------------------------------------------
    def _to_simt(self, b: int, ctrl, frames, pages_over=None, advanced=0):
        """Queue a block's valid lanes for the final SIMT pass, which
        runs the stopped instruction again unless the kernel `advanced`
        past it."""
        ids = self.block_lanes[b]
        vcols = np.nonzero(ids >= 0)[0]
        self.obs.instant("simt_residue_queue", cat="scheduler",
                         track=self._track, block=b, lanes=int(vcols.size))
        cols = self._extract_cols(b, vcols, {})
        self._simt_queue.append(_Pending(
            ctrl=ctrl.copy(), frames=frames.copy(), cols=cols,
            lane_ids=ids[vcols].astype(np.int64),
            steps0=int(self.block_steps[b]) + advanced,
            pages=pages_over[vcols].astype(np.int32)
            if pages_over is not None else None))
        self._free_block(b)

    def _run_simt_residue(self):
        if not self._simt_queue:
            return
        with self._phase("batch/residue", groups=len(self._simt_queue)):
            self._simt_residue()

    def _simt_residue(self):
        from wasmedge_tpu.batch.engine import BatchState

        self.fell_back_to_simt = True
        t_residue = self.obs.now()
        simt = self.eng.simt
        cfg = self.cfg
        L = simt.lanes
        D_s, CD_s = cfg.value_stack_depth, cfg.call_stack_depth
        img = self.eng.img
        simt_w = max(img.mem_pages_max * _PAGE_WORDS, 1) \
            if img.has_memory else 1
        NG = max(img.globals_lo.shape[0], 1)
        pc = np.zeros(L, np.int32)
        sp = np.zeros(L, np.int32)
        fp = np.zeros(L, np.int32)
        ob = np.zeros(L, np.int32)
        cd = np.zeros(L, np.int32)
        pages = np.zeros(L, np.int32)
        fuel = np.zeros(L, np.int32)
        trap = np.full(L, TRAP_DONE, np.int32)   # non-members: done
        retired0 = np.zeros(L, np.int64)
        s_lo = np.zeros((D_s, L), np.int32)
        s_hi = np.zeros((D_s, L), np.int32)
        g_lo = np.zeros((NG, L), np.int32)
        g_hi = np.zeros((NG, L), np.int32)
        mem = np.zeros((simt_w, L), np.int32)
        frp = np.zeros((CD_s, L), np.int32)
        frf = np.zeros((CD_s, L), np.int32)
        fro = np.zeros((CD_s, L), np.int32)
        simd = img.has_simd
        s_e2 = np.zeros((D_s, L), np.int32) if simd else None
        s_e3 = np.zeros((D_s, L), np.int32) if simd else None
        members = []
        d2h, h2d = self.link.d2h, self.link.h2d
        for p in self._simt_queue:
            n = len(p.lane_ids)
            li = p.lane_ids
            members.append(li)
            pc[li] = p.ctrl[_C_PC]
            sp[li] = p.ctrl[_C_SP]
            fp[li] = p.ctrl[_C_FP]
            ob[li] = p.ctrl[_C_OB]
            cd[li] = p.ctrl[_C_CD]
            pages[li] = p.ctrl[_C_PAGES] if p.pages is None else p.pages
            if cfg.fuel_per_launch is not None:
                fuel[li] = max(int(p.ctrl[_C_FUEL]), 0)
            trap[li] = d2h("trap", p.cols["trap"], np.s_[0, :n])
            retired0[li] = p.steps0
            d = min(p.cols["slo"].shape[0], D_s)
            s_lo[:d, li] = d2h("slo", p.cols["slo"], np.s_[:d, :n])
            s_hi[:d, li] = d2h("shi", p.cols["shi"], np.s_[:d, :n])
            if simd:
                s_e2[:d, li] = d2h("se2", p.cols["se2"], np.s_[:d, :n])
                s_e3[:d, li] = d2h("se3", p.cols["se3"], np.s_[:d, :n])
            g = min(p.cols["glo"].shape[0], NG)
            g_lo[:g, li] = d2h("glo", p.cols["glo"], np.s_[:g, :n])
            g_hi[:g, li] = d2h("ghi", p.cols["ghi"], np.s_[:g, :n])
            m = min(p.cols["mem"].shape[0], simt_w)
            mem[:m, li] = d2h("mem", p.cols["mem"], np.s_[:m, :n])
            ncd = min(p.frames.shape[1], CD_s)
            frp[:ncd, li] = p.frames[0, :ncd, None]
            frf[:ncd, li] = p.frames[1, :ncd, None]
            fro[:ncd, li] = p.frames[2, :ncd, None]
        from wasmedge_tpu.batch.engine import t0_state_planes

        state = BatchState(
            **{name: h2d(name, plane) for name, plane in dict(
                pc=pc, sp=sp, fp=fp, opbase=ob, call_depth=cd, trap=trap,
                retired=np.zeros(L, np.int32), fuel=fuel, mem_pages=pages,
                stack_lo=s_lo, stack_hi=s_hi, fr_ret_pc=frp, fr_fp=frf,
                fr_opbase=fro, glob_lo=g_lo, glob_hi=g_hi, mem=mem).items()},
            stack_e2=h2d("stack_e2", s_e2) if simd else None,
            stack_e3=h2d("stack_e3", s_e3) if simd else None,
            **t0_state_planes(img, cfg, L,
                              getattr(simt, "_t0kinds", None)))
        # account for work already done on the kernel so the caller's
        # max_steps bounds TOTAL execution, not each engine separately
        # (coarse like the pre-scheduler handoff: the max over members)
        total0 = max(int(p.steps0) for p in self._simt_queue)
        # v128 quarantine (VERDICT r5 weak #1): the XLA per-step v128
        # fallback is known to fault TPU workers on very long runs, so
        # a divergent v128 tenant's residue is step-capped; survivors
        # are re-run on the scalar engine (side-effect-free modules) or
        # trapped CostLimitExceeded instead of crashing the device
        # process under every other tenant.
        cap = getattr(cfg, "v128_residue_step_cap", None)
        simd_capped = bool(img.has_simd) and cap is not None
        max_steps_eff = min(self.max_steps, total0 + int(cap)) \
            if simd_capped else self.max_steps
        state, total = simt.run_from_state(state, total0, max_steps_eff)
        self._residue_steps = int(total)
        all_m = np.concatenate(members)
        trap_f = d2h("trap", state.trap)
        ret_f = d2h("retired", state.retired).astype(np.int64)
        self.trap[all_m] = trap_f[all_m]
        self.retired[all_m] = retired0[all_m] + ret_f[all_m]
        if self.nres:
            s_lo_f = d2h("res_lo", state.stack_lo, np.s_[:self.nres])
            s_hi_f = d2h("res_hi", state.stack_hi, np.s_[:self.nres])
            self.res_lo[:, all_m] = s_lo_f[:, all_m]
            self.res_hi[:, all_m] = s_hi_f[:, all_m]
        self.obs.span("simt_residue", t_residue, cat="scheduler",
                      track=self._track_simt, lanes=int(all_m.size),
                      steps=int(total))
        if simd_capped and max_steps_eff < self.max_steps:
            survivors = all_m[trap_f[all_m] == 0]
            if survivors.size:
                self._quarantine_lanes(survivors)

    def _quarantine_lanes(self, lanes: np.ndarray):
        """Lanes still running when the v128 residue cap hit: re-run
        them from their original arguments on the scalar engine when
        the module is side-effect-free (no host imports), else report
        CostLimitExceeded.  Either way the device process survives.

        The gas-metered scalar re-run itself is the shared bottom rung
        of the supervisor's degradation ladder (batch/supervisor.py
        scalar_rerun); host-side errors inside it surface as
        FailureRecords in the process-wide log instead of being
        silently swallowed."""
        self.quarantined = getattr(self, "quarantined", 0) + int(lanes.size)
        self.obs.instant("quarantine", cat="scheduler", track=self._track_simt,
                         lanes=int(lanes.size))
        inst = self.inst
        has_host = any(getattr(f, "kind", None) == "host"
                       for f in inst.funcs)
        if has_host:
            self.trap[lanes] = int(ErrCode.CostLimitExceeded)
            return
        from wasmedge_tpu.batch.supervisor import scalar_rerun
        from wasmedge_tpu.common.statistics import record_failure

        cells, trap_codes, records = scalar_rerun(
            inst, getattr(self.eng.simt, "conf", None), self.func_name,
            self.func_idx, self.args, np.asarray(lanes, np.int64),
            self.max_steps)
        for rec in records:
            record_failure(rec)
        nres = len(inst.funcs[self.func_idx].functype.results)
        for col, lane in enumerate(np.asarray(lanes, np.int64)):
            code = int(trap_codes[col])
            if code == TRAP_DONE:
                for r in range(nres):
                    cell = int(cells[r, col])
                    self.res_lo[r, lane] = np.int32(np.uint32(
                        cell & 0xFFFFFFFF))
                    self.res_hi[r, lane] = np.int32(np.uint32(
                        (cell >> 32) & 0xFFFFFFFF))
            self.trap[int(lane)] = code

    # -- result ------------------------------------------------------------
    def result(self):
        from wasmedge_tpu.batch.engine import BatchResult
        from wasmedge_tpu.batch.pallas_engine import decode_result_rows

        with self._phase("batch/result"):
            results = decode_result_rows(self.res_lo, self.res_hi,
                                         self.nres)
            steps = max(int(self.block_steps.max(initial=0)),
                        getattr(self, "_residue_steps", 0))
            return BatchResult(results=results, trap=self.trap,
                               retired=self.retired, steps=steps)
