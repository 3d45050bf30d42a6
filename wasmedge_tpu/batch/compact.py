"""Divergence-aware lane compaction: PC-sorted lane regrouping at
launch boundaries (ROADMAP #6a).

SIMT lanes that sit at different PCs interleave arbitrarily across the
lane axis: the dispatch step still walks every allocated column, retired
lanes keep occupying dispatch width until batch drain, and convergent
neighbourhoods (which the fused-superinstruction heads and the Pallas
block tier exploit) are destroyed by admission order.  GPUs solve the
same problem by regrouping threads at convergence points ("Control Flow
Management in Modern GPUs", PAPERS.md); this module is that regrouping
pass for the BatchState plane columns.

At a launch boundary the compactor:

  1. reads the round's pc/trap host mirrors (the trap mirror is pulled
     every round anyway; pc is one extra [lanes] int32 transfer, paid
     only when the anti-thrash quantum allows a fire);
  2. estimates divergence: adjacent-pair key breaks in the current lane
     order vs the minimum achievable (#distinct keys - 1) — the win a
     sort can buy — plus the live-lane count (the win a live-prefix
     pack can buy);
  3. decides via a deterministic cost model (skip when the estimated
     win is below the permutation's copy cost, never fire more often
     than `compact_min_interval` rounds — the same anti-thrash shape as
     hv's `min_resident_rounds`);
  4. fires ONE jitted gather-permutation over every lane-trailing
     BatchState plane (the same column-move seam the recycler, hv
     swap-in, and mesh migration use): live lanes sort to a contiguous
     prefix ordered by (divergence-score bias, pc) — high-divergence
     neighbourhoods group first, per the analyzer's r12 block scores —
     retired lanes sink to the tail;
  5. (fixed-cohort runs only) NARROWS the dispatch width to the
     smallest power of two covering the live prefix: subsequent chunk
     launches run a width-variant step over the prefix slice and write
     it back, so dead lanes stop costing dispatch work entirely.  This
     is where the raw-speed win lands on every backend; the pure
     permutation additionally restores convergent neighbourhoods for
     the fused heads and the kernel tier.

The permutation is tracked as `src` (physical position -> original lane
index, a bijection by construction): harvest paths gather results back
into original lane order through `restore_order()`, checkpoints journal
it as a `lane_src` array so crash/resume keeps the mapping, and the
serving layer (serve/server.py) instead remaps its lane->request
binding and hv virtual-lane tables through the permutation — harvest,
recycling, swap, checkpoints, and the exactly-once stdout cursors all
follow their lane.

Scoping (same caveat as recycling and hv): results are bit-identical
with compaction on/off for lane-placement-independent guests — tier-0
`random_get` keys its stream on the physical lane index, so a
random-drawing guest's output depends on placement, as at any other
lane position.  The shared stdout fd is drained in PHYSICAL lane
order, so the CROSS-lane interleaving of a multi-writer cohort's
stream follows the permutation too (each lane's own bytes stay
in-order and exactly-once; a recycled serving mix already interleaves
by placement).  `Configure.batch.compact` off (the default) compiles
and executes the exact seed path: nothing is pulled, permuted, or
rebuilt.  On a shard-drive mesh the permutation is block-diagonal per
device shard (no cross-device moves) and narrowing is disabled (the
global width is pinned by the sharding).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional

import numpy as np

from wasmedge_tpu.batch.image import TRAP_HOSTCALL


class CompactDecision(NamedTuple):
    """Deterministic boundary decision (pure function of the mirrors
    and the knobs — pinned by tests/test_compact.py)."""

    fire: bool
    reason: str            # "fire" | "idle" | "interval" | "cost"
    nlive: int
    breaks: int            # adjacent key mismatches in current order
    ideal_breaks: int      # minimum achievable after a sort
    unique_pcs: int        # distinct live pcs
    largest_group: float   # largest convergent group / live lanes
    narrow_width: int      # dispatch width after this boundary


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def live_mask(trap: np.ndarray) -> np.ndarray:
    """Lanes that can still execute: running, or parked at a hostcall
    stub (TRAP_HOSTCALL lanes re-arm and must stay in the live
    prefix).  Finished/trapped lanes never resume in a cohort run."""
    trap = np.asarray(trap)
    return (trap == 0) | (trap == TRAP_HOSTCALL)


def divergence_key(img) -> Optional[np.ndarray]:
    """Per-pc divergence score from the analyzer's r12 per-block
    scores (block pc ranges -> block_divergence), used to bias the
    sort so high-divergence neighbourhoods group first.  None when no
    analysis is attached (concatenated multi-tenant images, analyzer
    failure) — the sort degrades to a pure (pc) key.  Never raises:
    compaction is a performance pass, not a correctness gate."""
    try:
        analysis = getattr(img, "analysis", None)
        if analysis is None:
            return None
        out = np.zeros(int(img.code_len), np.int32)
        for f in analysis.funcs:
            for bi, b in enumerate(f.cfg.blocks):
                lo = max(int(b.start), 0)
                hi = min(int(b.end), out.size - 1)
                if hi >= lo:
                    out[lo:hi + 1] = int(f.block_divergence[bi])
        return out
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        return None


def function_key(img) -> Optional[np.ndarray]:
    """Per-pc ENGINE-GLOBAL function ordinal from the image's entry-pc
    plane, the r20 coarse grouping key: on a multi-tenant concatenated
    image f_entry is already rebased per tenant, so sorting by it first
    regroups serving mixes per function (and per tenant) before the
    finer (divergence, pc) keys order lanes inside one body.  Needs no
    analysis — unlike divergence_key it works on concatenated images.
    Never raises: compaction is a performance pass, not a correctness
    gate."""
    try:
        entries = np.asarray(getattr(img, "f_entry"), np.int64)
        entries = np.sort(entries[entries >= 0])
        if entries.size == 0:
            return None
        pcs = np.arange(int(img.code_len), dtype=np.int64)
        return np.searchsorted(entries, pcs, side="right").astype(
            np.int64) - 1
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception:
        return None


def estimate_breaks(pc: np.ndarray, live: np.ndarray,
                    shard_slices: Optional[List[slice]] = None):
    """(breaks, ideal_breaks, unique_pcs, largest_group_fraction) of
    the current lane order: `breaks` counts adjacent lane pairs whose
    (live, pc) keys differ (dead lanes are one shared key), `ideal`
    is the minimum after a perfect sort.  With `shard_slices` both are
    computed per shard block and summed — a shard-blocked permutation
    can neither fix cross-shard breaks nor merge per-shard groups, so
    a globally-computed ideal would leave win > 0 forever on an
    already-shard-sorted mesh and the policy would fire no-op
    permutations every quantum.  unique/largest stay global (they are
    convergence METRICS, not the cost model)."""
    key = np.where(live, np.asarray(pc, np.int64), np.int64(-1))
    nlive = int(live.sum())
    breaks = ideal = 0
    for sl in (shard_slices or [slice(0, key.size)]):
        ks, ls = key[sl], live[sl]
        breaks += int(np.count_nonzero(ks[1:] != ks[:-1]))
        ns = int(ls.sum())
        if ns:
            ideal += int(np.unique(ks[ls]).size) - 1 \
                + (1 if ns < ls.size else 0)
    if nlive == 0:
        return breaks, 0, 0, 1.0
    _, counts = np.unique(key[live], return_counts=True)
    return breaks, ideal, int(counts.size), float(counts.max()) / nlive


def build_permutation(pc: np.ndarray, trap: np.ndarray,
                      dscore: Optional[np.ndarray] = None,
                      shard_slices: Optional[List[slice]] = None,
                      fnkey: Optional[np.ndarray] = None
                      ) -> np.ndarray:
    """The boundary permutation as `perm` (destination -> source lane):
    new_plane[..., d] = old_plane[..., perm[d]].  Within each shard
    slice (the whole array when None — no cross-device moves on a
    mesh), live lanes sort to the front keyed by (function ordinal,
    descending divergence score, pc, original position) and dead lanes
    keep their relative order at the tail.  A bijection by
    construction; stable, so an already-grouped population is a
    no-op.  `fnkey` (function_key) is the r20 coarse group: lanes
    executing the same function become contiguous before the finer
    keys order them within it."""
    pc = np.asarray(pc, np.int64)
    live = live_mask(trap)
    n = pc.size
    if dscore is not None and dscore.size:
        score = np.asarray(dscore, np.int64)[np.clip(pc, 0,
                                                     dscore.size - 1)]
    else:
        score = np.zeros(n, np.int64)
    if fnkey is not None and fnkey.size:
        fk = np.asarray(fnkey, np.int64)[np.clip(pc, 0,
                                                 fnkey.size - 1)]
    else:
        fk = np.zeros(n, np.int64)
    dead = (~live).astype(np.int64)
    pckey = np.where(live, pc, np.int64(0))
    skey = np.where(live, -score, np.int64(0))
    fkey = np.where(live, fk, np.int64(0))
    pos = np.arange(n, dtype=np.int64)
    perm = np.empty(n, np.int64)
    for sl in (shard_slices or [slice(0, n)]):
        # np.lexsort: LAST key is primary ->
        # (dead, fn, -score, pc, pos)
        order = np.lexsort((pos[sl], pckey[sl], skey[sl], fkey[sl],
                            dead[sl]))
        perm[sl] = order + sl.start
    return perm


def compact_decision(pc: np.ndarray, trap: np.ndarray, width: int,
                     steps_per_launch: int, rounds_since_fire: int,
                     knobs, can_narrow: bool,
                     shard_slices: Optional[List[slice]] = None
                     ) -> CompactDecision:
    """The deterministic when-to-fire policy (cost model + trigger +
    anti-thrash quantum).  `width` is the current dispatch width; the
    copy cost of one permutation is modelled as `compact_cost_factor`
    lane-steps per lane, the win as one saved break per dispatched
    step (sorting) plus the narrowed slice (packing).  `shard_slices`
    bounds the win to what a shard-blocked permutation can achieve."""
    interval = max(int(getattr(knobs, "compact_min_interval", 2)), 1)
    trigger = float(getattr(knobs, "compact_trigger", 0.05))
    cost_factor = float(getattr(knobs, "compact_cost_factor", 4.0))
    floor = max(int(getattr(knobs, "compact_width_floor", 64)), 1)
    lanes = int(np.asarray(trap).size)
    live = live_mask(trap)
    breaks, ideal, unique, largest = estimate_breaks(pc, live,
                                                     shard_slices)
    nlive = int(live.sum())
    narrow_w = int(width)
    if can_narrow and nlive > 0:
        target = min(max(next_pow2(nlive), floor), int(width))
        if target < width:
            narrow_w = target
    if nlive == 0:
        return CompactDecision(False, "idle", 0, breaks, ideal, unique,
                               largest, int(width))
    if rounds_since_fire < interval:
        return CompactDecision(False, "interval", nlive, breaks, ideal,
                               unique, largest, int(width))
    win = max(breaks - ideal, 0)
    sort_pays = (win >= 1 and win >= trigger * nlive
                 and win * max(int(steps_per_launch), 1)
                 >= cost_factor * lanes)
    if not sort_pays and narrow_w >= width:
        return CompactDecision(False, "cost", nlive, breaks, ideal,
                               unique, largest, int(width))
    return CompactDecision(True, "fire", nlive, breaks, ideal, unique,
                           largest, narrow_w)


def _lane_plane_names(state, lanes: int):
    from wasmedge_tpu.hv.swapstore import lane_plane_names

    return lane_plane_names(state, lanes)


def make_permute(lane_names):
    """Build the jitted gather-permutation over the lane-trailing
    planes (ONE pass, donation discipline shared with the recycler's
    install and hv's column restore).  `lane_names` is the frozen set
    of plane names carrying a lane axis — laneless planes (op_hist,
    fu_ctr) and None planes pass through untouched.

    jit-purity lint target (tools/lint_jit_purity.py): everything
    nested here runs under trace.
    """
    import jax.numpy as jnp

    from wasmedge_tpu.batch import jit_in_place

    names = tuple(lane_names)

    def permute(state, perm):
        updates = {}
        for name in names:
            plane = getattr(state, name)
            updates[name] = jnp.take(plane, perm, axis=-1)
        return state._replace(**updates)

    return jit_in_place(permute, 0)


class LaneCompactor:
    """Per-run (engine cohort) or per-server lane compaction state:
    the composed permutation (`src`), the current dispatch width, the
    jitted permute pass, and the width-variant chunk cache.

    The cohort drivers (BatchEngine.run, ShardDrive.run, the uniform
    engine's divergence handoff, the batch supervisor's SIMT tier) arm
    one on the engine (`engine.compactor`) and `run_from_state` calls
    `boundary()` between rounds; the serving layer instead holds its
    own instance (narrowing off) and remaps its binding tables through
    each fired permutation (serve/server.py _compact_round)."""

    def __init__(self, engine, narrow: Optional[bool] = None):
        self.cfg = engine.cfg
        self.lanes = int(engine.lanes)
        self.mesh = getattr(engine, "mesh", None)
        allow = bool(getattr(self.cfg, "compact_narrow", True))
        if narrow is None:
            narrow = allow and self.mesh is None
        self.narrow = bool(narrow) and allow and self.mesh is None
        self.src = np.arange(self.lanes, dtype=np.int64)
        self.width = self.lanes
        self.rounds = 0
        self.last_fire = -(1 << 30)
        self._dscore = None
        self._dscore_ready = False
        self._fnkey = None
        self._fnkey_ready = False
        self._permute = None
        self._chunks = {}
        self._shards = self._shard_slices()
        self.stats = {"fires": 0, "noop_fires": 0, "rounds": 0,
                      "skipped_interval": 0, "skipped_cost": 0,
                      "moved_lanes": 0, "dispatch_slots": 0,
                      "min_width": self.lanes}

    def _shard_slices(self) -> Optional[List[slice]]:
        if self.mesh is None:
            return None
        from wasmedge_tpu.parallel.shard_drive import shard_slices

        n = int(np.prod(np.asarray(self.mesh.devices).shape))
        return shard_slices(self.lanes, n)

    def dscore(self, img) -> Optional[np.ndarray]:
        if not self._dscore_ready:
            self._dscore = divergence_key(img)
            self._dscore_ready = True
        return self._dscore

    def fnkey(self, img) -> Optional[np.ndarray]:
        if not self._fnkey_ready:
            self._fnkey = function_key(img)
            self._fnkey_ready = True
        return self._fnkey

    # -- permutation bookkeeping -------------------------------------------
    @property
    def identity(self) -> bool:
        return bool((self.src == np.arange(self.lanes)).all())

    def restore_order(self) -> Optional[np.ndarray]:
        """For each ORIGINAL lane index, the physical position holding
        it (argsort of src) — harvest paths gather result mirrors
        through it.  None when no permutation ever fired."""
        if self.identity:
            return None
        return np.argsort(self.src, kind="stable")

    def tick(self) -> bool:
        """One boundary round: False while the anti-thrash quantum
        holds (nothing is pulled or computed on skipped rounds)."""
        self.rounds += 1
        self.stats["rounds"] += 1
        interval = max(int(getattr(self.cfg, "compact_min_interval",
                                   2)), 1)
        if self.rounds - self.last_fire < interval:
            self.stats["skipped_interval"] += 1
            return False
        return True

    def decide(self, pc, trap) -> CompactDecision:
        d = compact_decision(
            pc, trap, self.width, int(self.cfg.steps_per_launch),
            self.rounds - self.last_fire, self.cfg, self.narrow,
            self._shards)
        if not d.fire and d.reason == "cost":
            self.stats["skipped_cost"] += 1
        return d

    def plan_boundary(self, engine, state):
        """tick -> decide -> build, shared by the cohort boundary()
        and the server's _compact_round so the two drivers can never
        drift: returns (decision, perm) when a non-identity
        permutation should be applied, else None.  An identity-perm
        fire still resets the quantum and applies narrowing (via
        fired()) but is NOT counted as a compaction — no lanes
        moved."""
        if not self.tick():
            return None
        trap = np.asarray(state.trap)
        pc = np.asarray(state.pc)
        d = self.decide(pc, trap)
        if not d.fire:
            return None
        perm = build_permutation(pc, trap, self.dscore(engine.img),
                                 self._shards,
                                 fnkey=self.fnkey(engine.img))
        if (perm == np.arange(perm.size)).all():
            self.fired(d, moved=False)
            return None
        return d, perm

    def fired(self, d: CompactDecision, moved: bool = True):
        """Apply a fire's side effects: narrowing + the anti-thrash
        quantum always; the fire COUNT only when lanes actually moved
        (`moved=False` = identity permutation, e.g. a narrowing-only
        boundary on already-sorted lanes) so stats['fires'] and
        wasmedge_compactions_total agree on what a compaction is."""
        if d.narrow_width < self.width:
            self.width = d.narrow_width
            self.stats["min_width"] = min(self.stats["min_width"],
                                          self.width)
        self.last_fire = self.rounds
        self.stats["fires" if moved else "noop_fires"] += 1

    def permute_state(self, engine, state, perm: np.ndarray):
        """Apply one boundary permutation: the jitted gather over the
        lane planes, the host-side exactly-once stdout cursor, and the
        composed src mapping.  Returns the permuted state."""
        import jax.numpy as jnp

        if self._permute is None:
            self._permute = make_permute(
                _lane_plane_names(state, self.lanes))
        state = self._permute(state, jnp.asarray(perm))
        if self.mesh is not None:
            # the gather's output drops the named lane sharding (the
            # permutation is an arbitrary gather to GSPMD); the shard
            # chunk pins its in_shardings, so put the planes back on
            # the mesh before the next launch
            from wasmedge_tpu.parallel.mesh import shard_batch_state

            state = shard_batch_state(state, self.mesh)
        self.src = self.src[perm]
        cur = getattr(engine, "_stdout_cursor", None)
        if cur is not None and cur[0].size == self.lanes:
            cur[0][:] = cur[0][perm]
            cur[1][:] = cur[1][perm]
        self.stats["moved_lanes"] += int((perm
                                          != np.arange(perm.size)).sum())
        return state

    # -- the engine-path boundary hook -------------------------------------
    def boundary(self, engine, state):
        """Called by run_from_state between rounds (fixed-cohort
        drivers).  Decides, permutes, and narrows; emits the `compact`
        instant + latency observation on the engine's recorder.  The
        quantum gate (inside plan_boundary's tick) runs BEFORE any
        device read: an off-cadence round costs nothing beyond a
        counter check."""
        obs = engine.obs
        t0 = obs.now()
        plan = self.plan_boundary(engine, state)
        if plan is None:
            return state
        d, perm = plan
        state = self.permute_state(engine, state, perm)
        self.fired(d)
        obs.observe_compaction(obs.now() - t0)
        obs.instant("compact", cat="compact", track="compact",
                    live=d.nlive, width=self.width,
                    breaks_before=d.breaks, breaks_ideal=d.ideal_breaks,
                    unique_pcs=d.unique_pcs)
        return state

    def note_launch(self, steps: int):
        """Dispatch-slot accounting: one slot per (step, lane) of the
        current dispatch width — the denominator of the
        retired-per-dispatch figure the bench guards."""
        self.stats["dispatch_slots"] += int(steps) * self.width

    def chunk_fn(self, engine):
        """The chunk loop for the current dispatch width: the engine's
        own full-width jit when nothing narrowed, else a width-variant
        cached ON THE ENGINE (a compactor is per-run; the compiled
        variants must survive across runs or every run re-pays the
        trace)."""
        if self.width >= self.lanes:
            return engine._run_chunk
        cache = getattr(engine, "_narrow_chunks", None)
        if cache is None:
            cache = engine._narrow_chunks = {}
        fn = cache.get(self.width)
        if fn is None:
            fn = engine._build_narrow_chunk(self.width)
            cache[self.width] = fn
        return fn


def restore_mirrors(comp, stack_lo, stack_hi, trap, retired):
    """Gather a cohort harvest's result mirrors back to original lane
    order through the compactor's composed permutation (the ONE remap
    seam shared by BatchEngine.run, the uniform handoff harvest, and
    the multi-tenant harvest; the shard drive composes it with its
    pad-strip slice instead).  Identity / no compactor -> unchanged."""
    order = None if comp is None else comp.restore_order()
    if order is None:
        return stack_lo, stack_hi, trap, retired
    return (stack_lo[:, order], stack_hi[:, order],
            trap[order], retired[order])


def arm(engine) -> Optional[LaneCompactor]:
    """Fresh per-run compactor for a cohort driver (None when the knob
    is off).  The serving layer never arms the ENGINE's compactor — it
    owns its own instance and remaps its tables itself."""
    if getattr(engine.cfg, "compact", False) \
            and not getattr(engine, "_compact_external", False):
        engine.compactor = LaneCompactor(engine)
    else:
        engine.compactor = None
    return engine.compactor


def restore_lane_src(engine, src: Optional[np.ndarray]):
    """Checkpoint-restore half of the src tracking: `src` is the
    journaled lane_src array (None when the snapshot predates any
    compaction).  Rolls the engine's compactor back to the snapshot's
    mapping — a restore to an OLDER boundary must also roll back the
    permutation — and refuses a permuted snapshot when compaction is
    unavailable (results would silently come back lane-shuffled)."""
    lanes = int(engine.lanes)
    identity = src is None or bool(
        (np.asarray(src) == np.arange(lanes)).all())
    managed = getattr(engine, "_compact_external", False)
    comp = getattr(engine, "compactor", None)
    if identity:
        if comp is not None:
            comp.src = np.arange(lanes, dtype=np.int64)
            comp.width = lanes
        return
    if managed or not getattr(engine.cfg, "compact", False):
        raise ValueError(
            "checkpoint refused: snapshot carries a lane compaction "
            "permutation (lane_src) but this engine cannot restore it "
            + ("(compaction is externally managed here — supervised "
               "rungs and serving engines run uncompacted)" if managed
               else "(Configure.batch.compact is off)"))
    if comp is None:
        comp = engine.compactor = LaneCompactor(engine)
    comp.src = np.asarray(src, np.int64).copy()
    comp.width = lanes   # restart full-width; narrowing re-fires
