"""LaneVirtualizer: the BatchServer-side lane-virtualization manager.

One instance rides one BatchServer (serve/server.py) and runs entirely
under the server's lock at launch boundaries.  It owns:

  - the VIRTUAL lane table: admitted requests currently off-device,
    either `fresh` (never installed — their state is reproducible from
    func+args through the recycler's template seam, so nothing is
    serialized) or `swapped` (their live plane columns parked in the
    SwapStore under a content key)
  - the boundary REBALANCE: fill free physical lanes with waiting
    virtual lanes first; once the device is full (or the resident-
    bytes budget is), evict policy-chosen victims (hv/policy.py) and
    install waiters into the freed columns — round-robin rotation
    under ties, so every virtual lane keeps making progress
  - per-tenant resident caps: a tenant's `resident_budget_bytes`
    (gateway/tenants.py) divided by the effective per-lane footprint
    caps how many physical lanes its requests may hold at once; over-
    cap requests wait as virtual lanes instead of being rejected
  - the fault seams (`swap_out` / `swap_in` / `swap_store_write`,
    testing/faults.py): a faulted swap-out leaves the lane resident
    and retries at the next boundary; a faulted swap-in re-queues the
    virtual lane without losing it; a corrupt store entry rejects that
    one request machine-readably and the server keeps serving

Results stay bit-identical to a never-swapped run for lane-placement-
independent guests: a swap round-trips the exact plane columns, and
the per-lane interpreter carries no cross-lane state (the same scoping
as the r9 recycler guarantee — tier-0 random_get keys its stream on
the physical lane index, so placement-dependent guests are out of
scope there and here alike).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from wasmedge_tpu.hv.policy import (
    EvictionCandidate,
    effective_lane_bytes,
    pick_victims,
    resident_lane_cap,
)
from wasmedge_tpu.hv.swapstore import (
    SwapCorrupt,
    SwapStore,
    deserialize_lane,
    serialize_lanes,
)


class VirtualLane:
    """One admitted request currently off-device."""

    __slots__ = ("req", "key", "stdout_pos", "admitted_round", "swaps")

    def __init__(self, req, key: Optional[str] = None,
                 stdout_pos: int = 0, admitted_round: int = 0):
        self.req = req
        self.key = key                # None = fresh (never installed)
        self.stdout_pos = int(stdout_pos)
        self.admitted_round = int(admitted_round)
        self.swaps = 0

    @property
    def fresh(self) -> bool:
        return self.key is None

    def journal(self) -> dict:
        """JSON-serializable checkpoint entry (deadlines are monotonic
        stamps and never journaled — same rule as ServeRequest)."""
        return {"id": self.req.id, "func": self.req.func_name,
                "args": [int(a) for a in self.req.args],
                "tenant": self.req.tenant,
                "key": self.key, "stdout_pos": self.stdout_pos}


class LaneVirtualizer:
    """Virtual-lane table + boundary rebalance for one BatchServer.
    Not thread-safe on its own: every entry point runs under the
    owning server's lock."""

    def __init__(self, engine, recycler, knobs, obs, faults=None,
                 record=None, tenant_budgets: Optional[Dict[str, int]] = None):
        self.engine = engine
        self.recycler = recycler
        self.k = knobs
        self.obs = obs
        self.faults = faults
        self._record = record or (lambda fault_class, exc: None)
        self.lanes = int(engine.lanes)
        self.store = SwapStore(dir=knobs.swap_dir, faults=faults)
        # bytes one resident lane charges against the budget: the
        # analyzer's proven footprint bound when available, else the
        # allocated geometry (hv/policy.py)
        self.lane_bytes = effective_lane_bytes(engine)
        self.resident_cap = resident_lane_cap(
            self.lanes, knobs.resident_budget_bytes, self.lane_bytes)
        mv = knobs.max_virtual_lanes
        self.virtual_cap = max(int(mv), 1) if mv is not None else self.lanes
        # kept verbatim so a live reshard (resize) can re-derive the
        # per-tenant caps at the new lane width
        self._tenant_budgets: Dict[str, int] = {
            t: int(b) for t, b in (tenant_budgets or {}).items()
            if b is not None}
        self.tenant_caps: Dict[str, int] = {
            t: resident_lane_cap(self.lanes, b, self.lane_bytes)
            for t, b in self._tenant_budgets.items()}
        self.waiting: "OrderedDict[int, VirtualLane]" = OrderedDict()
        # per-resident-lane tracking (host side)
        self._last_progress: Dict[int, int] = {}
        self._resident_since: Dict[int, int] = {}
        self._last_retired = np.zeros(self.lanes, np.int64)
        self._last_trap = np.zeros(self.lanes, np.int64)
        self._install_jit = [None]
        # server-side install hook (counters/obs the server owns:
        # recycled_lanes, admission latency) — called as
        # install_cb(lane, req, first_install)
        self.install_cb = None
        # server-side loss hook: called with the request just BEFORE a
        # corrupt-entry rejection resolves its future, so the server's
        # outcome counters stay reconcilable (submitted == completed +
        # trapped + expired + killed + rejected)
        self.lost_cb = None
        self.counters = {
            "swaps_in": 0, "swaps_out": 0, "swap_out_faults": 0,
            "swap_in_faults": 0, "swap_corrupt": 0,
            "swap_bytes_out": 0, "swap_bytes_in": 0,
        }
        self.peak_admitted = 0
        self.peak_resident_by_tenant: Dict[str, int] = {}

    # -- geometry ----------------------------------------------------------
    def resize(self, lanes: int):
        """Adopt a grown lane pool after a live reshard (r21,
        serve/server.py reshard): lanes only ever grow, and global
        lane indices are preserved, so resident tracking keeps its
        entries verbatim and the per-lane mirrors pad with zeros (the
        new tail lanes are idle — no progress, trap TRAP_DONE lands
        with the next note_progress).  Budgets re-derive at the new
        width; waiting virtual lanes are keyed by request id and ride
        through untouched."""
        lanes = int(lanes)
        if lanes < self.lanes:
            raise ValueError(
                f"hv resize cannot shrink ({self.lanes} -> {lanes})")
        if lanes == self.lanes:
            return
        grow = lanes - self.lanes
        self.lanes = lanes
        self.resident_cap = resident_lane_cap(
            self.lanes, self.k.resident_budget_bytes, self.lane_bytes)
        mv = self.k.max_virtual_lanes
        self.virtual_cap = max(int(mv), 1) if mv is not None \
            else self.lanes
        self.tenant_caps = {
            t: resident_lane_cap(self.lanes, b, self.lane_bytes)
            for t, b in self._tenant_budgets.items()}
        self._last_retired = np.concatenate(
            [self._last_retired, np.zeros(grow, np.int64)])
        self._last_trap = np.concatenate(
            [self._last_trap, np.zeros(grow, np.int64)])
        self._install_jit = [None]  # retrace at the new state shapes

    # -- admission ---------------------------------------------------------
    def admitted(self, bindings) -> int:
        return len(bindings) + len(self.waiting)

    def headroom(self, bindings) -> int:
        """Virtual-lane slots still open: the oversubscription budget
        the admission phase may pop from the queue this round."""
        return max(self.virtual_cap - self.admitted(bindings), 0)

    def admit(self, req, rnd: int) -> VirtualLane:
        """Register one popped request as a fresh virtual lane (it
        installs onto a physical lane at this or a later boundary's
        rebalance, budget permitting)."""
        v = VirtualLane(req, admitted_round=rnd)
        self.waiting[req.id] = v
        return v

    def note_admitted_peak(self, bindings):
        n = self.admitted(bindings)
        if n > self.peak_admitted:
            self.peak_admitted = n

    def expire(self, now: float) -> List[object]:
        """Pop + return waiting virtual lanes whose deadline passed
        (their blobs are released; the server rejects the futures and
        counts them as in-flight kills — a virtual lane IS admitted).
        Virtual lanes whose future already resolved elsewhere (a
        gateway withdraw after a failed journal write, a crash-restore
        replay) are reaped silently — installing one would burn a
        physical lane on work its caller already disowned."""
        out = []
        for rid in [rid for rid, v in self.waiting.items()
                    if v.req.future.done
                    or (v.req.deadline is not None
                        and now >= v.req.deadline)]:
            v = self.waiting.pop(rid)
            if v.key is not None:
                self.store.release(v.key)
            if not v.req.future.done:
                out.append(v.req)
        return out

    # -- progress tracking -------------------------------------------------
    def note_progress(self, trap: np.ndarray, retired: np.ndarray,
                      total: int):
        """Called after each launch slice with the round's host mirrors:
        lanes whose retired count advanced are 'recently used' for the
        LRU key; the trap mirror backs the mid-drain exclusion."""
        retired = np.asarray(retired, np.int64)
        moved = np.nonzero(retired != self._last_retired)[0]
        for lane in moved:
            if int(lane) in self._resident_since:
                self._last_progress[int(lane)] = int(total)
        self._last_retired[:] = retired
        self._last_trap[:] = np.asarray(trap, np.int64)

    def on_install(self, lane: int, rnd: int, total: int):
        self._resident_since[lane] = rnd
        self._last_progress[lane] = total
        self._last_trap[lane] = 0   # install clears the trap plane

    def on_free(self, lane: int):
        self._resident_since.pop(lane, None)
        self._last_progress.pop(lane, None)

    def reset_residency(self, lanes, rnd: int, total: int):
        """Re-anchor the per-lane tracking after a restore/adoption:
        exactly the restored binding set is resident, everything else
        is free, and LRU history restarts at the restored cursor."""
        self._resident_since.clear()
        self._last_progress.clear()
        self._last_retired[:] = 0
        self._last_trap[:] = 0
        for lane in lanes:
            self.on_install(int(lane), rnd, total)

    # -- boundary rebalance ------------------------------------------------
    def _fits(self, tenant: str, res_by_tenant: Dict[str, int]) -> bool:
        cap = self.tenant_caps.get(tenant)
        return cap is None or res_by_tenant.get(tenant, 0) < cap

    def _next_waiter(self, res_by_tenant, skip) -> Optional[VirtualLane]:
        for rid, v in self.waiting.items():
            if rid in skip:
                continue
            if self._fits(v.req.tenant, res_by_tenant):
                return v
        return None

    def rebalance(self, state, bindings: Dict[int, object],
                  free: List[int], now: float, total: int, rnd: int):
        """The launch-boundary scheduling pass (under the server lock).

        PLAN first (pure host data: which waiters install into which
        free lanes, which victims rotate out for which waiters — all
        respecting the global resident cap and per-tenant resident
        caps), then EXECUTE: fire the swap_out seams, batch-serialize
        every victim with one device gather per plane, park them in
        one column set, and install the planned waiters (fresh ones
        grouped per function through the recycler's batched install,
        swapped ones through the jitted per-lane column restore).
        Mutates `bindings` and the `free` heap in place; returns the
        updated state."""
        import heapq

        if not self.waiting:
            self.note_admitted_peak(bindings)
            return state
        res: Dict[str, int] = {}
        for req in bindings.values():
            res[req.tenant] = res.get(req.tenant, 0) + 1
        skip = set()          # waiter ids already planned this round
        plan: List[tuple] = []   # (lane, VirtualLane) to install
        # -- phase 1 plan: free lanes, resident budget permitting
        planned_resident = len(bindings)
        while free and planned_resident < self.resident_cap:
            v = self._next_waiter(res, skip)
            if v is None:
                break
            lane = heapq.heappop(free)
            plan.append((lane, v))
            skip.add(v.req.id)
            res[v.req.tenant] = res.get(v.req.tenant, 0) + 1
            planned_resident += 1
        # -- phase 2 plan: rotate victims out for remaining waiters
        budget = self.k.max_swaps_per_round
        budget = int(budget) if budget is not None else self.lanes
        pairs: List[tuple] = []   # (victim_lane, victim_req, waiter)
        planned_victims = set()   # rotating out this round
        no_fit = set()            # eviction would seat no waiter
        while budget > 0:
            cands = [
                EvictionCandidate(
                    lane=lane,
                    last_progress_step=self._last_progress.get(lane, 0),
                    resident_since_round=self._resident_since.get(
                        lane, rnd),
                    deadline=req.deadline,
                    trap=int(self._last_trap[lane]))
                for lane, req in bindings.items()
                if lane not in planned_victims and lane not in no_fit]
            # the sole-runnable guard credits lanes outside `cands`
            # that still keep the device busy: installs planned this
            # boundary, rotation pairs (each removes one runnable but
            # seats another), and no_fit lanes (excluded from the pick
            # yet still resident and runnable)
            victims = pick_victims(
                cands, 1, now, rnd,
                min_resident_rounds=int(self.k.min_resident_rounds),
                incoming_runnable=len(plan) + len(pairs)
                + len(no_fit))
            if not victims:
                break
            victim = victims[0]
            vreq = bindings[victim]
            # the eviction must buy an installable waiter: account the
            # victim's slot as freed when checking tenant caps (an
            # own-tenant rotation always fits).  When THIS victim's
            # eviction seats nobody (a capped tenant's waiter needs its
            # OWN lane back, not another tenant's), move on to the next
            # victim in policy order instead of abandoning rotation —
            # otherwise a capped tenant's virtual lane starves behind a
            # colder lane it can never use.
            after = dict(res)
            after[vreq.tenant] = max(after.get(vreq.tenant, 1) - 1, 0)
            v = self._next_waiter(after, skip)
            if v is None:
                no_fit.add(victim)
                continue
            pairs.append((victim, vreq, v))
            planned_victims.add(victim)
            skip.add(v.req.id)
            res = after
            res[v.req.tenant] = res.get(v.req.tenant, 0) + 1
            budget -= 1
        # -- execute: swap victims out (seams -> batched serialize ->
        # one park), collecting the lanes that actually freed
        state, freed_pairs = self._swap_out_batch(state, pairs,
                                                  bindings, rnd)
        installs = plan + freed_pairs
        # -- execute: install planned waiters.  Fresh lanes group per
        # function (one recycler column-set pass each, exactly like
        # plain admission); swapped lanes restore per-lane.
        state = self._install_batch(state, installs, bindings, free,
                                    total, rnd)
        self.note_admitted_peak(bindings)
        return state

    # -- swap-out ----------------------------------------------------------
    def _swap_out_batch(self, state, pairs, bindings, rnd: int):
        """Swap a planned victim set out: per-victim `swap_out` seam,
        ONE batched device gather per plane for the survivors, per-
        victim store put (its own `swap_store_write` seam), one park.
        A fault at any victim's seam/put leaves THAT lane resident and
        its paired waiter waiting (retried next boundary); the rest of
        the batch proceeds.  Returns (state, [(freed_lane, waiter)])."""
        if not pairs:
            return state, []
        t0 = self.obs.now()
        live = []
        for victim, vreq, waiter in pairs:
            try:
                if self.faults is not None:
                    self.faults.fire("swap_out", lane=int(victim),
                                     id=vreq.id)
                live.append((victim, vreq, waiter))
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                self.counters["swap_out_faults"] += 1
                self._record("swap", e)
        if not live:
            return state, []
        cur = getattr(self.engine, "_stdout_cursor", None)
        lanes_idx = [victim for victim, _, _ in live]
        spos = [int(cur[0][lane]) if cur is not None else 0
                for lane in lanes_idx]
        try:
            payloads = serialize_lanes(state, lanes_idx, self.lanes,
                                       stdout_pos=spos)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            # a whole-batch serialization failure leaves every victim
            # resident — the boundary retries
            self.counters["swap_out_faults"] += len(live)
            self._record("swap", e)
            return state, []
        parked = []
        freed_pairs = []
        for (victim, vreq, waiter), payload, sp in zip(live, payloads,
                                                       spos):
            try:
                key = self.store.put(payload)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                self.counters["swap_out_faults"] += 1
                self._record("swap", e)
                continue
            v = VirtualLane(vreq, key=key, stdout_pos=sp,
                            admitted_round=rnd)
            v.swaps = 1
            self.waiting[vreq.id] = v      # FIFO tail: behind waiters
            bindings.pop(victim, None)
            self.on_free(victim)
            parked.append(victim)
            freed_pairs.append((victim, waiter))
            self.counters["swaps_out"] += 1
            self.counters["swap_bytes_out"] += len(payload)
            self.obs.instant("swap_out", cat="hv", track="hv",
                             lane=int(victim), id=vreq.id,
                             nbytes=len(payload), tenant=vreq.tenant)
        if parked:
            state = self.recycler.park(state, parked)
            self.obs.observe_swap("out", self.obs.now() - t0)
        return state, freed_pairs

    # -- swap-in / install -------------------------------------------------
    def _install_batch(self, state, installs, bindings, free,
                       total: int, rnd: int):
        """Install planned (lane, VirtualLane) pairs: fresh lanes batch
        per function through the recycler template seam; swapped lanes
        batch through one jitted column-set pass (_swap_in_batch).  A
        failed install pushes its lane back onto the free heap."""
        fresh: Dict[int, List[tuple]] = {}
        swapped: List[tuple] = []
        for lane, v in installs:
            if v.fresh:
                fidx = self.recycler.func_idx(v.req.func_name)
                fresh.setdefault(fidx, []).append((lane, v))
            else:
                swapped.append((lane, v))
        for fidx, group in fresh.items():
            lanes_list = [lane for lane, _ in group]
            nargs = max((len(v.req.args) for _, v in group), default=0)
            args_rows = [[(v.req.args[i] if i < len(v.req.args) else 0)
                          for _, v in group] for i in range(nargs)]
            state = self.recycler.install(state, lanes_list, fidx,
                                          args_rows)
            for lane, v in group:
                self._finish_install(lane, v, bindings, total, rnd)
        return self._swap_in_batch(state, swapped, bindings, free,
                                   total, rnd)

    def _swap_in_batch(self, state, pairs, bindings, free,
                       total: int, rnd: int):
        """Restore swapped virtual lanes: per-lane `swap_in` seam +
        fetch + verify, then ONE jitted column-set pass over the whole
        surviving set (the mirror of _swap_out_batch's batched gather
        — a per-lane jit dispatch would pay the overhead once per
        victim per boundary).  A faulted swap-in re-queues its virtual
        lane without losing it (the lane stays free); a corrupt store
        entry rejects that one request machine-readably."""
        import heapq

        if not pairs:
            return state
        t0 = self.obs.now()
        ready = []   # (lane, v, cols, spos, nbytes)
        for lane, v in pairs:
            req = v.req
            try:
                if self.faults is not None:
                    self.faults.fire("swap_in", lane=int(lane),
                                     id=req.id)
                payload = self.store.get(v.key)
                cols, spos = deserialize_lane(payload)
            except (KeyboardInterrupt, SystemExit):
                raise
            except SwapCorrupt as e:
                # the state is unrecoverable: machine-readable failure
                # for THIS request; everyone else keeps serving
                from wasmedge_tpu.serve.queue import ServeRejected

                self.counters["swap_corrupt"] += 1
                self._record("swap", e)
                self.waiting.pop(req.id, None)
                self.store.release(v.key)
                if self.lost_cb is not None and not req.future.done:
                    self.lost_cb(req)
                req.future._reject(ServeRejected(
                    f"request {req.id} lost: swapped lane state "
                    f"corrupt ({e.reason})"))
                heapq.heappush(free, lane)
                continue
            except Exception as e:
                self.counters["swap_in_faults"] += 1
                self._record("swap", e)
                heapq.heappush(free, lane)
                continue
            ready.append((lane, v, cols, spos, len(payload)))
        if not ready:
            return state
        try:
            state = self._install_columns(
                state, [r[0] for r in ready], [r[2] for r in ready])
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            # whole-batch install failure: every lane stays free and
            # every virtual lane keeps waiting — retried next boundary
            self.counters["swap_in_faults"] += len(ready)
            self._record("swap", e)
            for lane, *_ in ready:
                heapq.heappush(free, lane)
            return state
        cur = getattr(self.engine, "_stdout_cursor", None)
        for lane, v, cols, spos, nbytes in ready:
            req = v.req
            if cur is not None:
                # continue the REQUEST's logical output stream on the
                # new physical lane: pos picks up where the request
                # left off, and the written high-water collapses to it
                # (the target lane's history belongs to other requests)
                cur[0][lane] = spos
                cur[1][lane] = spos
            self.store.release(v.key)
            self.counters["swaps_in"] += 1
            self.counters["swap_bytes_in"] += nbytes
            self.obs.instant("swap_in", cat="hv", track="hv",
                             lane=int(lane), id=req.id,
                             tenant=req.tenant)
            self._finish_install(lane, v, bindings, total, rnd)
        self.obs.observe_swap("in", self.obs.now() - t0)
        return state

    def _finish_install(self, lane: int, v: VirtualLane, bindings,
                        total: int, rnd: int):
        req = v.req
        self.waiting.pop(req.id, None)
        bindings[lane] = req
        if self.install_cb is not None:
            self.install_cb(lane, req, v.fresh)
        self.on_install(lane, rnd, total)
        n = sum(1 for r in bindings.values() if r.tenant == req.tenant)
        if n > self.peak_resident_by_tenant.get(req.tenant, 0):
            self.peak_resident_by_tenant[req.tenant] = n

    def _install_columns(self, state, lanes_list, cols_list):
        return install_lane_columns(state, self.lanes, lanes_list,
                                    cols_list, self._install_jit)

    # -- checkpoint / restore ----------------------------------------------
    def journal_entries(self) -> List[dict]:
        return [v.journal() for v in self.waiting.values()]

    def snapshot_payload(self) -> List[tuple]:
        """In-memory lineage payload: (req, key, stdout_pos) triples —
        request OBJECTS so an in-process restore resolves the futures
        callers already hold."""
        return [(v.req, v.key, v.stdout_pos)
                for v in self.waiting.values()]

    def blob_arrays(self, record=None) -> Dict[str, np.ndarray]:
        """Swapped blobs as npz-ready uint8 arrays, read from the store
        WITHOUT faulting any lane in — the checkpoint embeds them so a
        restore never depends on store retention.  Corrupt entries are
        recorded and skipped (the restore path re-queues those ids)."""
        out = {}
        for v in self.waiting.values():
            if v.key is None:
                continue
            try:
                payload = self.store.get(v.key)
            except SwapCorrupt as e:
                (record or self._record)("swap", e)
                continue
            out[f"hvblob_{v.key}"] = np.frombuffer(payload, np.uint8)
        return out

    def restore(self, triples, blobs: Dict[str, bytes],
                covered_ids) -> List[object]:
        """Reset the virtual table to a snapshot's view.  `triples` are
        (req, key, stdout_pos); `blobs` maps key -> payload bytes (the
        snapshot-embedded copies); ids in `covered_ids` (the snapshot's
        RESIDENT bindings) are skipped — a request must never be both
        resident and virtual.  Returns requests whose swapped state
        could not be restored (corrupt/missing blob) for the caller to
        re-queue or reject."""
        for v in self.waiting.values():
            if v.key is not None:
                self.store.release(v.key)
        self.waiting.clear()
        lost = []
        for req, key, spos in triples:
            if req.id in covered_ids or req.future.done:
                continue
            if key is not None:
                payload = blobs.get(key)
                try:
                    if payload is None:
                        raise SwapCorrupt(key, "blob missing from "
                                               "snapshot")
                    self.store.adopt(key, bytes(payload))
                except SwapCorrupt as e:
                    self.counters["swap_corrupt"] += 1
                    self._record("swap", e)
                    lost.append(req)
                    continue
            self.waiting[req.id] = VirtualLane(req, key=key,
                                               stdout_pos=spos)
        return lost

    def drop_all(self) -> List[object]:
        """Shutdown/terminal-failure sweep: release every blob and
        return the virtual requests so the server can reject their
        futures."""
        out = []
        for v in self.waiting.values():
            if v.key is not None:
                self.store.release(v.key)
            out.append(v.req)
        self.waiting.clear()
        return out

    # -- introspection -----------------------------------------------------
    def stats(self, bindings) -> dict:
        swapped = sum(1 for v in self.waiting.values()
                      if v.key is not None)
        return {
            "resident": len(bindings),
            "virtual": len(self.waiting),
            "virtual_swapped": swapped,
            "virtual_fresh": len(self.waiting) - swapped,
            "max_virtual_lanes": self.virtual_cap,
            "resident_cap": self.resident_cap,
            "lane_bytes": self.lane_bytes,
            "tenant_resident_caps": dict(self.tenant_caps),
            "peak_admitted": self.peak_admitted,
            "peak_resident_by_tenant":
                dict(self.peak_resident_by_tenant),
            "store_entries": len(self.store),
            "store_bytes": self.store.bytes_held,
            **self.counters,
        }


# ---------------------------------------------------------------------------
# shared column-install pass (hv swap-in + effects/ session unpark)
# ---------------------------------------------------------------------------
def install_lane_columns(state, total_lanes: int, lanes_list, cols_list,
                         jit_cache):
    """One jitted column-set pass restoring every serialized plane at
    the given lanes (the swap-in half of the recycler's install seam —
    same donation discipline and power-of-two index padding, so at most
    log2(lanes)+1 variants compile per engine).  Pads repeat lane 0
    with lane 0's columns: duplicate index writes carry identical
    values, so the pads are idempotent.

    `jit_cache` is a single-slot list holding the compiled setter; the
    owner clears it (sets [None]) when the state geometry changes
    (reshard) so the pass retraces at the new shapes.  Shared with the
    effects/ runtime: a parked session's unpark install is the exact
    code path of an hv swap-in."""
    import jax.numpy as jnp

    from wasmedge_tpu.batch import jit_in_place

    if jit_cache[0] is None:
        def install(state, idx, cols):
            updates = {}
            for name, col in cols.items():
                plane = getattr(state, name)
                if plane.ndim == 1:
                    updates[name] = plane.at[idx].set(col)
                else:
                    updates[name] = plane.at[:, idx].set(col)
            return state._replace(**updates)

        jit_cache[0] = jit_in_place(install, 0)
    n = len(lanes_list)
    w = min(total_lanes, 1 << (n - 1).bit_length())
    idx = np.full(w, lanes_list[0], np.int64)
    idx[:n] = lanes_list
    stacked = {}
    for name in cols_list[0]:
        cols = [np.asarray(c[name]) for c in cols_list]
        cols = cols + [cols[0]] * (w - n)
        # branch on the PLANE's rank, not the column's: serialized
        # columns of 1-D planes arrive as shape (1,) (numpy's
        # ascontiguousarray promotes 0-d scalars), which is
        # indistinguishable from a depth-1 2-D plane's column
        if getattr(state, name).ndim == 1:
            stacked[name] = np.asarray(
                [c.reshape(()) for c in cols])          # (w,)
        else:
            stacked[name] = np.stack(cols, axis=-1)     # (D, w)
    return jit_cache[0](state, jnp.asarray(idx),
                        {k: jnp.asarray(a) for k, a in stacked.items()})
