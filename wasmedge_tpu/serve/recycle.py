"""Lane recycling: re-initialize retired device lanes in place.

The batch engines drain a pre-packed cohort to completion; a serving
loop cannot afford that — a lane that retires while fib(30) grinds on in
its neighbours is dead capacity until batch drain.  GPU control-flow
work (PAPERS: "Control Flow Management in Modern GPUs") identifies
reclaiming dead lanes as the dominant occupancy lever for SIMT
execution; this module is that lever for the SIMT BatchState.

`LaneRecycler` captures, once per exported function, the lane-uniform
column of every state plane from `engine.initial_state()` (the same
construction seam the engines, the scheduler's `_install_pending`, and
the checkpoint layer share) and then `install()`s queued requests into
freed lane columns with device-side column sets — pc/sp/frames/globals/
memory all reset to the function's entry state, the request's argument
cells written into the stack rows, trap cleared to RUNNING.  No kernel
rebuild, no host round trip beyond the column updates: the next launch
simply finds the lanes live again.

Idle lanes park with trap=TRAP_DONE — the step function's `active`
mask already skips them, so an under-occupied serving state costs
nothing beyond the lanes' plane storage.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from wasmedge_tpu.batch.image import TRAP_DONE

MASK32 = 0xFFFFFFFF


class LaneRecycler:
    """Per-engine template cache + in-place lane (re)initialization."""

    def __init__(self, engine):
        self.engine = engine
        self.lanes = engine.lanes
        self._templates: Dict[int, dict] = {}   # func_idx -> plane templates
        self._nres: Dict[int, int] = {}
        self._install_fns: Dict[tuple, object] = {}  # (func, nargs) -> jit
        self._fidx: Dict[str, int] = {}   # validated name -> func index

    def func_idx(self, func_name: str) -> int:
        # memoized like _nres/_templates: harvest calls this once per
        # retired lane and submit once per request, all under the
        # server lock — the export lookup + v128 signature scan
        # (engine.export_func_idx: single-module names on BatchEngine,
        # "module:func" qualified names on the multi-module engine)
        # only needs to happen once per name
        idx = self._fidx.get(func_name)
        if idx is not None:
            return idx
        idx = self.engine.export_func_idx(func_name)
        self._fidx[func_name] = idx
        return idx

    def nresults(self, func_idx: int) -> int:
        n = self._nres.get(func_idx)
        if n is None:
            n = self.engine.func_nresults(func_idx)
            self._nres[func_idx] = n
        return n

    def idle_state(self, func_idx: int):
        """A fresh all-idle serving state (every lane parked TRAP_DONE).
        Geometry comes from the engine; the function only seeds the
        template cache so the first install is warm."""
        import jax.numpy as jnp

        state = self.engine.initial_state(func_idx, [])
        self._capture(func_idx, state)
        return state._replace(
            trap=jnp.full((self.lanes,), TRAP_DONE, jnp.int32))

    def _capture(self, func_idx: int, state=None) -> dict:
        """Lane-uniform template columns for one function's entry state.
        initial_state() with no argument arrays is identical across
        lanes by construction, so column 0 carries every plane."""
        tmpl = self._templates.get(func_idx)
        if tmpl is not None:
            return tmpl
        if state is None:
            state = self.engine.initial_state(func_idx, [])
        tmpl = {}
        for name in state._fields:
            plane = getattr(state, name)
            if plane is None:
                continue
            arr = np.asarray(plane)
            if arr.ndim == 0 or arr.shape[-1] != self.lanes:
                continue  # no lane axis (e.g. the op_hist histogram)
            tmpl[name] = arr[..., 0].copy()
        self._templates[func_idx] = tmpl
        return tmpl

    def _install_fn(self, func_idx: int, nargs: int):
        """One jitted column-set pass per (function, arity): every
        template plane written at the lane index vector (the caller
        pads with repeats of the first freed lane — duplicate indices
        carry identical values, so the pad writes are idempotent).
        jit retraces per index width; the caller pads to a power of
        two, so at most log2(lanes)+1 variants compile per (function,
        arity) while the write volume stays proportional to the lanes
        actually installed instead of the full lane width."""
        fn = self._install_fns.get((func_idx, nargs))
        if fn is not None:
            return fn
        import jax.numpy as jnp

        from wasmedge_tpu.batch import jit_in_place

        tmpl = {name: jnp.asarray(col)
                for name, col in self._capture(func_idx).items()}

        def install(state, idx, lo_rows, hi_rows):
            w = idx.shape[0]
            updates = {}
            for name, col in tmpl.items():
                plane = getattr(state, name)
                if col.ndim == 0:
                    updates[name] = plane.at[idx].set(
                        jnp.broadcast_to(col, (w,)))
                else:
                    updates[name] = plane.at[:, idx].set(
                        jnp.broadcast_to(col[:, None], (col.shape[0], w)))
            state = state._replace(**updates)
            if nargs:
                rows = jnp.arange(nargs)[:, None]
                cols = jnp.broadcast_to(idx[None, :], (nargs, w))
                state = state._replace(
                    stack_lo=state.stack_lo.at[rows, cols].set(lo_rows),
                    stack_hi=state.stack_hi.at[rows, cols].set(hi_rows))
            return state

        # donate the carried state so the column writes happen in place
        # instead of copying every plane (the caller always rebinds
        # `self.state = install(self.state, ...)`)
        fn = jit_in_place(install, 0)
        self._install_fns[(func_idx, nargs)] = fn
        return fn

    def install(self, state, lanes: Sequence[int], func_idx: int,
                args_rows: List[Sequence[int]]):
        """Re-initialize `lanes` in place for `func_idx` with per-lane
        argument cells (`args_rows[i][k]` = arg i of the request going
        into lanes[k]).  Returns the updated state."""
        lanes = np.asarray(lanes, np.int64)
        n = int(lanes.size)
        if n == 0:
            return state
        with self.engine.obs.timed("serve/install", cat="serve", lanes=n):
            return self._install(state, lanes, n, func_idx, args_rows)

    def _install(self, state, lanes, n, func_idx, args_rows):
        import jax.numpy as jnp

        # imagestore observability: when the engine carries a
        # pre-initialized overlay for this function's module, these
        # lanes are snapshot-admitted (the template the column-set
        # writes IS the post-init snapshot) — let it count them
        note = getattr(self.engine, "note_snapshot_install", None)
        if note is not None:
            note(func_idx, n)
        nargs = len(args_rows)
        # pad the index vector to the next power of two so a sparse
        # steady-state install (1-2 recycled lanes on a 4096-lane
        # server) writes O(freed lanes) columns, not the full lane
        # width; pads repeat lanes[0] with lanes[0]'s values
        # (idempotent duplicate writes)
        w = min(self.lanes, 1 << (n - 1).bit_length())
        idx = np.full(w, lanes[0], np.int64)
        idx[:n] = lanes
        lo_rows = np.zeros((nargs, w), np.int32)
        hi_rows = np.zeros((nargs, w), np.int32)
        for i, row in enumerate(args_rows):
            cells = np.full(w, int(row[0]), np.int64)
            cells[:n] = np.asarray(row, np.int64)
            lo_rows[i] = (cells & MASK32).astype(np.uint32).view(np.int32)
            hi_rows[i] = ((cells >> 32) & MASK32).astype(np.uint32) \
                .view(np.int32)
        fn = self._install_fn(func_idx, nargs)
        return fn(state, jnp.asarray(idx), jnp.asarray(lo_rows),
                  jnp.asarray(hi_rows))

    def harvest_cells(self, state, lanes: Sequence[int],
                      func_idx: int) -> np.ndarray:
        """Raw 64-bit result cells [nres, n] for retired lanes (stack
        rows 0..nres-1, same decode as BatchEngine.run)."""
        lanes = np.asarray(lanes, np.int64)
        nres = self.nresults(func_idx)
        if nres == 0 or lanes.size == 0:
            return np.zeros((nres, lanes.size), np.int64)
        lo = np.asarray(state.stack_lo[:nres])[:, lanes] \
            .view(np.uint32).astype(np.uint64)
        hi = np.asarray(state.stack_hi[:nres])[:, lanes] \
            .view(np.uint32).astype(np.uint64)
        return (lo | (hi << np.uint64(32))).view(np.int64)

    def park(self, state, lanes: Sequence[int]):
        """Park lanes idle (TRAP_DONE): harvested or killed lanes stop
        costing dispatch work until the next install."""
        import jax.numpy as jnp

        lanes = np.asarray(lanes, np.int64)
        if lanes.size == 0:
            return state
        with self.engine.obs.timed("serve/park", cat="serve",
                                   lanes=int(lanes.size)):
            return state._replace(
                trap=state.trap.at[jnp.asarray(lanes)].set(
                    jnp.int32(TRAP_DONE)))
