"""Single-program mesh drive: one jitted chunk program over a named
device mesh (shard_map semantics via NamedSharding + jit / GSPMD).

The threaded drive (parallel/mesh.py run_pallas_sharded) is N Python
threads coordinating N per-device engines under the GIL: per-round host
overhead grows with device count and pod scale is out of reach.  This
module recasts the whole fleet step as ONE array program, the
SNIPPETS.md [2] NamedSharding shape ("8-chip v4 to 6000-chip v5p
without changing application code") applied to the lane batch:

  - every BatchState plane becomes one GLOBAL lane-sharded array
    (`lanes` mesh axis on the trailing dim, parallel/mesh.py
    state_shardings — the replication rule for laneless planes is
    shared with the threaded drive's checkpoint slicing);
  - the existing jitted SIMT chunk body runs per-shard UNCHANGED —
    XLA's SPMD partitioner places one program on every device, zero
    collectives in steady state (wasm instances are share-nothing);
  - hostcall/trap/retired mirrors are gathered ONCE per launch
    boundary (np.asarray reassembles the per-device shards) and viewed
    per shard (`shard_mirrors` — the per-device mesh_round spans read
    the trap mirror through it), so the tier-1 WASI drain and the
    harvest logic see exactly the per-device views the threaded drive
    gave them — the drain itself serves the concatenation in global
    lane order, which restores single-device determinism (the threaded
    drive's cross-device flush interleaving was scheduler-dependent).

A lane count that does not divide the device count pads the GLOBAL
array up to the next multiple: pad lanes are born parked (trap ==
TRAP_DONE), so the step function's `active` mask excludes them — they
never retire an instruction, never park at a hostcall stub, and never
duplicate a WASI side effect; the harvest strips them before the merged
BatchResult is returned.

The drive is the default for devices > 1 (parallel/mesh.py run_mesh).
The threaded drive is retained as an explicit degradation-ladder rung:
the MeshSupervisor attempts this drive first and falls back to the
threaded per-device rungs on any shard-drive failure, preserving
quarantine / ejection / checkpoint semantics (parallel/supervisor.py).

Determinism note: tier-0 random_get keys its stream on the GLOBAL lane
index here, exactly like single-device execute_batch — the threaded
drive keys on the device-local index, so a random-drawing guest is
bit-identical between THIS drive and the single-device path, and
lane-placement-independent guests are bit-identical across all three.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


class ShardDriveError(RuntimeError):
    """A single-program shard-drive failure — the MeshSupervisor's cue
    to demote to the threaded per-device rung (the failure stays
    chained as __cause__ for attribution)."""


def padded_lanes(lanes: int, n_devices: int) -> int:
    """Global lane count padded up to a multiple of the device count
    (NamedSharding splits the lane dim evenly across the mesh)."""
    n = max(int(n_devices), 1)
    return ((int(lanes) + n - 1) // n) * n


def shard_slices(padded: int, n_devices: int) -> List[slice]:
    """Contiguous per-device lane ranges of the padded global arrays —
    the per-shard view geometry (`lanes` axis shards are contiguous
    equal blocks, device order = mesh order)."""
    per = int(padded) // max(int(n_devices), 1)
    return [slice(d * per, (d + 1) * per) for d in range(int(n_devices))]


def shard_mirrors(mirror, slices):
    """Per-shard zero-copy views of one launch-boundary host mirror
    (trap / retired / so_off — any lane-trailing plane pulled to the
    host with np.asarray, which reassembles the per-device shards).
    The per-device mesh_round spans read the trap mirror through this,
    and the WASI drain / harvest see the same per-device views as the
    concatenation in global lane order."""
    return [mirror[sl] for sl in slices]


def regrow_state(state, old_lanes: int, idle_state, new_lanes: int):
    """Host-side state re-placement for a LIVE reshard (r21): every
    lane-trailing plane of `state` (the running generation, old_lanes
    wide) keeps its columns at their GLOBAL lane indices and extends
    with the matching columns of `idle_state` (a fresh all-idle state
    at the new geometry — its tail lanes are born parked TRAP_DONE,
    exactly like the pad lanes of an uneven split).  Laneless planes
    pass through from the running state untouched.

    Lanes only ever grow across a reshard (the server pads the lane
    pool up from its CURRENT width, never down — a device shrink keeps
    the width and just re-splits it), so every resident lane's column
    is preserved verbatim: results are bit-identical to the
    unresharded run by construction, not by remapping.

    Returns a host (numpy) pytree — the caller places it on the new
    mesh (parallel/mesh.py shard_batch_state) or hands it straight to
    the unsharded jit for a single-device target."""
    import jax

    if new_lanes < old_lanes:
        raise ValueError(
            f"reshard cannot shrink the lane pool "
            f"({old_lanes} -> {new_lanes}); device shrinks keep the "
            f"width and re-split it")

    def _combine(old_leaf, idle_leaf):
        o = np.asarray(old_leaf)
        if o.ndim and o.shape[-1] == old_lanes:
            if new_lanes == old_lanes:
                return o
            pad = np.asarray(idle_leaf)[..., old_lanes:new_lanes]
            return np.concatenate([o, pad.astype(o.dtype)], axis=-1)
        return o

    return jax.tree_util.tree_map(_combine, state, idle_state)


def _build_shard_chunk(run_chunk, mesh, probe_state):
    """Jit the chunk body as ONE program over the named mesh.

    `run_chunk` is the engine's traced chunk loop (the SAME body the
    single-device path jits — batch/engine.py _build); this wrapper
    only pins the data placement: every lane-dim plane of the
    BatchState pytree sharded on the `lanes` mesh axis in and out, the
    per-launch time base replicated.  XLA's SPMD partitioner then
    compiles one per-shard executable and the host issues ONE dispatch
    per round regardless of device count.  The state is donated as on
    one device (`jit_in_place`).

    jit-purity lint target (tools/lint_jit_purity.py): everything
    nested here runs under trace.
    """
    from wasmedge_tpu.batch import jit_in_place
    from wasmedge_tpu.parallel.mesh import state_shardings

    shardings = state_shardings(mesh, probe_state)
    return jit_in_place(run_chunk, 0, in_shardings=(shardings, None),
                        out_shardings=(None, shardings))


class ShardDrive:
    """One module's batch driven as a single jitted program over a
    lane-sharded named device mesh.

    `run()` returns the same merged BatchResult the threaded drive
    does, bit-identical for lane-placement-independent guests (and
    bit-identical to single-device execute_batch unconditionally — the
    global lane index IS the single-device lane index).  `faults` arms
    the deterministic seams `shard_launch` / `shard_serve` (the
    engine's launch/serve seams re-labelled, so supervisor tests can
    target the shard rung without touching the threaded one).
    """

    def __init__(self, inst, store=None, conf=None, devices=None,
                 faults=None):
        from wasmedge_tpu.common.configure import Configure
        from wasmedge_tpu.parallel.mesh import (
            lane_mesh, normalize_devices)

        self.inst = inst
        self.store = store
        self.conf = conf if conf is not None else Configure()
        self.devices = normalize_devices(devices)
        if not self.devices:
            raise ValueError("shard drive needs at least one device")
        self.mesh = lane_mesh(devices=self.devices)
        self.faults = faults
        self.engine = None       # built per run (lane width is per-run)
        self._slices = []
        self._pad = 0
        self._lanes = 0

    # -- fault seam: engine launch/serve re-labelled shard_* -------------
    def _fault_hook(self, point, **ctx):
        if point in ("launch", "serve"):
            point = "shard_" + point
        self.faults.fire(point, drive="shard", **ctx)

    # -- per-round per-device spans (obs mesh_round satellite) -----------
    def _on_round(self, done_steps: int, trap_host, t_launch):
        from wasmedge_tpu.batch.image import TRAP_HOSTCALL

        obs = self.engine.obs
        if not obs.enabled:
            return
        for di, (sl, t) in enumerate(
                zip(self._slices, shard_mirrors(trap_host,
                                                self._slices))):
            pad = max(sl.stop - self._lanes, 0) if self._pad else 0
            obs.span("mesh_round", t_launch, cat="mesh",
                     track=f"mesh/dev{di}", device=str(self.devices[di]),
                     steps=int(done_steps), lanes=int(t.size),
                     live_lanes=int((t == 0).sum()),
                     parked_lanes=int((t == TRAP_HOSTCALL).sum()),
                     pad_lanes=int(min(pad, t.size)))

    def _build_engine(self, padded: int):
        from wasmedge_tpu.batch.engine import BatchEngine

        eng = BatchEngine(self.inst, store=self.store, conf=self.conf,
                          lanes=padded, mesh=self.mesh)
        # launch/serve spans of the single driving thread land on one
        # dedicated track; the per-device mesh_round spans above keep
        # per-chip attribution
        eng.obs_track = "mesh/shard"
        return eng

    def run(self, func_name: str, args_lanes, max_steps: int = 10_000_000,
            lanes: Optional[int] = None):
        from wasmedge_tpu.batch.engine import (
            BatchResult, new_hostcall_stats)
        from wasmedge_tpu.batch.hostcall import stdout_cursor_reset
        from wasmedge_tpu.batch.image import TRAP_DONE
        from wasmedge_tpu.parallel.mesh import (
            shard_batch_state, size_lane_args)

        args, lanes = size_lane_args(args_lanes, lanes)
        n = len(self.devices)
        padded = padded_lanes(lanes, n)
        self._lanes = lanes
        self._pad = padded - lanes
        self._slices = shard_slices(padded, n)
        if self._pad:
            args = [np.concatenate([a, np.zeros(self._pad, np.int64)])
                    for a in args]
        eng = self.engine
        if eng is None or eng.lanes != padded:
            eng = self.engine = self._build_engine(padded)
        func_idx = eng.export_func_idx(func_name)
        eng.hostcall_stats = new_hostcall_stats()
        stdout_cursor_reset(eng)   # fresh run = fresh output stream
        # lane compaction (batch/compact.py): per-shard permutations
        # only (the compactor derives the shard blocks from the mesh),
        # fresh mapping per run
        from wasmedge_tpu.batch.compact import arm

        arm(eng)
        state = eng.initial_state(func_idx, args)
        if self._pad:
            import jax.numpy as jnp

            # pad lanes are born parked: the step function's `active`
            # mask excludes them — zero retirements, zero WASI effects
            state = state._replace(
                trap=state.trap.at[lanes:].set(jnp.int32(TRAP_DONE)))
        state = shard_batch_state(state, self.mesh)
        if self.faults is not None:
            eng._fault_hook = self._fault_hook
        eng._round_hook = self._on_round
        try:
            state, total = eng.run_from_state(state, 0, max_steps)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:
            raise ShardDriveError(
                f"single-program shard drive failed over {n} device(s): "
                f"{e!r}") from e
        finally:
            eng._fault_hook = None
            eng._round_hook = None
        # harvest: same decode as BatchEngine.run, pads stripped.  A
        # compacted run's pads may have migrated within their shard, so
        # the restore order (physical position of each original lane)
        # replaces the plain prefix slice — sel[:lanes] covers exactly
        # the original lanes because pad src ids sort after them.
        nres = eng.func_nresults(func_idx)
        comp = getattr(eng, "compactor", None)
        order = None if comp is None else comp.restore_order()
        sel = slice(None, lanes) if order is None else order[:lanes]
        stack_lo = np.asarray(state.stack_lo)
        stack_hi = np.asarray(state.stack_hi)
        results = []
        for r in range(nres):
            lo = stack_lo[r, sel].view(np.uint32).astype(np.uint64)
            hi = stack_hi[r, sel].view(np.uint32).astype(np.uint64)
            results.append((lo | (hi << np.uint64(32))).view(np.int64))
        return BatchResult(
            results=results,
            trap=np.asarray(state.trap)[sel].copy(),
            retired=np.asarray(state.retired)[sel].copy(),
            steps=total)


def run_shard_drive(inst, store, conf, func_name, args_lanes,
                    devices=None, max_steps: int = 10_000_000,
                    lanes: Optional[int] = None, faults=None):
    """Functional front door: one single-program shard-drive run.
    Raises ShardDriveError on any drive failure (callers wanting the
    threaded fallback ladder go through the MeshSupervisor —
    parallel/mesh.py run_mesh with supervised=True; failure accounting
    lives there too, on the supervisor's FailureRecord seam)."""
    return ShardDrive(inst, store=store, conf=conf, devices=devices,
                      faults=faults).run(
        func_name, args_lanes, max_steps=max_steps, lanes=lanes)
