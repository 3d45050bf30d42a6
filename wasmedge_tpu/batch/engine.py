"""BatchEngine: the SIMT lockstep interpreter (pure JAX/XLA version).

One `step()` advances every lane by one instruction: fetch each lane's
(class, sub, operands) from the device image tables, run every class
handler vectorized under lane masks, and merge the candidate state updates
with `where`-selects. Divergent control flow needs no special casing — a
lane's pc simply differs; traps park a lane (trap != 0) without unwinding,
the host harvests results when all lanes halt.

This is the moral replacement of the reference's dispatch loop
(/root/reference/lib/executor/engine/engine.cpp:68-1641): the `switch`
becomes masked class handlers, `StackManager` becomes [depth, lanes] int32
planes, MemoryInstance becomes a [words, lanes] plane with software bounds
checks, Statistics/StopToken become per-lane retired/fuel counters
(SURVEY.md §2.10, §5.1-5.3).

State layout is depth-major ([depth, lanes]) so converged lanes hit
dynamic-slice-friendly rows and the lane dim vectorizes on the VPU; the
pallas kernel (batch/pallas_engine.py) consumes the same layout.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional

import numpy as np

from wasmedge_tpu.common.configure import BatchConfigure
from wasmedge_tpu.common.errors import ErrCode
from wasmedge_tpu.batch.image import (
    ALU1_SUB,
    ALU2_F32_BASE,
    ALU2_I32_BASE,
    ALU2_I64_BASE,
    CLS_ALU1,
    CLS_ALU2,
    CLS_BR,
    CLS_BR_TABLE,
    CLS_BRNZ,
    CLS_BRZ,
    CLS_CALL,
    CLS_CALL_INDIRECT,
    CLS_CONST,
    CLS_DROP,
    CLS_GLOBAL_GET,
    ALU2_F64_BASE,
    CLS_GLOBAL_SET,
    CLS_HOSTCALL,
    CLS_LOAD,
    CLS_LOCAL_GET,
    CLS_LOCAL_SET,
    CLS_LOCAL_TEE,
    CLS_MEMCOPY,
    CLS_MEMFILL,
    CLS_MEMGROW,
    CLS_V1,
    CLS_V2,
    CLS_VBITSEL,
    CLS_VCONST,
    CLS_VEXTRACT,
    CLS_VLOAD,
    CLS_VREPLACE,
    CLS_VSHIFT,
    CLS_VSHUFFLE,
    CLS_VSPLAT,
    CLS_VSTORE,
    CLS_VTEST,
    CLS_MEMSIZE,
    CLS_RETURN,
    CLS_SELECT,
    CLS_STORE,
    CLS_TRAP,
    CLS_TABLE_GET,
    CLS_TABLE_SET,
    CLS_TABLE_SIZE,
    CLS_TABLE_GROW,
    CLS_TABLE_FILL,
    CLS_TABLE_COPY,
    CLS_TABLE_INIT,
    CLS_ELEM_DROP,
    CLS_MEMINIT,
    CLS_DATA_DROP,
    CLS_RETCALL,
    CLS_RETCALL_INDIRECT,
    CLS_REFFUNC,
    NUM_CLASSES,
    TRAP_DONE,
    _F64_BIN,
    TRAP_HOSTCALL,
    DeviceImage,
    _F32_BIN,
    _I32_BIN,
)

_PAGE_WORDS = 65536 // 4


class BatchState(NamedTuple):
    pc: object
    sp: object
    fp: object
    opbase: object
    call_depth: object
    trap: object
    retired: object
    fuel: object
    mem_pages: object
    stack_lo: object
    stack_hi: object
    fr_ret_pc: object
    fr_fp: object
    fr_opbase: object
    glob_lo: object
    glob_hi: object
    mem: object
    # v128 extension planes (bits 64..127 of each cell) — present only
    # for modules whose image uses SIMD (img.has_simd); None otherwise
    stack_e2: object = None
    stack_e3: object = None
    # r05 optional planes (same None-when-unused discipline):
    tab: object = None     # [table_cap, lanes] per-lane mutable table
    tsize: object = None   # [lanes] per-lane table size (table.grow)
    edrop: object = None   # [n_elem_segs, lanes] dropped flags
    ddrop: object = None   # [n_data_segs, lanes] dropped flags
    # r06 tier-0 hostcall planes (three-tier pipeline, batch/hostcall.py).
    # The read-only per-launch time base is NOT a state field: it rides
    # the jitted chunk as a separate non-donated argument (an identity-
    # passthrough donated leaf miscompiles under the persistent
    # compilation cache on the CPU backend).
    t0_time: object = None  # reserved (always None; see note above)
    t0_ctr: object = None   # [4, lanes] int32: clock seq / rng seq /
    #                         fd_write count / yield+exit count
    so_buf: object = None   # [SW, lanes] int32 stdout record buffer
    so_off: object = None   # [lanes] int32 next free word in so_buf
    # r08 observability plane (Configure.obs.opcode_histogram): per-pc
    # retired count, scatter-incremented once per step across lanes and
    # folded into per-opcode counts (img.op_id -> Statistics cost_table
    # domain) on sync.  None unless the knob is on (no per-step cost).
    # Under superinstruction fusion every CONSTITUENT op of a fused run
    # increments its own pc (histogram == retired, batch/fuse.py).
    op_hist: object = None
    # r17 fusion counters [3] int32: fused dispatches / instructions
    # retired through fused cells / total retired.  Laneless like
    # op_hist; allocated only when obs is enabled AND the image
    # compiled fused cells (obs_state_planes), folded into the flight
    # recorder on sync.
    fu_ctr: object = None
    # r20 tier-up counters [3] int32: compiled function-call dispatches
    # / instructions retired through compiled bodies / total retired
    # (liveness row: never an identity passthrough in the donated carry
    # when a promoted-plane state resumes on a tierup-off build).
    tu_ctr: object = None


@dataclasses.dataclass
class BatchResult:
    results: List[np.ndarray]  # one [lanes] int64 raw-cell array per result
    # trap[k]: TRAP_DONE (-1) = finished, >0 = ErrCode trap, 0 = lane was
    # STILL RUNNING when max_steps ran out — its results slot is garbage;
    # check `completed` before consuming results.
    trap: np.ndarray
    retired: np.ndarray  # [lanes] instructions retired
    steps: int  # lockstep iterations executed

    @property
    def completed(self) -> np.ndarray:
        """Mask of lanes that finished normally (results valid)."""
        return self.trap == TRAP_DONE


def r05_plane_names(img: DeviceImage) -> tuple:
    """Names of the r05 planes this image requires (no allocation —
    checkpoint's missing-plane guard needs only the keys)."""
    out = []
    if getattr(img, "has_table_mut", False):
        out += ["tab", "tsize"]
    if bool(np.isin(img.cls, (CLS_TABLE_INIT, CLS_ELEM_DROP)).any()):
        out.append("edrop")
    if bool(np.isin(img.cls, (CLS_MEMINIT, CLS_DATA_DROP)).any()):
        out.append("ddrop")
    return tuple(out)


def r05_state_planes(img: DeviceImage, lanes: int) -> dict:
    """Initial tab/tsize/edrop/ddrop planes for the r05 table/segment
    families — shared by every BatchState constructor (engine, uniform
    handoff, multitenant, scheduler).  Returns {} (BatchState None
    defaults) when the image uses none of them."""
    import jax.numpy as jnp

    out = {}
    if getattr(img, "has_table_mut", False):
        T = max(int(img.table_cap or img.table0.shape[0]), 1)
        tb = np.zeros((T, lanes), np.int32)
        n0 = min(img.table0.shape[0], T)
        tb[:n0] = img.table0[:n0, None]
        out["tab"] = jnp.asarray(tb)
        out["tsize"] = jnp.full((lanes,), img.table_size_init, jnp.int32)
    cls = img.cls
    if bool(np.isin(cls, (CLS_TABLE_INIT, CLS_ELEM_DROP)).any()):
        out["edrop"] = jnp.zeros((img.elem_len.shape[0], lanes), jnp.int32)
    if bool(np.isin(cls, (CLS_MEMINIT, CLS_DATA_DROP)).any()):
        out["ddrop"] = jnp.zeros((img.data_len.shape[0], lanes), jnp.int32)
    return out


def obs_state_planes(conf, img: DeviceImage, mesh=None) -> dict:
    """Initial device-side observability planes: the per-pc opcode
    histogram (Configure.obs.opcode_histogram) and the fusion
    dispatch/retired counters (allocated whenever obs is enabled and
    the image compiled fused cells).  {} when obs is off — the
    BatchState defaults (None) then keep the step function free of the
    per-step scatters entirely.  Mesh runs skip both (no lane axis to
    shard)."""
    obs_conf = getattr(conf, "obs", None)
    if mesh is not None or obs_conf is None or not obs_conf.enabled:
        return {}
    import jax.numpy as jnp

    out = {}
    if obs_conf.opcode_histogram:
        out["op_hist"] = jnp.zeros((img.cls.shape[0],), jnp.int32)
    from wasmedge_tpu.batch.fuse import fusion_active

    if fusion_active(img, conf.batch):
        out["fu_ctr"] = jnp.zeros((3,), jnp.int32)
    from wasmedge_tpu.batch.tierup import tierup_active

    if tierup_active(img, conf.batch):
        out["tu_ctr"] = jnp.zeros((3,), jnp.int32)
    return out


# ---------------------------------------------------------------------------
# tier-0 hostcalls: pure WASI calls serviced inside the kernel
# ---------------------------------------------------------------------------
T0_CTR_ROWS = 4  # clock seq / rng seq / fd_write count / yield+exit count


def new_hostcall_stats() -> dict:
    """Per-run hostcall pipeline counters (reset by BatchEngine.run):
    tier0_* are in-kernel retirements (zero device<->host round trips),
    tier1_calls is lanes drained through the outcall channel, and
    serve_rounds counts park->drain->re-arm cycles (each one is at
    least one device<->host round trip); out_bytes is what the calls
    the Pallas block serve drained handed to an fd."""
    return {"tier0_clock": 0, "tier0_random": 0, "tier0_fd_write": 0,
            "tier0_sys": 0, "tier0_calls": 0,
            "tier1_calls": 0, "tier1_vectorized": 0, "serve_rounds": 0,
            "stdout_flushes": 0, "stdout_bytes": 0, "out_bytes": 0}


def t0_effective_kinds(img: DeviceImage, cfg) -> Optional[np.ndarray]:
    """Per-pc tier-0 kinds this image+config will service in-kernel, or
    None when tier 0 is entirely off (no recognized stubs, knob off, or
    a concatenated multi-tenant image that carries no t0kind plane)."""
    from wasmedge_tpu.batch.image import T0_FD_WRITE, T0_NEEDS_MEMORY

    kinds = getattr(img, "t0kind", None)
    if kinds is None or not getattr(cfg, "tier0_hostcalls", True):
        return None
    kinds = np.asarray(kinds, np.int32).copy()
    if not getattr(img, "t0_fdwrite_safe", False):
        kinds[kinds == T0_FD_WRITE] = 0
    if not img.has_memory:
        # these kinds all write through guest memory
        kinds[np.isin(kinds, T0_NEEDS_MEMORY)] = 0
    if not (kinds != 0).any():
        return None
    return kinds


# Shared tier-0 kernel logic lives in batch/tier0.py (one source for the
# SIMT and uniform engines' bit-identical streams); re-exported here for
# compatibility with existing importers.
from wasmedge_tpu.batch.tier0 import (  # noqa: F401
    t0_clock_value,
    t0_masked_store,
    t0_prng32,
    t0_random_fill,
    t0_rng_seq_hash,
    t0_shifted_src_word,
    t0_statics,
    t0_word_mix,
)


def check_batch_entry(inst, func_name: str) -> int:
    """Resolve an exported batch entry on `inst` with the ONE entry
    guard every batch front door shares (BatchEngine.run/export_func_idx
    and the multi-module engine's qualified-name lookup): the export
    must be a function and its signature must not carry v128 —
    install()/harvest move only the 64-bit lo/hi cell halves, so a
    v128 entry would silently compute garbage instead of failing
    loudly.  Returns the instance-local function index."""
    ex = inst.exports.get(func_name)
    if ex is None or ex[0] != 0:
        raise KeyError(f"no exported function {func_name}")
    from wasmedge_tpu.common.types import ValType

    ft = inst.funcs[ex[1]].functype
    if ValType.V128 in tuple(ft.params) + tuple(ft.results):
        raise ValueError(
            "batch entry functions cannot take or return v128 "
            f"({func_name})")
    return ex[1]


def pack_lane_args(args_lanes, lanes: int, depth: int):
    """Entry arguments -> the (stack_lo, stack_hi) int32 planes: one
    int64 cell per (arg, lane), scalars broadcast, shapes validated.
    Shared by every lane-uniform state constructor (BatchEngine and the
    multi-module engine, batch/multitenant.py)."""
    stack_lo = np.zeros((depth, lanes), np.int32)
    stack_hi = np.zeros((depth, lanes), np.int32)
    for i, arg in enumerate(args_lanes):
        arr = np.asarray(arg, dtype=np.int64)
        if arr.ndim == 0:
            arr = np.full(lanes, arr, np.int64)
        if arr.shape != (lanes,):
            raise ValueError(
                f"arg {i}: expected shape ({lanes},) (one value per "
                f"lane) or a scalar, got {arr.shape}")
        stack_lo[i] = (arr & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
        stack_hi[i] = ((arr >> 32) & 0xFFFFFFFF).astype(np.uint32) \
            .view(np.int32)
    return stack_lo, stack_hi


def t0_time_planes() -> np.ndarray:
    """Per-relaunch time base: (realtime, monotonic) ns as int32 (lo, hi).

    In-kernel clock_time_get returns base + per-lane call seq, so values
    are strictly increasing per lane even within one launch window."""
    import time

    out = np.zeros((2, 2), np.int32)
    for r, ns in enumerate((time.time_ns(), time.monotonic_ns())):
        out[r, 0] = np.int32(np.uint32(ns & 0xFFFFFFFF))
        out[r, 1] = np.int32(np.uint32((ns >> 32) & 0xFFFFFFFF))
    return out


def t0_state_planes(img: DeviceImage, cfg, lanes: int,
                    kinds: Optional[np.ndarray]) -> dict:
    """Initial tier-0 planes for a BatchState; {} when tier 0 is off.
    `kinds` is the owning engine's gated kind plane (engine._t0kinds).
    Shared by every BatchState constructor (engine, uniform/pallas
    handoffs, scheduler residue)."""
    import jax.numpy as jnp

    from wasmedge_tpu.batch.image import T0_FD_WRITE

    if kinds is None:
        return {}
    # NOTE t0_time is deliberately NOT part of the state: it is a
    # read-only per-launch input threaded as a separate (non-donated)
    # argument into the jitted chunk — an identity-passthrough donated
    # leaf miscompiles under the persistent compilation cache on jax's
    # CPU backend (deserialized executables lose the input/output alias)
    out = {
        "t0_ctr": jnp.zeros((T0_CTR_ROWS, lanes), jnp.int32),
    }
    if (kinds == T0_FD_WRITE).any():
        sw = max(int(getattr(cfg, "stdout_buffer_words", 2048)), 16)
        out["so_buf"] = jnp.zeros((sw, lanes), jnp.int32)
        out["so_off"] = jnp.zeros((lanes,), jnp.int32)
    return out


def _make_step(img: DeviceImage, cfg: BatchConfigure, lanes: int,
               t0kinds: Optional[np.ndarray] = None):
    """Build the jittable single-step function closed over image constants."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from wasmedge_tpu.batch import laneops as lo_ops

    I32 = jnp.int32
    D = cfg.value_stack_depth
    CD = cfg.call_stack_depth
    lane_iota = jnp.arange(lanes, dtype=I32)

    cls_t = jnp.asarray(img.cls)
    sub_t = jnp.asarray(img.sub)
    a_t = jnp.asarray(img.a)
    b_t = jnp.asarray(img.b)
    c_t = jnp.asarray(img.c)
    ilo_t = jnp.asarray(img.imm_lo)
    ihi_t = jnp.asarray(img.imm_hi)
    brt_t = jnp.asarray(img.br_table)  # [n, 3]
    f_entry = jnp.asarray(img.f_entry)
    f_nparams = jnp.asarray(img.f_nparams)
    f_nlocals = jnp.asarray(img.f_nlocals)
    f_frame_top = jnp.asarray(img.f_frame_top)
    f_type = jnp.asarray(img.f_type)
    table0 = jnp.asarray(img.table0)
    fuel_enabled = cfg.fuel_per_launch is not None
    # per-opcode gas weights: gather the Statistics cost table through
    # the image's original-opcode plane (flat 1/instr when no table —
    # the reference's CostTab default, statistics.h:85-98)
    weighted_gas = (
        fuel_enabled and cfg.cost_table is not None
        and getattr(img, "op_id", None) is not None
        and any(c != 1 for c in cfg.cost_table))
    if weighted_gas:
        _ct = np.clip(np.asarray(cfg.cost_table, np.int64),
                      0, 1 << 30).astype(np.int32)
        _cost_np = _ct[np.clip(img.op_id, 0, len(_ct) - 1)]
        cost_t = jnp.asarray(_cost_np)
    else:
        _cost_np = None
    HAS_SIMD = bool(getattr(img, "has_simd", False))
    if HAS_SIMD:
        from wasmedge_tpu.batch import simdops as sops

        v128_t = jnp.asarray(img.v128)  # [n, 4]
        used_of = lambda kls: {int(sv) for sv, cv in zip(img.sub, img.cls)
                               if cv == kls}
        used_v2 = used_of(CLS_V2)
        used_v1 = used_of(CLS_V1)
        used_vtest = used_of(CLS_VTEST)
        used_vshift = used_of(CLS_VSHIFT)
        used_vsplat = used_of(CLS_VSPLAT)
        used_vextract = used_of(CLS_VEXTRACT)
        used_vreplace = used_of(CLS_VREPLACE)
        uses_vshuffle = bool((img.cls == CLS_VSHUFFLE).any())
        uses_vmem = bool(((img.cls == CLS_VLOAD)
                          | (img.cls == CLS_VSTORE)).any())

    # ALU sub ids
    S_I32 = {n: ALU2_I32_BASE + i for i, n in enumerate(_I32_BIN)}
    S_I64 = {n: ALU2_I64_BASE + i for i, n in enumerate(_I32_BIN)}
    S_F32 = {n: ALU2_F32_BASE + i for i, n in enumerate(_F32_BIN)}
    A1 = ALU1_SUB

    def gat(plane, idx):
        """plane [D?, lanes] gathered at per-lane row idx -> [lanes]."""
        idx = jnp.clip(idx, 0, plane.shape[0] - 1)
        return jnp.take_along_axis(plane, idx[None, :], axis=0)[0]

    def scat(plane, idx, vals, mask):
        idx = jnp.clip(idx, 0, plane.shape[0] - 1)
        cur = jnp.take_along_axis(plane, idx[None, :], axis=0)[0]
        new = jnp.where(mask, vals, cur)
        return plane.at[idx, lane_iota].set(new)

    def sel_chain(sub, pairs, default):
        out = default
        for sid, val in pairs:
            out = jnp.where(sub == sid, val, out)
        return out

    b2i = lo_ops.b2i
    u_lt = lo_ops.u_lt
    # r05 families: static presence flags gate what gets traced
    HAS_T_ANY = bool(np.isin(img.cls, (
        CLS_TABLE_GET, CLS_TABLE_SET, CLS_TABLE_SIZE, CLS_TABLE_GROW,
        CLS_TABLE_FILL, CLS_TABLE_COPY, CLS_TABLE_INIT)).any())
    HAS_T_MUT = bool(img.has_table_mut)
    HAS_ESEG = bool(np.isin(img.cls, (CLS_TABLE_INIT, CLS_ELEM_DROP)).any())
    HAS_DSEG = bool(np.isin(img.cls, (CLS_MEMINIT, CLS_DATA_DROP)).any())
    HAS_TAIL = bool(np.isin(img.cls, (CLS_RETCALL,
                                      CLS_RETCALL_INDIRECT)).any())
    T_CAP = max(int(img.table_cap or img.table0.shape[0]), 1)
    MAX_NPAR = int(img.f_nparams.max()) if HAS_TAIL else 0
    if HAS_ESEG:
        elem_flat_t = jnp.asarray(img.elem_flat)
        elem_off_t = jnp.asarray(img.elem_off)
        elem_len_t = jnp.asarray(img.elem_len)
    if HAS_DSEG:
        data_words_t = jnp.asarray(img.data_words)
        data_off_t = jnp.asarray(img.data_off)
        data_len_t = jnp.asarray(img.data_len)
    used_alu2 = {int(sv) for sv, cv in zip(img.sub, img.cls)
                 if cv == CLS_ALU2}
    used_alu1 = {int(sv) for sv, cv in zip(img.sub, img.cls)
                 if cv == CLS_ALU1}
    _A2F = lo_ops.alu2_fns()
    _A1F = lo_ops.alu1_fns()
    _T1F = lo_ops.alu1_trap_fns()
    _HEAVY_ALU2 = {ALU2_F64_BASE + _F64_BIN.index("div")}
    from wasmedge_tpu.batch.image import ALU1_SUB as _A1S
    _HEAVY_ALU1 = {_A1S["f64.sqrt"]}

    # ---- tier-0 hostcall statics (three-tier pipeline) ----
    from wasmedge_tpu.batch.image import (
        T0_CLOCK_TIME_GET, T0_FD_WRITE, T0_PROC_EXIT, T0_RANDOM_GET,
        T0_SCHED_YIELD)

    t0k = t0kinds
    HAS_T0 = t0k is not None
    if HAS_T0:
        t0k_t = jnp.asarray(np.asarray(t0k, np.int32))
        USE_T0_CLOCK = bool((t0k == T0_CLOCK_TIME_GET).any())
        USE_T0_RANDOM = bool((t0k == T0_RANDOM_GET).any())
        USE_T0_YIELD = bool((t0k == T0_SCHED_YIELD).any())
        USE_T0_EXIT = bool((t0k == T0_PROC_EXIT).any())
        USE_T0_FDW = bool((t0k == T0_FD_WRITE).any())
        _t0s = t0_statics(cfg)
        RMAX_W = _t0s["RMAX_W"]
        WMAX_W = _t0s["WMAX_W"]
        RNG_SEED = jnp.asarray(_t0s["RNG_SEED"])
        _E_INVAL = _t0s["E_INVAL"]
        _E_FAULT = _t0s["E_FAULT"]

        def t0_rmw(plane, idx, m, v, ok):
            """Masked word RMW through this engine's gather/scatter —
            the primitive the shared tier-0 bodies are built on."""
            cur = gat(plane, idx)
            return scat(plane, idx, (cur & ~m) | (v & m), ok & (m != 0))

    # ---- superinstruction fusion statics (batch/fuse.py) ----
    # FUSE_ON is trace-time static: knob off (or nothing realized)
    # compiles the exact seed per-op step.  Memory-run patterns (r19,
    # absint-licensed load/store runs) compile through their own
    # handler; a pattern table with only one kind builds only that
    # handler.
    from wasmedge_tpu.batch.fuse import (
        fusion_active, make_fused_apply, make_memfuse_apply,
        pattern_has_mem)

    FUSE_ON = fusion_active(img, cfg)
    HAS_PURE_PAT = HAS_MEM_PAT = False
    if FUSE_ON:
        flen_t = jnp.asarray(img.fuse_len)
        MAX_F = int(np.asarray(img.fuse_len).max())
        _pats = img.fuse_patterns or ()
        _pat_mem = np.array([pattern_has_mem(p) for p in _pats], bool)
        HAS_PURE_PAT = bool((~_pat_mem).any())
        HAS_MEM_PAT = bool(_pat_mem.any())
        if HAS_PURE_PAT:
            fused_apply = make_fused_apply(img, lanes, HAS_SIMD)
        if HAS_MEM_PAT:
            from wasmedge_tpu.batch.fuse import memfuse_store_slots

            memfuse_apply = make_memfuse_apply(img, lanes, HAS_SIMD)
            N_MEM_SLOTS = memfuse_store_slots(img)
            _fpat_np = np.asarray(img.fuse_pat)
            _memhead = np.zeros(_fpat_np.shape[0], bool)
            _valid = _fpat_np >= 0
            _memhead[_valid] = _pat_mem[_fpat_np[_valid]]
            _memhead &= np.asarray(img.fuse_len) >= 2
            memhead_t = jnp.asarray(_memhead)
            # heads of patterns that STORE (the fused-store channel's
            # any-lane gate; load-only runs never touch the plane)
            _pat_st = np.array(
                [any(cl in (CLS_STORE, CLS_VSTORE) for cl, _ in p)
                 for p in _pats],
                bool)
            _sthead = np.zeros(_fpat_np.shape[0], bool)
            _sthead[_valid] = _pat_st[_fpat_np[_valid]]
            _sthead &= _memhead
            sthead_t = jnp.asarray(_sthead)

    # ---- whole-function tier-up statics (batch/tierup.py) ----
    # TIER_ON is trace-time static like FUSE_ON: knob off (or nothing
    # promoted) compiles the exact seed/fused step by construction.
    from wasmedge_tpu.batch.tierup import make_tierup_apply, tierup_active

    TIER_ON = tierup_active(img, cfg)
    if TIER_ON:
        tier_fn_t = jnp.asarray(img.tier_fn)
        if fuel_enabled:
            tier_fuel_t = jnp.asarray(img.tier_fuel_bound)
        tierup_apply = make_tierup_apply(img, lanes, HAS_SIMD, _cost_np)

    def step(st: BatchState, t0_time=None) -> BatchState:
        """One lockstep instruction (or one fused dispatch cell — a
        whole straight-line run of stack/ALU effects for lanes parked
        at a fused run head).  `t0_time` is the [2, 2] int32
        per-launch time base (read-only; threaded as a separate argument
        so the donated state never carries an identity-passthrough
        leaf — see t0_state_planes)."""
        alive = st.trap == 0
        pc = jnp.clip(st.pc, 0, img.code_len - 1)
        if TIER_ON:
            # lanes parked at a promoted function's ENTRY pc run the
            # compiled CFG body this step (one dispatch per call); they
            # leave both the per-op and fused paths.  The fuel pre-gate
            # mirrors the fused one: a lane without fuel for the
            # worst-case whole call steps per-op instead, so gas
            # exhaustion lands at the correct op bit-identically.
            is_comp = tier_fn_t[pc] >= 0
            if fuel_enabled:
                is_comp = is_comp & (st.fuel > tier_fuel_t[pc])
            is_comp = alive & is_comp
        else:
            is_comp = jnp.bool_(False) & alive
        if FUSE_ON:
            f_n = flen_t[pc]
            is_fused = alive & (f_n >= 2)
            if TIER_ON:
                is_fused = is_fused & ~is_comp
            if fuel_enabled:
                # a lane without the fuel to retire the WHOLE run steps
                # through the original per-op cells instead, so gas
                # exhaustion lands at the correct op with the correct
                # pre-op sp/pc — bit-exact with the unfused build
                if weighted_gas:
                    fuse_cost = jnp.zeros_like(f_n)
                    for j in range(MAX_F):
                        pcj = jnp.clip(pc + j, 0, img.code_len - 1)
                        fuse_cost = fuse_cost + jnp.where(
                            j < f_n, cost_t[pcj], 0)
                else:
                    fuse_cost = f_n
                is_fused = is_fused & (st.fuel - fuse_cost > 0)
            # the per-op path must not also fire for fused lanes: the
            # head pc still carries its ORIGINAL first-op cell
            active = alive & ~is_fused
            if HAS_MEM_PAT:
                is_fused_mem = is_fused & memhead_t[pc]
                is_fused_pure = is_fused & ~memhead_t[pc]
            else:
                is_fused_mem = jnp.bool_(False) & alive
                is_fused_pure = is_fused
        else:
            is_fused = jnp.bool_(False) & alive
            is_fused_mem = is_fused_pure = is_fused
            active = alive
        if TIER_ON:
            active = active & ~is_comp
        cls = cls_t[pc]
        sub = sub_t[pc]
        a = a_t[pc]
        b = b_t[pc]
        c = c_t[pc]
        ilo = ilo_t[pc]
        ihi = ihi_t[pc]
        sp, fp, opbase = st.sp, st.fp, st.opbase

        # ---- operand prefetch (top 3 cells + addressed local/global) ----
        v0_lo = gat(st.stack_lo, sp - 1)
        v0_hi = gat(st.stack_hi, sp - 1)
        v1_lo = gat(st.stack_lo, sp - 2)
        v1_hi = gat(st.stack_hi, sp - 2)
        v2_lo = gat(st.stack_lo, sp - 3)
        v2_hi = gat(st.stack_hi, sp - 3)
        loc_lo = gat(st.stack_lo, fp + a)
        loc_hi = gat(st.stack_hi, fp + a)
        zl = jnp.zeros_like(v0_lo)
        if HAS_SIMD:
            v0_e2 = gat(st.stack_e2, sp - 1)
            v0_e3 = gat(st.stack_e3, sp - 1)
            v1_e2 = gat(st.stack_e2, sp - 2)
            v1_e3 = gat(st.stack_e3, sp - 2)
            v2_e2 = gat(st.stack_e2, sp - 3)
            v2_e3 = gat(st.stack_e3, sp - 3)
            loc_e2 = gat(st.stack_e2, fp + a)
            loc_e3 = gat(st.stack_e3, fp + a)
        else:
            v0_e2 = v0_e3 = v1_e2 = v1_e3 = v2_e2 = v2_e3 = zl
            loc_e2 = loc_e3 = zl
        ng = st.glob_lo.shape[0]
        gidx = jnp.clip(a, 0, ng - 1)
        g_lo = jnp.take_along_axis(st.glob_lo, gidx[None, :], axis=0)[0]
        g_hi = jnp.take_along_axis(st.glob_hi, gidx[None, :], axis=0)[0]

        is_cls = [cls == k for k in range(NUM_CLASSES)]
        trap = st.trap

        # =================== ALU2 ===================
        x_lo, x_hi = v1_lo, v1_hi  # first operand
        y_lo, y_hi = v0_lo, v0_hi  # second operand
        sh32 = y_lo & 31
        div_guard = jnp.where(y_lo == 0, jnp.int32(1), y_lo)
        q32 = lax.div(x_lo, div_guard)
        r32 = lax.rem(x_lo, div_guard)
        # unsigned 32-bit div via f64-free route: use i64-pair division only
        # for i64; for u32 use bit trick through uint32 dtype
        xu = x_lo.astype(jnp.uint32)
        yu = jnp.where(y_lo == 0, jnp.uint32(1), y_lo.astype(jnp.uint32))
        qu32 = lax.div(xu, yu).astype(I32)
        ru32 = lax.rem(xu, yu).astype(I32)

        i32_pairs = [
            (S_I32["add"], x_lo + y_lo),
            (S_I32["sub"], x_lo - y_lo),
            (S_I32["mul"], x_lo * y_lo),
            (S_I32["div_s"], q32),
            (S_I32["div_u"], qu32),
            (S_I32["rem_s"], r32),
            (S_I32["rem_u"], ru32),
            (S_I32["and"], x_lo & y_lo),
            (S_I32["or"], x_lo | y_lo),
            (S_I32["xor"], x_lo ^ y_lo),
            (S_I32["shl"], lax.shift_left(x_lo, sh32)),
            (S_I32["shr_s"], lax.shift_right_arithmetic(x_lo, sh32)),
            (S_I32["shr_u"], lax.shift_right_logical(x_lo, sh32)),
            (S_I32["rotl"], lo_ops.rotl32(x_lo, y_lo)),
            (S_I32["rotr"], lo_ops.rotl32(x_lo, (32 - (y_lo & 31)) & 31)),
            (S_I32["eq"], b2i(x_lo == y_lo)),
            (S_I32["ne"], b2i(x_lo != y_lo)),
            (S_I32["lt_s"], b2i(x_lo < y_lo)),
            (S_I32["lt_u"], b2i(u_lt(x_lo, y_lo))),
            (S_I32["gt_s"], b2i(x_lo > y_lo)),
            (S_I32["gt_u"], b2i(u_lt(y_lo, x_lo))),
            (S_I32["le_s"], b2i(x_lo <= y_lo)),
            (S_I32["le_u"], b2i(lo_ops.u_le(x_lo, y_lo))),
            (S_I32["ge_s"], b2i(x_lo >= y_lo)),
            (S_I32["ge_u"], b2i(lo_ops.u_le(y_lo, x_lo))),
        ]

        add64 = lo_ops.add64(x_lo, x_hi, y_lo, y_hi)
        sub64 = lo_ops.sub64(x_lo, x_hi, y_lo, y_hi)
        mul64 = lo_ops.mul64(x_lo, x_hi, y_lo, y_hi)
        sh64 = y_lo & 63
        shl64 = lo_ops.shl64(x_lo, x_hi, sh64)
        shrs64 = lo_ops.shr64_s(x_lo, x_hi, sh64)
        shru64 = lo_ops.shr64_u(x_lo, x_hi, sh64)
        rotl64 = lo_ops.rotl64(x_lo, x_hi, sh64)
        rotr64 = lo_ops.rotr64(x_lo, x_hi, sh64)
        eq64 = lo_ops.eq64(x_lo, x_hi, y_lo, y_hi)
        lts64 = lo_ops.lt64_s(x_lo, x_hi, y_lo, y_hi)
        ltu64 = lo_ops.lt64_u(x_lo, x_hi, y_lo, y_hi)
        gts64 = lo_ops.lt64_s(y_lo, y_hi, x_lo, x_hi)
        gtu64 = lo_ops.lt64_u(y_lo, y_hi, x_lo, x_hi)

        i64_pairs = [
            (S_I64["add"], add64),
            (S_I64["sub"], sub64),
            (S_I64["mul"], mul64),
            (S_I64["and"], (x_lo & y_lo, x_hi & y_hi)),
            (S_I64["or"], (x_lo | y_lo, x_hi | y_hi)),
            (S_I64["xor"], (x_lo ^ y_lo, x_hi ^ y_hi)),
            (S_I64["shl"], shl64),
            (S_I64["shr_s"], shrs64),
            (S_I64["shr_u"], shru64),
            (S_I64["rotl"], rotl64),
            (S_I64["rotr"], rotr64),
        ]
        i64_cmp_pairs = [
            (S_I64["eq"], b2i(eq64)),
            (S_I64["ne"], b2i(~eq64)),
            (S_I64["lt_s"], b2i(lts64)),
            (S_I64["lt_u"], b2i(ltu64)),
            (S_I64["gt_s"], b2i(gts64)),
            (S_I64["gt_u"], b2i(gtu64)),
            (S_I64["le_s"], b2i(~gts64)),
            (S_I64["le_u"], b2i(~gtu64)),
            (S_I64["ge_s"], b2i(~lts64)),
            (S_I64["ge_u"], b2i(~ltu64)),
        ]

        # rare i64 div/rem under an any-lane conditional (64-iteration loop)
        is_alu2 = is_cls[CLS_ALU2]
        rare_divs = is_alu2 & (
            (sub == S_I64["div_s"]) | (sub == S_I64["div_u"])
            | (sub == S_I64["rem_s"]) | (sub == S_I64["rem_u"]))

        def rare_compute(_):
            glo = jnp.where((y_lo | y_hi) == 0, jnp.int32(1), y_lo)
            ghi = jnp.where((y_lo | y_hi) == 0, jnp.int32(0), y_hi)
            qlo, qhi, rlo, rhi = lo_ops.divmod64_u(x_lo, x_hi, glo, ghi)
            sqlo, sqhi, srlo, srhi = lo_ops.div64_s(x_lo, x_hi, glo, ghi)
            dlo = sel_chain(sub, [
                (S_I64["div_s"], sqlo), (S_I64["div_u"], qlo),
                (S_I64["rem_s"], srlo), (S_I64["rem_u"], rlo)], x_lo)
            dhi = sel_chain(sub, [
                (S_I64["div_s"], sqhi), (S_I64["div_u"], qhi),
                (S_I64["rem_s"], srhi), (S_I64["rem_u"], rhi)], x_hi)
            return dlo, dhi

        rare_lo, rare_hi = lax.cond(
            jnp.any(rare_divs & active), rare_compute,
            lambda _: (x_lo, x_hi), operand=None)

        # f32
        fx = lo_ops.to_f32(x_lo)
        fy = lo_ops.to_f32(y_lo)
        fadd = lo_ops.canon32(lo_ops.from_f32(fx + fy))
        fsub = lo_ops.canon32(lo_ops.from_f32(fx - fy))
        fmul = lo_ops.canon32(lo_ops.from_f32(fx * fy))
        fdiv = lo_ops.canon32(lo_ops.from_f32(fx / fy))
        f32_pairs = [
            (S_F32["add"], fadd), (S_F32["sub"], fsub),
            (S_F32["mul"], fmul), (S_F32["div"], fdiv),
            (S_F32["min"], lo_ops.f32_min(x_lo, y_lo)),
            (S_F32["max"], lo_ops.f32_max(x_lo, y_lo)),
            (S_F32["copysign"],
             (x_lo & jnp.int32(0x7FFFFFFF)) | (y_lo & lo_ops._SIGN)),
        ]
        # comparisons in the integer domain: exact under hardware FTZ
        feq = lo_ops.f32_cmp_eq(x_lo, y_lo)
        flt = lo_ops.f32_cmp_lt(x_lo, y_lo)
        fgt = lo_ops.f32_cmp_lt(y_lo, x_lo)
        fnan = lo_ops.is_nan32(x_lo) | lo_ops.is_nan32(y_lo)
        f32_pairs += [
            (S_F32["eq"], b2i(feq)), (S_F32["ne"], b2i(~feq)),
            (S_F32["lt"], b2i(flt)), (S_F32["gt"], b2i(fgt)),
            (S_F32["le"], b2i((flt | feq) & ~fnan)),
            (S_F32["ge"], b2i((fgt | feq) & ~fnan)),
        ]

        alu2_lo = sel_chain(sub, i32_pairs + i64_cmp_pairs + f32_pairs
                            + [(s, v[0]) for s, v in i64_pairs], jnp.int32(0))
        alu2_hi = sel_chain(sub, [(s, v[1]) for s, v in i64_pairs], jnp.int32(0))
        alu2_lo = jnp.where(rare_divs, rare_lo, alu2_lo)
        alu2_hi = jnp.where(rare_divs, rare_hi, alu2_hi)

        # binary64 (softfloat) subs from the shared table, pruned to what
        # this module's image actually uses so f64-free modules pay
        # nothing; the iterative f64.div runs under an any-lane cond like
        # the i64 divisions above
        for sid in sorted(used_alu2 & set(_A2F)):
            if sid < ALU2_F64_BASE:
                continue
            fn = _A2F[sid]
            if sid in _HEAVY_ALU2:
                m = is_alu2 & (sub == sid)
                rl, rh = lax.cond(
                    jnp.any(m & active),
                    lambda fn=fn: fn(x_lo, x_hi, y_lo, y_hi),
                    lambda: (x_lo, x_hi))
            else:
                rl, rh = fn(x_lo, x_hi, y_lo, y_hi)
            alu2_lo = jnp.where(sub == sid, rl, alu2_lo)
            alu2_hi = jnp.where(sub == sid, rh, alu2_hi)

        # ALU2 traps: i32/i64 division
        div_i32 = is_alu2 & ((sub == S_I32["div_s"]) | (sub == S_I32["div_u"])
                             | (sub == S_I32["rem_s"]) | (sub == S_I32["rem_u"]))
        div_by_zero = (div_i32 & (y_lo == 0)) | (rare_divs & ((y_lo | y_hi) == 0))
        int_min32 = x_lo == jnp.int32(-0x80000000)
        ovf32 = is_alu2 & (sub == S_I32["div_s"]) & int_min32 & (y_lo == -1)
        int_min64 = (x_lo == 0) & (x_hi == jnp.int32(-0x80000000))
        ovf64 = rare_divs & (sub == S_I64["div_s"]) & int_min64 & \
            (y_lo == -1) & (y_hi == -1)
        alu2_trap = jnp.where(div_by_zero, int(ErrCode.DivideByZero), 0)
        alu2_trap = jnp.where(ovf32 | ovf64, int(ErrCode.IntegerOverflow),
                              alu2_trap)

        # =================== ALU1 ===================
        w_lo, w_hi = v0_lo, v0_hi
        fw = lo_ops.to_f32(w_lo)
        ext8 = lax.shift_right_arithmetic(lax.shift_left(w_lo, 24), 24)
        ext16 = lax.shift_right_arithmetic(lax.shift_left(w_lo, 16), 16)
        sign_w = lax.shift_right_arithmetic(w_lo, 31)
        # f32 -> i32 trunc with trap/sat handling
        tr = jnp.where(fw < 0, lax.ceil(fw), lax.floor(fw))
        # bit-domain NaN test: exact under hardware FTZ, same as uniform.py
        nan_w = lo_ops.is_nan32(w_lo)
        in_s = (tr >= jnp.float32(-2147483648.0)) & (tr <= jnp.float32(2147483520.0))
        # 2147483520 = largest f32 below 2^31
        trunc_s_val = jnp.where(in_s & ~nan_w, tr, jnp.float32(0)).astype(I32)
        in_u = (tr >= 0) & (tr <= jnp.float32(4294967040.0))
        tr_u_shift = jnp.where(in_u & ~nan_w, tr, jnp.float32(0))
        trunc_u_val = jnp.where(
            tr_u_shift >= jnp.float32(2147483648.0),
            (tr_u_shift - jnp.float32(4294967296.0)).astype(I32),
            tr_u_shift.astype(I32))
        sat_s = jnp.where(nan_w, 0, jnp.where(
            tr < jnp.float32(-2147483648.0), jnp.int32(-0x80000000), jnp.where(
                tr > jnp.float32(2147483520.0), jnp.int32(0x7FFFFFFF),
                trunc_s_val)))
        sat_u = jnp.where(nan_w | (tr < 0), 0, jnp.where(
            tr > jnp.float32(4294967040.0), jnp.int32(-1), trunc_u_val))
        # i32 -> f32 converts
        cvt_s = lo_ops.from_f32(w_lo.astype(jnp.float32))
        cvt_u = lo_ops.from_f32(w_lo.astype(jnp.uint32).astype(jnp.float32))

        alu1_pairs_lo = [
            (A1["i32.clz"], lax.clz(w_lo)),
            (A1["i32.ctz"], lo_ops.ctz32(w_lo)),
            (A1["i32.popcnt"], lax.population_count(w_lo)),
            (A1["i32.eqz"], b2i(w_lo == 0)),
            (A1["i32.extend8_s"], ext8),
            (A1["i32.extend16_s"], ext16),
            (A1["i64.clz"], lo_ops.clz64(w_lo, w_hi)),
            (A1["i64.ctz"], lo_ops.ctz64(w_lo, w_hi)),
            (A1["i64.popcnt"], lo_ops.popcnt64(w_lo, w_hi)),
            (A1["i64.eqz"], b2i((w_lo | w_hi) == 0)),
            (A1["i64.extend8_s"], ext8),
            (A1["i64.extend16_s"], ext16),
            (A1["i64.extend32_s"], w_lo),
            (A1["f32.abs"], w_lo & jnp.int32(0x7FFFFFFF)),
            (A1["f32.neg"], w_lo ^ lo_ops._SIGN),
            (A1["f32.ceil"], lo_ops.canon32(lo_ops.from_f32(lax.ceil(fw)))),
            (A1["f32.floor"], lo_ops.canon32(lo_ops.from_f32(lax.floor(fw)))),
            (A1["f32.trunc"], lo_ops.f32_trunc(w_lo)),
            (A1["f32.nearest"], lo_ops.f32_nearest(w_lo)),
            (A1["f32.sqrt"], lo_ops.canon32(lo_ops.from_f32(lax.sqrt(fw)))),
            (A1["i32.wrap_i64"], w_lo),
            (A1["i64.extend_i32_s"], w_lo),
            (A1["i64.extend_i32_u"], w_lo),
            (A1["i32.trunc_f32_s"], trunc_s_val),
            (A1["i32.trunc_f32_u"], trunc_u_val),
            (A1["i32.trunc_sat_f32_s"], sat_s),
            (A1["i32.trunc_sat_f32_u"], sat_u),
            (A1["f32.convert_i32_s"], cvt_s),
            (A1["f32.convert_i32_u"], cvt_u),
            (A1["i32.reinterpret_f32"], w_lo),
            (A1["f32.reinterpret_i32"], w_lo),
            (A1["ref.is_null"], b2i((w_lo | w_hi) == 0)),
        ]
        alu1_pairs_hi = [
            (A1["i64.clz"], jnp.int32(0)),
            (A1["i64.ctz"], jnp.int32(0)),
            (A1["i64.popcnt"], jnp.int32(0)),
            (A1["i64.extend8_s"], lax.shift_right_arithmetic(ext8, 31)),
            (A1["i64.extend16_s"], lax.shift_right_arithmetic(ext16, 31)),
            (A1["i64.extend32_s"], sign_w),
            (A1["i64.extend_i32_s"], sign_w),
            (A1["i64.extend_i32_u"], jnp.int32(0)),
        ]
        alu1_lo = sel_chain(sub, alu1_pairs_lo, w_lo)
        alu1_hi = sel_chain(sub, alu1_pairs_hi, jnp.int32(0))
        is_alu1 = is_cls[CLS_ALU1]
        # subs beyond the hand-rolled chain (the f64/softfloat family and
        # the i64<->float conversions) come from the shared table, pruned
        # to the module's image
        _handled = {sid for sid, _ in alu1_pairs_lo}
        for sid in sorted(used_alu1 & set(_A1F)):
            if sid in _handled:
                continue
            fn = _A1F[sid]
            if sid in _HEAVY_ALU1:
                m = is_alu1 & (sub == sid)
                rl, rh = lax.cond(
                    jnp.any(m & active),
                    lambda fn=fn: fn(w_lo, w_hi),
                    lambda: (w_lo, w_hi))
            else:
                rl, rh = fn(w_lo, w_hi)
            alu1_lo = jnp.where(sub == sid, rl, alu1_lo)
            alu1_hi = jnp.where(sub == sid, rh, alu1_hi)
        # traps for every trapping truncation, from the shared table
        alu1_trap = jnp.int32(0) * w_lo
        for sid in sorted(used_alu1 & set(_T1F)):
            bad, codes = _T1F[sid](w_lo, w_hi)
            m = is_alu1 & (sub == sid) & bad
            alu1_trap = jnp.where(m, codes, alu1_trap)

        # =================== memory ===================
        is_load = is_cls[CLS_LOAD]
        is_store = is_cls[CLS_STORE]
        addr_base = jnp.where(is_store, v1_lo, v0_lo)
        ea = addr_base + a  # u32 wrap
        ea_carry = u_lt(ea, addr_base) | u_lt(ea, a)
        nbytes = b
        mem_bytes = st.mem_pages * jnp.int32(65536)
        end = ea + nbytes
        mem_oob = ea_carry | u_lt(end, ea) | u_lt(mem_bytes, end)
        widx = lax.shift_right_logical(ea, 2)
        shB = (ea & 3) * 8
        mw0 = gat(st.mem, widx)
        mw1 = gat(st.mem, widx + 1)
        mw2 = gat(st.mem, widx + 2)
        inv_sh = (32 - shB) & 31
        hi_or = jnp.where(shB == 0, 0, -1)
        raw_lo = lax.shift_right_logical(mw0, shB) | \
            (lax.shift_left(mw1, inv_sh) & hi_or)
        raw_hi = lax.shift_right_logical(mw1, shB) | \
            (lax.shift_left(mw2, inv_sh) & hi_or)
        signed = (c & 1) != 0
        is64 = (c & 2) != 0
        b1 = nbytes == 1
        b2 = nbytes == 2
        b4 = nbytes == 4
        lraw = jnp.where(b1, raw_lo & 0xFF,
                         jnp.where(b2, raw_lo & 0xFFFF, raw_lo))
        lsext = jnp.where(
            b1, lax.shift_right_arithmetic(lax.shift_left(raw_lo, 24), 24),
            jnp.where(b2, lax.shift_right_arithmetic(lax.shift_left(raw_lo, 16), 16),
                      raw_lo))
        load_lo = jnp.where(signed, lsext, lraw)
        load_hi = jnp.where(
            is64,
            jnp.where(nbytes == 8, raw_hi,
                      jnp.where(signed, lax.shift_right_arithmetic(load_lo, 31), 0)),
            jnp.int32(0))

        # stores: build 3-word write masks and values
        full_m_lo = jnp.where(b1, 0xFF, jnp.where(b2, 0xFFFF, jnp.int32(-1)))
        full_m_hi = jnp.where(nbytes == 8, jnp.int32(-1), 0)
        sm0, sm1 = lo_ops.shl64(full_m_lo, full_m_hi, shB)
        sm2 = jnp.where(shB == 0, 0,
                        lo_ops.shr64_u(full_m_lo, full_m_hi, 64 - shB)[0])
        sv0, sv1 = lo_ops.shl64(v0_lo, v0_hi, shB)
        sv2 = jnp.where(shB == 0, 0,
                        lo_ops.shr64_u(v0_lo, v0_hi, 64 - shB)[0])
        nw0 = (mw0 & ~sm0) | (sv0 & sm0)
        nw1 = (mw1 & ~sm1) | (sv1 & sm1)
        nw2 = (mw2 & ~sm2) | (sv2 & sm2)
        store_ok = active & is_store & ~mem_oob

        def run_stores(mp):
            mp = scat(mp, widx, nw0, store_ok & (sm0 != 0))
            mp = scat(mp, widx + 1, nw1, store_ok & (sm1 != 0))
            mp = scat(mp, widx + 2, nw2, store_ok & (sm2 != 0))
            return mp

        # any-lane conditional: steps where no lane stores skip the
        # plane scatters entirely (lockstep batches spend most steps in
        # compute; an unconditional masked scatter still walks the
        # plane on the CPU backend)
        mem_plane = lax.cond(jnp.any(store_ok), run_stores,
                             lambda m: m, st.mem)

        # ------ bulk memory: fill / copy (full-plane masked ops, run
        # under an any-lane conditional since they rewrite [W, lanes]) ---
        # compiled only when the image contains bulk ops: the any-lane
        # lax.cond costs a full-plane pass-through on the CPU backend,
        # which a module without memory.fill/copy must never pay
        HAS_BULK = bool(np.isin(img.cls, (CLS_MEMFILL, CLS_MEMCOPY)).any())
        if HAS_BULK:
            is_fill = is_cls[CLS_MEMFILL]
            is_copy = is_cls[CLS_MEMCOPY]
            is_bulk = is_fill | is_copy
            # operands (top of stack): fill = dst,val,n / copy = dst,src,n
            bulk_n = v0_lo
            bulk_b = v1_lo            # fill value / copy src
            bulk_dst = v2_lo
            mem_bytes_v = st.mem_pages * jnp.int32(65536)
            bulk_end = bulk_dst + bulk_n
            src_end = bulk_b + bulk_n
            bulk_oob = is_bulk & active & (
                u_lt(bulk_end, bulk_dst) | u_lt(mem_bytes_v, bulk_end)
                | (is_copy & (u_lt(src_end, bulk_b)
                              | u_lt(mem_bytes_v, src_end))))
            bulk_go = is_bulk & active & ~bulk_oob & (bulk_n != 0)

            uses_copy = bool((img.cls == CLS_MEMCOPY).any())

            def run_bulk(mem_in):
                return lo_ops.plane_fill_copy(
                    mem_in, bulk_dst, bulk_end, bulk_b, bulk_go,
                    copy_lanes=is_copy if uses_copy else None)

            mem_plane = lax.cond(jnp.any(bulk_go), run_bulk,
                                 lambda m: m, mem_plane)
        else:
            is_bulk = jnp.bool_(False) & (cls == cls)
            bulk_oob = is_bulk

        # =================== v128 (SIMD) ===================
        # cells are 4 int32 planes; ops come from batch/simdops.py and
        # compile only for the sub ids the module image actually uses
        z4p = (zl, zl, zl, zl)
        if HAS_SIMD:
            is_vconst = is_cls[CLS_VCONST]
            is_v2 = is_cls[CLS_V2]
            is_v1 = is_cls[CLS_V1]
            is_vtest = is_cls[CLS_VTEST]
            is_vshift = is_cls[CLS_VSHIFT]
            is_vsplat = is_cls[CLS_VSPLAT]
            is_vextract = is_cls[CLS_VEXTRACT]
            is_vreplace = is_cls[CLS_VREPLACE]
            is_vshuffle = is_cls[CLS_VSHUFFLE]
            is_vbitsel = is_cls[CLS_VBITSEL]
            is_vload = is_cls[CLS_VLOAD]
            is_vstore = is_cls[CLS_VSTORE]
            x4 = (v1_lo, v1_hi, v1_e2, v1_e3)   # second-from-top cell
            y4 = (v0_lo, v0_hi, v0_e2, v0_e3)   # top cell
            w4 = (v2_lo, v2_hi, v2_e2, v2_e3)   # third-from-top cell

            def vsel(used, mk_fn, *args):
                acc = z4p
                for sid in sorted(used):
                    r = mk_fn(sid)(*args)
                    m = sub == sid
                    acc = tuple(jnp.where(m, rn, an)
                                for rn, an in zip(r, acc))
                return acc

            v2_res = vsel(used_v2, sops.v2_fn, x4, y4)
            v1_res = vsel(used_v1, sops.v1_fn, y4)
            vshift_res = vsel(used_vshift,
                              lambda s: sops.vshift_fn(s), x4, v0_lo)
            vsplat_res = vsel(used_vsplat,
                              lambda s: sops.vsplat_fn(s), v0_lo, v0_hi)
            vrepl_res = vsel(used_vreplace,
                             lambda s: (lambda xx, ll, hh, f=sops.
                                        vreplace_dyn(s): f(xx, a, ll, hh)),
                             x4, v0_lo, v0_hi)
            vtest_res = zl
            for sid in sorted(used_vtest):
                r = sops.vtest_fn(sid)(y4)
                vtest_res = jnp.where(sub == sid, r, vtest_res)
            vex_lo, vex_hi = zl, zl
            for sid in sorted(used_vextract):
                rl, rh = sops.vextract_dyn(sid)(y4, a)
                m = sub == sid
                vex_lo = jnp.where(m, rl, vex_lo)
                vex_hi = jnp.where(m, rh, vex_hi)
            vcidx = jnp.clip(a, 0, v128_t.shape[0] - 1)
            vconst_res = tuple(v128_t[vcidx, k] for k in range(4))
            if uses_vshuffle:
                m4 = tuple(v128_t[vcidx, k] for k in range(4))
                vshuf_res = sops.vshuffle_dyn()(x4, y4, m4)
            else:
                vshuf_res = z4p
            # bitselect: operands (v1, v2, mask) = (w4, x4, y4)
            vbit_res = sops.vbitselect()(w4, x4, y4)

            # ---- v128.load / v128.store (5-word shifted window) ----
            # compiled only when the image contains them: the 5 gathers +
            # 5 masked plane scatters are runtime-masked and XLA cannot
            # dead-code-eliminate them otherwise
            if uses_vmem:
                vaddr = jnp.where(is_vstore, v1_lo, v0_lo)
                vea = vaddr + a
                vcarry = u_lt(vea, vaddr) | u_lt(vea, a)
                vend = vea + 16
                v_oob = vcarry | u_lt(vend, vea) | u_lt(mem_bytes, vend)
                vwidx = lax.shift_right_logical(vea, 2)
                vsh = (vea & 3) * 8
                vinv = (32 - vsh) & 31
                v_hi_or = jnp.where(vsh == 0, 0, -1)
                vmw = [gat(st.mem, vwidx + k) for k in range(5)]
                vload_res = tuple(
                    lax.shift_right_logical(vmw[k], vsh)
                    | (lax.shift_left(vmw[k + 1], vinv) & v_hi_or)
                    for k in range(4))
                # store masks/values across the 5-word window
                vm = [lax.shift_left(jnp.int32(-1), vsh)] \
                    + [jnp.int32(-1) * jnp.ones_like(zl)] * 3 \
                    + [jnp.where(vsh == 0, 0,
                                 ~lax.shift_left(jnp.int32(-1), vsh))]
                sv = []
                prev = zl
                for k in range(4):
                    sv.append(lax.shift_left(y4[k], vsh)
                              | (lax.shift_right_logical(prev, vinv)
                                 & v_hi_or))
                    prev = y4[k]
                sv.append(lax.shift_right_logical(prev, vinv) & v_hi_or)
                vstore_ok = active & is_vstore & ~v_oob
                for k in range(5):
                    nw = (vmw[k] & ~vm[k]) | (sv[k] & vm[k])
                    mem_plane = scat(mem_plane, vwidx + k, nw,
                                     vstore_ok & (vm[k] != 0))
            else:
                vload_res = z4p
                v_oob = jnp.zeros_like(cls == cls)
        else:
            is_vconst = is_v2 = is_v1 = is_vtest = is_vshift = \
                is_vsplat = is_vextract = is_vreplace = is_vshuffle = \
                is_vbitsel = is_vload = is_vstore = jnp.bool_(False) & \
                (cls == cls)
            v2_res = v1_res = vshift_res = vsplat_res = vrepl_res = \
                vconst_res = vshuf_res = vbit_res = vload_res = z4p
            vtest_res = vex_lo = vex_hi = zl
            v_oob = jnp.zeros_like(cls == cls)

        is_grow = is_cls[CLS_MEMGROW]
        grow_delta = v0_lo
        grow_ok = ~u_lt(jnp.int32(img.mem_pages_max), st.mem_pages + grow_delta) \
            & (grow_delta >= 0) & ((st.mem_pages + grow_delta) >= st.mem_pages)
        grow_res = jnp.where(grow_ok, st.mem_pages, jnp.int32(-1))
        new_mem_pages = jnp.where(active & is_grow & grow_ok,
                                  st.mem_pages + grow_delta, st.mem_pages)

        # ========== memory.init / data.drop (r05) ==========
        ddrop_p = st.ddrop
        if HAS_DSEG:
            is_minit = is_cls[CLS_MEMINIT]
            is_ddrop = is_cls[CLS_DATA_DROP]
            didx = jnp.clip(a, 0, data_len_t.shape[0] - 1)
            ddropped = gat(st.ddrop, didx)
            dseg_len = jnp.where(ddropped != 0, 0, data_len_t[didx])
            dseg_off = data_off_t[didx]
            mi_n, mi_src, mi_dst = v0_lo, v1_lo, v2_lo
            mi_send = mi_src + mi_n
            mi_dend = mi_dst + mi_n
            mi_oob = is_minit & active & (
                u_lt(mi_send, mi_src) | u_lt(dseg_len, mi_send)
                | u_lt(mi_dend, mi_dst) | u_lt(mem_bytes, mi_dend))
            mi_go = active & is_minit & ~mi_oob & (mi_n != 0)

            def run_minit(mem_in):
                rows = jnp.arange(mem_in.shape[0], dtype=I32)[:, None]
                out = mem_in
                # src byte index for dst byte addr ba: seg_off+src+(ba-dst)
                base_sb = dseg_off + mi_src - mi_dst
                nW = data_words_t.shape[0]
                for bpos in range(4):
                    ba = rows * 4 + bpos
                    inr = (ba >= mi_dst) & (ba < mi_dend) & mi_go
                    sbi = ba + base_sb
                    w = data_words_t[jnp.clip(
                        lax.shift_right_logical(sbi, 2), 0, nW - 1)]
                    byte = lax.shift_right_logical(w, (sbi & 3) * 8) & 0xFF
                    mk = np.int32(np.uint32(0xFF << (bpos * 8)))
                    val = lax.shift_left(byte, bpos * 8)
                    out = jnp.where(inr, (out & ~mk) | (val & mk), out)
                return out

            mem_plane = lax.cond(jnp.any(mi_go), run_minit,
                                 lambda m: m, mem_plane)
            ddrop_p = scat(st.ddrop, didx, jnp.ones_like(didx),
                           active & is_ddrop)
        else:
            is_minit = jnp.bool_(False) & (cls == cls)
            mi_oob = is_minit

        # ========== table families (r05): per-lane table plane ==========
        # The reference's tableInstr.cpp handlers over a shared
        # TableInstance become masked ops over a [T_CAP, lanes] plane —
        # functional arrays make copy/init overlap-safe for free (gather
        # from the pre-op plane, then select).
        tab_p, tsize_p, edrop_p = st.tab, st.tsize, st.edrop
        table_trap = jnp.zeros_like(trap)
        if HAS_T_ANY:
            is_tget = is_cls[CLS_TABLE_GET]
            is_tset = is_cls[CLS_TABLE_SET]
            is_tgrow = is_cls[CLS_TABLE_GROW]
            is_tfill = is_cls[CLS_TABLE_FILL]
            is_tcopy = is_cls[CLS_TABLE_COPY]
            is_tinit = is_cls[CLS_TABLE_INIT]
            tbase = c
            tsize_l = st.tsize if st.tsize is not None else b
            tg_oob = is_tget & ~u_lt(v0_lo, tsize_l)
            if HAS_T_MUT:
                tget_val = gat(st.tab, tbase + v0_lo)
            else:
                tget_val = table0[jnp.clip(tbase + v0_lo, 0,
                                           table0.shape[0] - 1)]
            ts_oob = is_tset & ~u_lt(v1_lo, tsize_l)
            # grow: ... init delta -> v0 = delta, v1 = init ref.  The
            # instruction's b carries this table's CAPACITY (engine
            # rewrites it after clamping; per-tenant slot size in a
            # concatenated multi-tenant image) — growth past it returns
            # -1, the spec-legal failure mode.
            tgrow_new = tsize_l + v0_lo
            tgrow_ok = is_tgrow & (v0_lo >= 0) & (tgrow_new >= tsize_l) \
                & ~u_lt(b, tgrow_new)
            tgrow_res = jnp.where(tgrow_ok, tsize_l, jnp.int32(-1))
            # fill: ... i val n -> v0 = n, v1 = val, v2 = i
            tf_end = v2_lo + v0_lo
            tf_oob = is_tfill & (u_lt(tf_end, v2_lo)
                                 | u_lt(tsize_l, tf_end))
            # copy: ... dst src n -> v0 = n, v1 = src, v2 = dst
            tc_send = v1_lo + v0_lo
            tc_dend = v2_lo + v0_lo
            tc_oob = is_tcopy & (
                u_lt(tc_send, v1_lo) | u_lt(tsize_l, tc_send)
                | u_lt(tc_dend, v2_lo) | u_lt(tsize_l, tc_dend))
            # init: ... dst src n; a = elem segment (len 0 once dropped)
            if HAS_ESEG:
                eidx = jnp.clip(a, 0, elem_len_t.shape[0] - 1)
                edropped = gat(st.edrop, eidx) if st.edrop is not None \
                    else jnp.zeros_like(a)
                eseg_len = jnp.where(edropped != 0, 0, elem_len_t[eidx])
                eseg_off = elem_off_t[eidx]
                ti_send2 = v1_lo + v0_lo
                ti_dend2 = v2_lo + v0_lo
                tinit_oob = is_tinit & (
                    u_lt(ti_send2, v1_lo) | u_lt(eseg_len, ti_send2)
                    | u_lt(ti_dend2, v2_lo) | u_lt(tsize_l, ti_dend2))
            else:
                tinit_oob = is_tinit  # unreachable (no segments)
            t_oob = active & (tg_oob | ts_oob | tf_oob | tc_oob | tinit_oob)
            table_trap = jnp.where(
                t_oob, jnp.int32(int(ErrCode.TableOutOfBounds)), table_trap)
            if HAS_T_MUT:
                tab_p = scat(st.tab, tbase + v1_lo, v0_lo,
                             active & is_tset & ~ts_oob)
                m_grow = active & is_tgrow & tgrow_ok & (v0_lo > 0)
                m_fill = active & is_tfill & ~tf_oob & (v0_lo != 0)
                m_copy = active & is_tcopy & ~tc_oob & (v0_lo != 0)
                m_init = active & is_tinit & ~tinit_oob & (v0_lo != 0) \
                    if HAS_ESEG else jnp.bool_(False) & (cls == cls)
                ranged_go = m_grow | m_fill | m_copy | m_init

                def run_trange(tp):
                    rows = jnp.arange(T_CAP, dtype=I32)[:, None]
                    cur = tp
                    # constant fill: grow writes init (v1) into the new
                    # rows, table.fill writes val (v1) into [i, i+n)
                    lo_f = tbase + jnp.where(m_grow, tsize_l, v2_lo)
                    hi_f = tbase + jnp.where(m_grow, tgrow_new, tf_end)
                    inr = (rows >= lo_f) & (rows < hi_f) & (m_grow | m_fill)
                    cur = jnp.where(inr, v1_lo, cur)
                    if bool((img.cls == CLS_TABLE_COPY).any()):
                        srows = jnp.clip(rows - v2_lo + v1_lo, 0, T_CAP - 1)
                        svals = jnp.take_along_axis(
                            tp, jnp.broadcast_to(srows, tp.shape), axis=0)
                        inc = (rows >= tbase + v2_lo) \
                            & (rows < tbase + tc_dend) & m_copy
                        cur = jnp.where(inc, svals, cur)
                    if HAS_ESEG and bool((img.cls == CLS_TABLE_INIT).any()):
                        sidx = jnp.clip(
                            eseg_off + v1_lo + (rows - (tbase + v2_lo)),
                            0, elem_flat_t.shape[0] - 1)
                        ivals = elem_flat_t[sidx]
                        ini = (rows >= tbase + v2_lo) \
                            & (rows < tbase + ti_dend2) & m_init
                        cur = jnp.where(ini, ivals, cur)
                    return cur

                tab_p = lax.cond(jnp.any(ranged_go), run_trange,
                                 lambda t: t, tab_p)
                if st.tsize is not None:
                    tsize_p = jnp.where(active & is_tgrow & tgrow_ok,
                                        tgrow_new, st.tsize)
            if HAS_ESEG and st.edrop is not None:
                is_edrop = is_cls[CLS_ELEM_DROP]
                edrop_p = scat(st.edrop, eidx, jnp.ones_like(eidx),
                               active & is_edrop)
        else:
            is_tget = is_tgrow = jnp.bool_(False) & (cls == cls)
            tget_val = zl
            tgrow_res = zl
            tsize_l = b

        # =================== tier-0 hostcalls ===================
        # Pure WASI calls retired inside the kernel: the lane executes
        # its HOSTCALL stub like any other instruction (result pushed at
        # opbase, pc+1 to the stub's RETURN) instead of parking for the
        # device->host outcall channel.  Unhandled shapes (cputime
        # clocks, oversized buffers, full stdout buffer, foreign fds)
        # keep the parking path below.
        t0_push = jnp.bool_(False) & (cls == cls)   # retire with a result
        t0_exit = jnp.bool_(False) & (cls == cls)   # proc_exit lanes
        t0_val = zl                                  # pushed cell (errno)
        t0_ctr_p = st.t0_ctr
        so_buf_p = st.so_buf
        so_off_p = st.so_off
        if HAS_T0:
            k0 = t0k_t[pc]
            is_hc = is_cls[CLS_HOSTCALL] & active
            arg0 = gat(st.stack_lo, fp)
            arg1 = gat(st.stack_lo, fp + 1)
            arg2 = gat(st.stack_lo, fp + 2)
            arg3 = gat(st.stack_lo, fp + 3)
            ctr_clk = st.t0_ctr[0]
            ctr_rng = st.t0_ctr[1]
            ctr_fdw = st.t0_ctr[2]
            ctr_sys = st.t0_ctr[3]

            if USE_T0_CLOCK:
                m_clk = is_hc & (k0 == T0_CLOCK_TIME_GET)
                cid = arg0
                tptr = arg2
                bad_id = u_lt(jnp.int32(3), cid)       # unsigned id > 3
                hard_id = (cid == 2) | (cid == 3)      # cputime: tier 1
                tend = tptr + 8
                c_oob = u_lt(tend, tptr) | u_lt(mem_bytes, tend)
                tv_lo, tv_hi = t0_clock_value(t0_time, cid, ctr_clk)
                ok_c = m_clk & ~bad_id & ~hard_id
                wr_c = ok_c & ~c_oob
                mem_plane = lax.cond(
                    jnp.any(wr_c),
                    lambda mp: t0_masked_store(t0_rmw, mp, tptr, tv_lo,
                                               tv_hi, 8, wr_c),
                    lambda mp: mp, mem_plane)
                done_c = m_clk & ~hard_id
                res_c = jnp.where(bad_id, jnp.int32(_E_INVAL),
                                  jnp.where(c_oob, jnp.int32(_E_FAULT), 0))
                t0_push = t0_push | done_c
                t0_val = jnp.where(done_c, res_c, t0_val)
                t0_ctr_p = t0_ctr_p.at[0].set(
                    jnp.where(wr_c, ctr_clk + 1, ctr_clk))

            if USE_T0_RANDOM:
                m_rnd = is_hc & (k0 == T0_RANDOM_GET)
                rbuf, rlen = arg0, arg1
                fits_r = ~u_lt(jnp.int32(RMAX_W * 4), rlen)
                rend = rbuf + rlen
                r_oob = u_lt(rend, rbuf) | u_lt(mem_bytes, rend)
                ok_r = m_rnd & fits_r
                wr_r = ok_r & ~r_oob & (rlen != 0)
                seq_h = t0_rng_seq_hash(RNG_SEED, lane_iota, ctr_rng)

                mem_plane = lax.cond(
                    jnp.any(wr_r),
                    lambda mp: t0_random_fill(t0_rmw, mp, rbuf, rend,
                                              wr_r, seq_h, RMAX_W, zl),
                    lambda mp: mp, mem_plane)
                res_r = jnp.where(r_oob, jnp.int32(_E_FAULT), 0)
                t0_push = t0_push | ok_r
                t0_val = jnp.where(ok_r, res_r, t0_val)
                t0_ctr_p = t0_ctr_p.at[1].set(
                    jnp.where(wr_r, ctr_rng + 1, ctr_rng))

            if USE_T0_FDW:
                m_fdw = is_hc & (k0 == T0_FD_WRITE)
                wfd, wiovs, wcnt, wnp = arg0, arg1, arg2, arg3
                SW = so_buf_p.shape[0]
                iov_end = wiovs + 8
                iov_ok = ~(u_lt(iov_end, wiovs) | u_lt(mem_bytes, iov_end))
                iw = lax.shift_right_logical(wiovs, 2)
                wbuf = gat(mem_plane, iw)
                wlen = gat(mem_plane, iw + 1)
                fits_w = ~u_lt(jnp.int32(WMAX_W * 4), wlen)
                nwords = lax.shift_right_logical(wlen + 3, 2)
                space = ~u_lt(jnp.int32(SW), st.so_off + 1 + nwords)
                npend = wnp + 4
                np_ok = ~(u_lt(npend, wnp) | u_lt(mem_bytes, npend))
                handled_w = m_fdw & ((wfd == 1) | (wfd == 2)) \
                    & (wcnt == 1) & ((wiovs & 3) == 0) & iov_ok \
                    & fits_w & space & np_ok
                dend = wbuf + wlen
                d_oob = u_lt(dend, wbuf) | u_lt(mem_bytes, dend)
                wr_w = handled_w & ~d_oob
                shB_w = (wbuf & 3) * 8
                inv_w = (32 - shB_w) & 31
                hi_or_w = jnp.where(shB_w == 0, 0, -1)
                wsrc0 = lax.shift_right_logical(wbuf, 2)
                mem_snapshot = mem_plane

                def run_fdw(sob):
                    # record: header (fd << 28 | len), then len bytes
                    # padded to whole words — always word-aligned in the
                    # buffer, so only the guest-side source is shifted
                    hdr = wlen | lax.shift_left(wfd, 28)
                    sob = scat(sob, st.so_off, hdr, wr_w)
                    for j in range(WMAX_W):
                        v = t0_shifted_src_word(gat, mem_snapshot, wsrc0,
                                                j, shB_w, inv_w, hi_or_w)
                        sob = scat(sob, st.so_off + 1 + j, v,
                                   wr_w & (jnp.int32(j * 4) < wlen))
                    return sob

                so_buf_p = lax.cond(jnp.any(wr_w), run_fdw,
                                    lambda s: s, so_buf_p)
                mem_plane = lax.cond(
                    jnp.any(wr_w),
                    lambda mp: t0_masked_store(t0_rmw, mp, wnp, wlen,
                                               jnp.zeros_like(wlen), 4,
                                               wr_w),
                    lambda mp: mp, mem_plane)
                so_off_p = jnp.where(wr_w, st.so_off + 1 + nwords,
                                     so_off_p)
                res_w = jnp.where(d_oob, jnp.int32(_E_FAULT), 0)
                done_w = handled_w
                t0_push = t0_push | done_w
                t0_val = jnp.where(done_w, res_w, t0_val)
                t0_ctr_p = t0_ctr_p.at[2].set(
                    jnp.where(wr_w, ctr_fdw + 1, ctr_fdw))

            if USE_T0_YIELD:
                m_yld = is_hc & (k0 == T0_SCHED_YIELD)
                t0_push = t0_push | m_yld
                t0_val = jnp.where(m_yld, 0, t0_val)
                t0_ctr_p = t0_ctr_p.at[3].set(
                    jnp.where(m_yld, ctr_sys + 1, ctr_sys))
                ctr_sys = t0_ctr_p[3]

            if USE_T0_EXIT:
                m_ext = is_hc & (k0 == T0_PROC_EXIT)
                t0_exit = t0_exit | m_ext
                # exit code lands in the result slot for the harvester
                t0_val = jnp.where(m_ext, arg0, t0_val)
                t0_ctr_p = t0_ctr_p.at[3].set(
                    jnp.where(m_ext, ctr_sys + 1, ctr_sys))

        # =================== branches ===================
        is_br = is_cls[CLS_BR]
        is_brz = is_cls[CLS_BRZ]
        is_brnz = is_cls[CLS_BRNZ]
        is_brt = is_cls[CLS_BR_TABLE]
        cond_zero = v0_lo == 0
        brnz_taken = is_brnz & ~cond_zero
        bt_i = jnp.where(u_lt(b, v0_lo), b, v0_lo)  # unsigned clamp to default
        bt_entry = jnp.clip(a + bt_i, 0, brt_t.shape[0] - 1)
        bt_tgt = brt_t[bt_entry, 0]
        bt_keep = brt_t[bt_entry, 1]
        bt_pop = brt_t[bt_entry, 2]

        # =================== call / return ===================
        is_call = is_cls[CLS_CALL]
        is_calli = is_cls[CLS_CALL_INDIRECT]
        if HAS_TAIL:
            # return_call(_indirect): frame REPLACEMENT — the reference's
            # StackManager tail-call path (include/runtime/stackmgr.h:80-98)
            is_rcall = is_cls[CLS_RETCALL]
            is_rcalli = is_cls[CLS_RETCALL_INDIRECT]
        else:
            is_rcall = is_rcalli = jnp.bool_(False) & (cls == cls)
        is_tail = is_rcall | is_rcalli
        is_icall = is_calli | is_rcalli
        is_callany = is_call | is_calli | is_tail
        # per-instruction table window: b = size, c = base (multi-tenant
        # concatenated tables); per-lane tsize plane wins when present
        # (table.grow can have changed it)
        calli_size = st.tsize if (HAS_T_MUT and st.tsize is not None) else b
        ti = c + jnp.clip(v0_lo, 0, jnp.maximum(calli_size - 1, 0))
        ti = jnp.clip(ti, 0, T_CAP - 1 if HAS_T_MUT else table0.shape[0] - 1)
        t_h = gat(st.tab, ti) if HAS_T_MUT else table0[ti]
        # unsigned idx < size (never size-1 arithmetic: b == 0 — an empty
        # table — must always be UndefinedElement, not an underflow)
        ti_oob = is_icall & ~u_lt(v0_lo, calli_size)
        ti_null = is_icall & ~ti_oob & (t_h == 0)
        callee = jnp.where(is_icall, jnp.clip(t_h - 1, 0, f_entry.shape[0] - 1),
                           jnp.clip(a, 0, f_entry.shape[0] - 1))
        sig_bad = is_icall & ~ti_oob & ~ti_null & (f_type[callee] != a)
        c_entry = f_entry[callee]
        c_nparams = f_nparams[callee]
        c_nlocals = f_nlocals[callee]
        c_frame_top = f_frame_top[callee]
        sp_eff = jnp.where(is_icall, sp - 1, sp)
        # tail calls reuse the caller's frame slot: fp stays, args slide
        fp_new = jnp.where(is_tail, fp, sp_eff - c_nparams)
        opbase_new = fp_new + c_nlocals
        # CD-1, not CD: the scalar engine's entry sentinel frame counts
        # toward max_call_depth, so nesting capacity is depth-1 calls
        depth_ovf = (is_call | is_calli) & (st.call_depth >= CD - 1)
        stack_ovf = is_callany & (fp_new + c_frame_top > D)
        call_trap = jnp.where(ti_oob, int(ErrCode.UndefinedElement), 0)
        call_trap = jnp.where(ti_null, int(ErrCode.UninitializedElement), call_trap)
        call_trap = jnp.where(sig_bad, int(ErrCode.IndirectCallTypeMismatch), call_trap)
        call_trap = jnp.where(depth_ovf, int(ErrCode.CallStackExhausted), call_trap)
        call_trap = jnp.where(stack_ovf, int(ErrCode.StackOverflow), call_trap)
        call_ok = active & is_callany & (call_trap == 0)
        tail_ok = call_ok & is_tail

        # frame push (tail calls don't push — they replace)
        fr_ret_pc = scat(st.fr_ret_pc, st.call_depth, pc + 1,
                         call_ok & ~is_tail)
        fr_fp = scat(st.fr_fp, st.call_depth, fp, call_ok & ~is_tail)
        fr_opbase = scat(st.fr_opbase, st.call_depth, opbase,
                         call_ok & ~is_tail)

        # return
        is_ret = is_cls[CLS_RETURN]
        ret_done = is_ret & (st.call_depth == 0)
        rd = jnp.clip(st.call_depth - 1, 0, CD - 1)
        r_pc = gat(st.fr_ret_pc, rd)
        r_fp = gat(st.fr_fp, rd)
        r_opbase = gat(st.fr_opbase, rd)
        nres = b  # CLS_RETURN carries result count in b

        # =================== merge: stack top write ===================
        is_const = is_cls[CLS_CONST]
        is_lget = is_cls[CLS_LOCAL_GET]
        is_gget = is_cls[CLS_GLOBAL_GET]
        is_msize = is_cls[CLS_MEMSIZE]
        is_sel = is_cls[CLS_SELECT]
        sel_lo = jnp.where(cond_zero, v1_lo, v2_lo)
        sel_hi = jnp.where(cond_zero, v1_hi, v2_hi)

        sel_e2 = jnp.where(cond_zero, v1_e2, v2_e2)
        sel_e3 = jnp.where(cond_zero, v1_e3, v2_e3)
        wpos = sp  # default for push-class
        wlo = ilo
        whi = ihi
        we2 = zl
        we3 = zl
        does_write = is_const
        write_entries = [
            (is_lget, sp, loc_lo, loc_hi, loc_e2, loc_e3),
            (is_gget, sp, g_lo, g_hi),
            (is_msize, sp, st.mem_pages, jnp.zeros_like(st.mem_pages)),
            (is_alu1, sp - 1, alu1_lo, alu1_hi),
            (is_grow, sp - 1, grow_res, jnp.zeros_like(grow_res)),
            (is_load & ~mem_oob, sp - 1, load_lo, load_hi),
            (is_alu2, sp - 2, alu2_lo, alu2_hi),
            (is_sel, sp - 3, sel_lo, sel_hi, sel_e2, sel_e3),
            (is_br & (b == 1), opbase + c, v0_lo, v0_hi, v0_e2, v0_e3),
            (brnz_taken & (b == 1), opbase + c, v1_lo, v1_hi,
             v1_e2, v1_e3),
            (is_brt & (bt_keep == 1), opbase + bt_pop, v1_lo, v1_hi,
             v1_e2, v1_e3),
            (is_ret & (nres == 1), fp, v0_lo, v0_hi, v0_e2, v0_e3),
            (is_vconst, sp, *vconst_res),
            (is_v2, sp - 2, *v2_res),
            (is_vshift, sp - 2, *vshift_res),
            (is_vshuffle, sp - 2, *vshuf_res),
            (is_vreplace, sp - 2, *vrepl_res),
            (is_v1, sp - 1, *v1_res),
            (is_vsplat, sp - 1, *vsplat_res),
            (is_vextract, sp - 1, vex_lo, vex_hi),
            (is_vtest, sp - 1, vtest_res, zl),
            (is_vbitsel, sp - 3, *vbit_res),
            (is_vload & ~v_oob, sp - 1, *vload_res),
            (is_tget & (table_trap == 0), sp - 1, tget_val,
             jnp.zeros_like(tget_val)),
            (is_cls[CLS_REFFUNC], sp, a + 1, jnp.zeros_like(a)),
            (is_cls[CLS_TABLE_SIZE], sp, tsize_l, jnp.zeros_like(tsize_l)),
            (is_tgrow & (table_trap == 0), sp - 2, tgrow_res,
             jnp.zeros_like(tgrow_res)),
        ]
        if HAS_T0:
            # tier-0 retirements push their errno (or proc_exit code) at
            # the frame's operand base, exactly where the host outcall
            # serve would have written the result
            write_entries.append((t0_push | t0_exit, opbase, t0_val, zl))
        for entry in write_entries:
            m, pos, lo_v, hi_v = entry[0], entry[1], entry[2], entry[3]
            e2_v = entry[4] if len(entry) > 4 else zl
            e3_v = entry[5] if len(entry) > 5 else zl
            wpos = jnp.where(m, pos, wpos)
            wlo = jnp.where(m, lo_v, wlo)
            whi = jnp.where(m, hi_v, whi)
            if HAS_SIMD:
                we2 = jnp.where(m, e2_v, we2)
                we3 = jnp.where(m, e3_v, we3)
            does_write = does_write | m

        wmask = active & does_write & (trap == 0)
        stack_lo = scat(st.stack_lo, wpos, wlo, wmask)
        stack_hi = scat(st.stack_hi, wpos, whi, wmask)
        if HAS_SIMD:
            stack_e2 = scat(st.stack_e2, wpos, we2, wmask)
            stack_e3 = scat(st.stack_e3, wpos, we3, wmask)

        # locals write (set/tee)
        is_lset = is_cls[CLS_LOCAL_SET]
        is_ltee = is_cls[CLS_LOCAL_TEE]
        lmask = active & (is_lset | is_ltee)
        stack_lo = scat(stack_lo, fp + a, v0_lo, lmask)
        stack_hi = scat(stack_hi, fp + a, v0_hi, lmask)
        if HAS_SIMD:
            stack_e2 = scat(stack_e2, fp + a, v0_e2, lmask)
            stack_e3 = scat(stack_e3, fp + a, v0_e3, lmask)

        # tail-call arg slide: [sp_eff - nparams, sp_eff) -> [fp, fp+nparams)
        # (ascending copy is overlap-safe: src row >= dst row always,
        # because src base sp_eff - nparams >= opbase >= fp)
        if HAS_TAIL:
            for k in range(MAX_NPAR):
                amask = tail_ok & (k < c_nparams)
                srcp = sp_eff - c_nparams + k
                stack_lo = scat(stack_lo, fp + k, gat(stack_lo, srcp), amask)
                stack_hi = scat(stack_hi, fp + k, gat(stack_hi, srcp), amask)
                if HAS_SIMD:
                    stack_e2 = scat(stack_e2, fp + k, gat(stack_e2, srcp),
                                    amask)
                    stack_e3 = scat(stack_e3, fp + k, gat(stack_e3, srcp),
                                    amask)

        # zero callee locals beyond params (static unrolled window)
        for k in range(img.max_local_zeros):
            zpos = fp_new + c_nparams + k
            zmask = call_ok & (k < (c_nlocals - c_nparams))
            stack_lo = scat(stack_lo, zpos, jnp.zeros_like(v0_lo), zmask)
            stack_hi = scat(stack_hi, zpos, jnp.zeros_like(v0_hi), zmask)
            if HAS_SIMD:
                stack_e2 = scat(stack_e2, zpos, zl, zmask)
                stack_e3 = scat(stack_e3, zpos, zl, zmask)
        if not HAS_SIMD:
            stack_e2 = st.stack_e2
            stack_e3 = st.stack_e3

        # globals write
        is_gset = is_cls[CLS_GLOBAL_SET]
        gmask = active & is_gset
        gcur_lo = jnp.take_along_axis(st.glob_lo, gidx[None, :], axis=0)[0]
        gcur_hi = jnp.take_along_axis(st.glob_hi, gidx[None, :], axis=0)[0]
        glob_lo = st.glob_lo.at[gidx, lane_iota].set(
            jnp.where(gmask, v0_lo, gcur_lo))
        glob_hi = st.glob_hi.at[gidx, lane_iota].set(
            jnp.where(gmask, v0_hi, gcur_hi))

        # =================== fused dispatch cells ===================
        # one dispatch retires a whole straight-line run's stack
        # effects (batch/fuse.py); fused-lane masks are disjoint from
        # every per-op write mask above (active excludes them), so
        # applying the fused scatters after the per-op ones is exact.
        # Any-lane conditional: steps where no lane sits at a fused
        # head skip the pattern handlers entirely (same rationale as
        # the store scatters above on the CPU backend).
        if FUSE_ON:
            fused_sp = sp
            _stk = tuple([stack_lo, stack_hi] + (
                [stack_e2, stack_e3] if HAS_SIMD else []))

            if HAS_PURE_PAT:
                def _run_fused(ops):
                    stk, gl, gh = ops
                    stk2, (gl2, gh2), fsp = fused_apply(
                        list(stk), (gl, gh), pc, sp, fp,
                        is_fused_pure)
                    return tuple(stk2), gl2, gh2, fsp

                def _skip_fused(ops):
                    stk, gl, gh = ops
                    return stk, gl, gh, sp

                _stk, glob_lo, glob_hi, fused_sp = lax.cond(
                    jnp.any(is_fused_pure), _run_fused, _skip_fused,
                    (_stk, glob_lo, glob_hi))
            if HAS_MEM_PAT:
                # licensed memory runs (r19): same disjoint-mask merge
                # for the stack/global planes; the memory plane itself
                # NEVER rides the conditional's tuple carry (a big
                # buffer there costs a full-plane copy every step on
                # the CPU backend) — the handler reads it and returns
                # per-lane (widx, value, mask) store triples, applied
                # below under the per-op path's run_stores shape
                _zstores = tuple((zl, zl, is_fused_mem & False)
                                 for _ in range(N_MEM_SLOTS))

                def _run_memfused(ops):
                    stk, gl, gh = ops
                    stk2, (gl2, gh2), st_out, fsp = memfuse_apply(
                        list(stk), (gl, gh), mem_plane, pc, sp, fp,
                        is_fused_mem)
                    return tuple(stk2), gl2, gh2, st_out, fsp

                def _skip_memfused(ops):
                    stk, gl, gh = ops
                    return stk, gl, gh, _zstores, sp

                _stk, glob_lo, glob_hi, _mstores, _fsp_mem = \
                    lax.cond(jnp.any(is_fused_mem), _run_memfused,
                             _skip_memfused,
                             (_stk, glob_lo, glob_hi))
                fused_sp = jnp.where(is_fused_mem, _fsp_mem, fused_sp)
                fused_st = is_fused_mem & sthead_t[pc]

                def _apply_mstores(mp):
                    for wi, v, mk in _mstores:
                        mp = scat(mp, wi, v, mk)
                    return mp

                mem_plane = lax.cond(jnp.any(fused_st),
                                     _apply_mstores, lambda mp: mp,
                                     mem_plane)
            stack_lo, stack_hi = _stk[0], _stk[1]
            if HAS_SIMD:
                stack_e2, stack_e3 = _stk[2], _stk[3]

        # =================== compiled-function bodies ===================
        # one dispatch retires a whole promoted CALL (batch/tierup.py);
        # compiled-lane masks are disjoint from every per-op and fused
        # mask above (active/is_fused exclude them), so applying the
        # body's scatters after theirs is exact.  Any-lane conditional:
        # steps where no lane sits at a promoted entry skip the bodies
        # entirely.  The memory plane is READ-ONLY inside (v1 promotes
        # load-only functions) and the opcode histogram rides the
        # conditional so in-body retirement attributes per-pc
        # (histogram == retired, as with fused runs).
        if TIER_ON:
            _cstk = tuple([stack_lo, stack_hi] + (
                [stack_e2, stack_e3] if HAS_SIMD else []))
            _c_hist0 = st.op_hist

            def _run_comp(ops):
                stk, oh = ops
                stk2, oh2, csp, cret, cbail, cbpc, crd, cfd = \
                    tierup_apply(list(stk), mem_plane, oh, pc, sp, fp,
                                 opbase, is_comp)
                return tuple(stk2), oh2, csp, cret, cbail, cbpc, crd, cfd

            def _skip_comp(ops):
                stk, oh = ops
                fl = jnp.bool_(False) & alive
                return stk, oh, sp, fl, fl, pc, zl, zl

            (_cstk, _c_hist, comp_sp, comp_ret, comp_bail, comp_bail_pc,
             comp_rd, comp_fd) = lax.cond(
                jnp.any(is_comp), _run_comp, _skip_comp,
                (_cstk, _c_hist0))
            stack_lo, stack_hi = _cstk[0], _cstk[1]
            if HAS_SIMD:
                stack_e2, stack_e3 = _cstk[2], _cstk[3]

        # =================== merge: sp / pc / frames ===================
        new_sp = sp
        for m, v in (
            (t0_push, opbase + 1),
            (is_const | is_lget | is_gget | is_msize | is_vconst
             | is_cls[CLS_TABLE_SIZE] | is_cls[CLS_REFFUNC], sp + 1),
            (is_cls[CLS_DROP] | is_lset | is_gset | is_alu2 | is_brz
             | (is_brnz & cond_zero) | is_v2 | is_vshift | is_vshuffle
             | is_vreplace | is_tgrow, sp - 1),
            (is_cls[CLS_STORE] | is_sel | is_vstore | is_vbitsel
             | is_cls[CLS_TABLE_SET], sp - 2),
            (is_bulk | is_cls[CLS_TABLE_FILL] | is_cls[CLS_TABLE_COPY]
             | is_cls[CLS_TABLE_INIT] | is_minit, sp - 3),
            (is_br, opbase + c + b),
            (brnz_taken, opbase + c + b),
            (is_brt, opbase + bt_pop + bt_keep),
            (is_ret, fp + nres),
            (call_ok, opbase_new),
        ):
            new_sp = jnp.where(m, v, new_sp)

        new_pc = pc + 1
        new_pc = jnp.where(is_br, a, new_pc)
        new_pc = jnp.where(is_brz & cond_zero, a, new_pc)
        new_pc = jnp.where(brnz_taken, a, new_pc)
        new_pc = jnp.where(is_brt, bt_tgt, new_pc)
        new_pc = jnp.where(call_ok, c_entry, new_pc)
        new_pc = jnp.where(is_ret & ~ret_done, r_pc, new_pc)

        new_fp = jnp.where(call_ok, fp_new, fp)
        new_fp = jnp.where(is_ret & ~ret_done, r_fp, new_fp)
        new_opbase = jnp.where(call_ok, opbase_new, opbase)
        new_opbase = jnp.where(is_ret & ~ret_done, r_opbase, new_opbase)
        new_depth = st.call_depth + jnp.where(call_ok & ~is_tail, 1, 0) \
            - jnp.where(active & is_ret & ~ret_done, 1, 0)

        # =================== traps / fuel / retire ===================
        new_trap = trap
        for m, code in (
            (is_cls[CLS_TRAP], a),
            # park at the stub UNLESS tier 0 retired the call in-kernel;
            # the host outcall loop re-arms parked lanes
            (is_cls[CLS_HOSTCALL] & ~t0_push & ~t0_exit,
             jnp.int32(TRAP_HOSTCALL)),
            # in-kernel proc_exit: the lane terminates; its exit code
            # sits in the result slot (stack[opbase])
            (t0_exit, jnp.int32(int(ErrCode.Terminated))),
            (alu2_trap != 0, alu2_trap),
            (alu1_trap != 0, alu1_trap),
            ((is_load | is_store) & mem_oob,
             jnp.int32(int(ErrCode.MemoryOutOfBounds))),
            ((is_vload | is_vstore) & v_oob,
             jnp.int32(int(ErrCode.MemoryOutOfBounds))),
            (bulk_oob, jnp.int32(int(ErrCode.MemoryOutOfBounds))),
            (mi_oob, jnp.int32(int(ErrCode.MemoryOutOfBounds))),
            (table_trap != 0, table_trap),
            (is_callany & (call_trap != 0), call_trap),
            (ret_done, jnp.int32(TRAP_DONE)),
        ):
            new_trap = jnp.where(active & m, code, new_trap)

        if FUSE_ON:
            # a fused dispatch retires the whole run; each constituent
            # keeps per-op attribution (f_n ops of gas/histogram)
            ret_inc = jnp.where(
                alive, jnp.where(is_fused, f_n, jnp.int32(1)), jnp.int32(0))
        else:
            ret_inc = b2i(active)
        if TIER_ON:
            # a compiled dispatch retires the whole CALL; the body
            # reports the exact per-lane count (bail-outs included)
            ret_inc = jnp.where(is_comp, comp_rd, ret_inc)
        new_retired = st.retired + ret_inc
        if fuel_enabled:
            dec = jnp.where(active, cost_t[pc], 0) if weighted_gas \
                else b2i(active)
            if FUSE_ON:
                # fused lanes are pre-gated on fuel > run cost, so the
                # exhaustion check below (active-only) stays exact
                dec = dec + jnp.where(is_fused, fuse_cost, 0)
            if TIER_ON:
                # compiled lanes: exact per-op gas from the body, also
                # pre-gated (fuel > whole-call worst case)
                dec = dec + jnp.where(is_comp, comp_fd, 0)
            new_fuel = st.fuel - dec
            new_trap = jnp.where(active & (new_fuel <= 0) & (new_trap == 0),
                                 int(ErrCode.CostLimitExceeded), new_trap)
        else:
            new_fuel = st.fuel

        # lanes that trapped THIS step keep their pre-step control state
        halted_now = active & (new_trap != 0)
        new_pc = jnp.where(halted_now, pc, new_pc)
        keep = ~halted_now & active
        pc_out = jnp.where(keep, new_pc, st.pc)
        sp_out = jnp.where(keep, new_sp,
                           jnp.where(ret_done, fp + nres, st.sp))
        if FUSE_ON:
            # fused lanes: pc jumps past the whole run, sp takes the
            # run's net stack effect (fp/opbase/depth never change —
            # fused classes are pure stack/ALU)
            pc_out = jnp.where(is_fused, pc + f_n, pc_out)
            sp_out = jnp.where(is_fused, fused_sp, sp_out)
        fp_out = jnp.where(keep, new_fp, st.fp)
        opbase_out = jnp.where(keep, new_opbase, st.opbase)
        depth_out = jnp.where(keep, new_depth, st.call_depth)
        if TIER_ON:
            # compiled lanes come back RETURNED (the whole call retired:
            # replicate the per-op CLS_RETURN merge — the body never
            # pushed frames, so r_pc/r_fp/r_opbase gathered from the
            # pre-step frame stack are exactly the right pop) or BAILED
            # at a block head (iteration cap: resume per-op mid-function
            # with the body's partial sp/retired/fuel, bit-identically)
            comp_done = comp_ret & (st.call_depth == 0)
            comp_pop = comp_ret & (st.call_depth > 0)
            pc_out = jnp.where(comp_pop, r_pc, pc_out)
            pc_out = jnp.where(comp_bail, comp_bail_pc, pc_out)
            # comp_done lanes keep their pre-step pc (the halted shape:
            # pc_out already defaults to st.pc for non-active lanes)
            sp_out = jnp.where(is_comp, comp_sp, sp_out)
            fp_out = jnp.where(comp_pop, r_fp, fp_out)
            opbase_out = jnp.where(comp_pop, r_opbase, opbase_out)
            depth_out = jnp.where(comp_pop, st.call_depth - 1, depth_out)
            new_trap = jnp.where(comp_done, jnp.int32(TRAP_DONE),
                                 new_trap)

        # device-side obs planes: per-pc retired histogram (attributed
        # to every CONSTITUENT op of a fused run — histogram == retired
        # by construction) and the fused/unfused dispatch counters.
        # Both are trace-time static: None planes compile to nothing.
        op_hist_p = _c_hist if (TIER_ON and st.op_hist is not None) \
            else st.op_hist
        if st.op_hist is not None:
            H = st.op_hist.shape[0]
            if FUSE_ON:
                hln = jnp.where(is_fused, f_n, jnp.int32(1))
                if TIER_ON:
                    # compiled lanes attributed in-body (per block
                    # execution count -> per constituent pc)
                    hln = jnp.where(is_comp, jnp.int32(0), hln)
                for j in range(MAX_F):
                    op_hist_p = op_hist_p.at[
                        jnp.clip(pc + j, 0, H - 1)].add(
                        b2i(alive & (j < hln)))
            else:
                hm = (alive & ~is_comp) if TIER_ON else alive
                op_hist_p = op_hist_p.at[jnp.clip(pc, 0, H - 1)].add(
                    b2i(hm))
        fu_ctr_p = st.fu_ctr
        if st.fu_ctr is not None:
            if FUSE_ON:
                fu_ctr_p = st.fu_ctr + jnp.stack([
                    jnp.sum(b2i(is_fused)),
                    jnp.sum(jnp.where(is_fused, f_n, 0)),
                    jnp.sum(ret_inc)])
            else:
                # a fused-plane state resumed on an unfused build (the
                # supervisor's demotion rung) keeps the total-retired
                # row live so the plane is never an identity
                # passthrough in the donated carry
                fu_ctr_p = st.fu_ctr + jnp.stack([
                    jnp.int32(0), jnp.int32(0), jnp.sum(ret_inc)])
        tu_ctr_p = st.tu_ctr
        if st.tu_ctr is not None:
            if TIER_ON:
                tu_ctr_p = st.tu_ctr + jnp.stack([
                    jnp.sum(b2i(is_comp)),
                    jnp.sum(jnp.where(is_comp, comp_rd, 0)),
                    jnp.sum(ret_inc)])
            else:
                # same liveness discipline as fu_ctr for states resumed
                # on a tierup-off build (the simt_nocomp demotion rung)
                tu_ctr_p = st.tu_ctr + jnp.stack([
                    jnp.int32(0), jnp.int32(0), jnp.sum(ret_inc)])
        return BatchState(
            pc=pc_out,
            sp=sp_out,
            fp=fp_out,
            opbase=opbase_out,
            call_depth=depth_out,
            trap=new_trap,
            retired=new_retired,
            fuel=new_fuel,
            mem_pages=new_mem_pages,
            stack_lo=stack_lo,
            stack_hi=stack_hi,
            fr_ret_pc=fr_ret_pc,
            fr_fp=fr_fp,
            fr_opbase=fr_opbase,
            glob_lo=glob_lo,
            glob_hi=glob_hi,
            mem=mem_plane,
            stack_e2=stack_e2,
            stack_e3=stack_e3,
            tab=tab_p,
            tsize=tsize_p,
            edrop=edrop_p,
            ddrop=ddrop_p,
            # t0_time stays None in the carried state (it rides the
            # chunk as a separate non-donated argument)
            t0_ctr=t0_ctr_p,
            so_buf=so_buf_p,
            so_off=so_off_p,
            op_hist=op_hist_p,
            fu_ctr=fu_ctr_p,
            tu_ctr=tu_ctr_p,
        )

    # a stable name for the step's operations in a device trace
    return jax.named_scope("wasm_simt_step")(step)


class BatchEngine:
    """Runs one module's exported function over N lanes in lockstep.

    Engine-facing analog of Executor::invoke for the tpu_batch engine
    (SURVEY.md §2.10): construct from an instantiated module, call run()
    with per-lane argument arrays.
    """

    def __init__(self, inst, store=None, conf=None, lanes: Optional[int] = None,
                 mesh=None, img=None):
        from wasmedge_tpu.common.configure import Configure
        from wasmedge_tpu.batch.image import batchability, build_device_image

        self.mesh = mesh  # lane-sharded multi-chip execution (parallel/mesh.py)
        self.conf = conf or Configure()
        cfg = self.conf.batch
        self.cfg = cfg
        self.lanes = lanes or cfg.lanes
        self.inst = inst
        self.store = store  # kept for re-deriving engines (scheduler)
        self.hostcall_stats = new_hostcall_stats()
        # flight recorder (obs/): the shared ring when conf.obs is
        # enabled, the no-op guard object otherwise
        from wasmedge_tpu.obs.recorder import recorder_of

        self.obs = recorder_of(self.conf)
        # divergence-aware lane compaction (batch/compact.py): armed
        # per run by the fixed-cohort drivers (run/ShardDrive/uniform
        # handoff/supervisor SIMT tier); the serving layer sets
        # _compact_external and owns its own compactor instead, so the
        # engine never permutes under a server's lane bindings
        self.compactor = None
        if img is not None:
            # share an already-built (and already-normalized) image — the
            # scheduler derives width-variant engines from one module
            self.img = img
            self._t0kinds = self._t0_gate(t0_effective_kinds(img, cfg))
            self._step = None
            self._run_chunk = None
            return
        host_imports = {i for i, f in enumerate(inst.funcs)
                        if getattr(f, "kind", None) == "host"}
        reason = batchability(inst.lowered, host_imports=host_imports,
                              n_memories=len(inst.memories or ()))
        if reason is not None:
            raise ValueError(f"module not batchable: {reason}")
        self.img = build_device_image(
            inst.lowered, memories=inst.memories, globals_=inst.globals,
            table0=self._table_snapshot(inst, store), mod=inst.ast,
            elem_segs=self._elem_snapshot(inst, store),
            data_segs=[bytes(d.data) for d in inst.datas])
        # Per-lane table capacity for table.grow, mirroring the memory
        # knob clamp below: declared max wins, clamped by the Configure
        # knob; grow beyond capacity returns -1 (spec-legal failure).
        tsize0 = self.img.table0.shape[0]
        if self.img.has_table_grow:
            declared = self.img.table_max if self.img.table_max > 0 \
                else cfg.table_elems_per_lane
            self.img.table_cap = max(
                tsize0, min(declared, cfg.table_elems_per_lane))
            # table.grow checks capacity from its instruction word (b):
            # per-table in a concatenated multi-tenant image
            self.img.b[self.img.cls == CLS_TABLE_GROW] = self.img.table_cap
        else:
            self.img.table_cap = tsize0
        # Static per-lane memory ceiling: the declared max clamped by the
        # Configure knob (scalar analog: MemoryInstance.grow page_limit).
        # A module with no declared max (mem_pages_max == 0) gets the knob
        # value — growth beyond memory_pages_per_lane returns -1, which is
        # the one place batch semantics are knob-dependent (static HBM
        # allocation; set the knob >= the workload's peak for parity).
        if self.img.has_memory:
            declared = self.img.mem_pages_max \
                if self.img.mem_pages_max > 0 else cfg.memory_pages_per_lane
            self.img.mem_pages_max = max(
                self.img.mem_pages_init,
                min(declared, cfg.memory_pages_per_lane))
        # type-level checks run unconditionally: a module can carry
        # v128-typed globals/signatures without any v128 OPCODE (pure
        # moves), and the 2-plane cells would silently truncate them
        from wasmedge_tpu.common.types import ValType

        for g in inst.globals:
            if g.type.val_type == ValType.V128:
                raise ValueError(
                    "module not batchable: v128-typed global")
        self._t0kinds = self._t0_gate(t0_effective_kinds(self.img, cfg))
        self._step = None
        self._run_chunk = None

    def _plan_fusion(self):
        """Run the superinstruction translation pass once per image
        (batch/fuse.py): the analyzer's top candidates become fused
        dispatch cells in new image planes.  Knob off = never planned =
        the step builder compiles the bit-identical seed path.

        Deferred to first _build() / obs-on initial_state / ladder
        gating / image concat rather than engine construction: planning
        dereferences the image's LAZY analysis binding, and a merely-
        constructed engine (batchability probes, registry stash) must
        keep the r12 guarantee that startups which never compile a step
        never pay the analyzer.  Idempotent (fusion_report sentinel)."""
        if not getattr(self.cfg, "fuse_superinstructions", True):
            return
        if getattr(self.img, "fusion_report", None) is not None:
            return  # already planned (shared image)
        from wasmedge_tpu.batch.fuse import plan_fusion

        plan_fusion(self.img, self.cfg)
        # licensed-vs-reverted memory-run counters for the Prometheus
        # export (planning statics — the device fu_ctr plane already
        # counts fused dispatches at runtime)
        mem = (self.img.fusion_report or {}).get("memory")
        if mem and self.obs.enabled:
            self.obs.set_memfuse_static(mem)

    def _plan_tierup(self):
        """Run the whole-function promotion pass once per image
        (batch/tierup.py), AFTER _plan_fusion — hot-function selection
        reads the realized fusion plan.  Same lazy/idempotent
        discipline as _plan_fusion (tierup_report sentinel); knob off =
        never planned = the step builder compiles the bit-identical
        seed/fused path."""
        if not getattr(self.cfg, "tierup", True):
            return
        if getattr(self.img, "tierup_report", None) is not None:
            return  # already planned (shared image)
        self._plan_fusion()
        from wasmedge_tpu.batch.tierup import plan_tierup

        plan_tierup(self.img, self.cfg)
        rep = self.img.tierup_report or {}
        if rep and self.obs.enabled:
            self.obs.set_tierup_static(rep)

    def _t0_gate(self, kinds):
        """Engine-level tier-0 gating: fd_write buffering additionally
        requires that the instance's WASI environ has fds 1/2 as plain
        writable sinks at engine-build time (the image-level gate
        already excludes modules that could mutate the fd table)."""
        from wasmedge_tpu.batch.image import T0_FD_WRITE

        if kinds is None or not (kinds == T0_FD_WRITE).any():
            return kinds
        from wasmedge_tpu.batch.hostcall import wasi_env_of
        from wasmedge_tpu.host.wasi.wasi_abi import Rights

        env = wasi_env_of(self)
        ok = env is not None
        for fd in (1, 2):
            e = env.fds.get(fd) if ok else None
            ok = ok and e is not None and e.kind in ("stdio", "file") \
                and bool(e.rights_base & Rights.FD_WRITE)
        if not ok:
            kinds = kinds.copy()
            kinds[kinds == T0_FD_WRITE] = 0
            if not (kinds != 0).any():
                return None
        return kinds

    @staticmethod
    def _table_snapshot(inst, store):
        """Table image: store-interned handles -> funcidx+1 (0 = null).

        Cross-module refs are unresolvable on device; batchability() already
        gates modules whose tables could contain them (no table mutation,
        active elems only reference local funcs)."""
        if not inst.tables:
            return None
        func_index = {id(f): i for i, f in enumerate(inst.funcs)}
        refs = []
        for h in inst.tables[0].refs:
            if h == 0:
                refs.append(0)
                continue
            fi = store.deref_func(h) if store is not None else None
            idx = func_index.get(id(fi)) if fi is not None else None
            if idx is None:
                raise ValueError("table entry references a non-local function; "
                                 "module not batchable")
            refs.append(idx + 1)
        return refs

    @staticmethod
    def _elem_snapshot(inst, store):
        """Element segments resolved into the device funcref domain
        (funcidx+1, 0 = null) for in-kernel table.init.  A segment
        holding a cross-module ref only blocks modules that can reach it
        (table.init); others keep batching with that segment omitted."""
        from wasmedge_tpu.common.opcodes import Op

        func_index = {id(f): i for i, f in enumerate(inst.funcs)}
        segs = []
        ops = np.asarray(inst.lowered.op[:inst.lowered.code_len])
        needs = bool((ops == int(Op.table_init)).any())
        for seg in inst.elems:
            refs = []
            bad = False
            for h in seg.refs:
                if h == 0:
                    refs.append(0)
                    continue
                fi = store.deref_func(h) if store is not None else None
                idx = func_index.get(id(fi)) if fi is not None else None
                if idx is None:
                    bad = True
                    break
                refs.append(idx + 1)
            if bad:
                if needs:
                    raise ValueError(
                        "element segment references a non-local function; "
                        "module not batchable")
                refs = []
            segs.append(refs)
        return segs

    # -- execution ---------------------------------------------------------
    def _build(self):
        from wasmedge_tpu.batch import ensure_jax_backend, jit_in_place

        self._plan_fusion()
        self._plan_tierup()
        ensure_jax_backend()
        import jax.numpy as jnp
        from jax import lax

        step = _make_step(self.img, self.cfg, self.lanes,
                          t0kinds=getattr(self, "_t0kinds", None))
        chunk = self.cfg.steps_per_launch

        def run_chunk(state, t0_time):
            # the obs planes (op_hist / fu_ctr) are carried and updated
            # by step() itself when allocated (obs_state_planes); a
            # None plane compiles the exact seed loop

            def cond(carry):
                i, s = carry
                return (i < chunk) & jnp.any(s.trap == 0)

            def body(carry):
                i, s = carry
                return i + 1, step(s, t0_time)

            i, state = lax.while_loop(cond, body, (jnp.int32(0), state))
            return i, state

        # the carry donated (`jit_in_place`): the big planes stay in place
        if self.mesh is not None:
            # single-program mesh drive: ONE jitted program over the
            # named mesh, lane planes sharded on the `lanes` axis — the
            # chunk body above runs per-shard unchanged
            from wasmedge_tpu.parallel.shard_drive import \
                _build_shard_chunk

            probe = self.initial_state(0, [])
            self._run_chunk = _build_shard_chunk(run_chunk, self.mesh,
                                                 probe)
        else:
            self._run_chunk = jit_in_place(run_chunk, 0)
        self._step = step

    def _build_narrow_chunk(self, width: int):
        """Chunk loop at a live-prefix dispatch width < lanes (lane
        compaction's narrowing rung, batch/compact.py): the step
        retraces at `width`, each launch slices the live prefix out of
        the full-width state, drives it, and writes it back in place.
        Lanes beyond the prefix are guaranteed dead (trap != 0 and
        never TRAP_HOSTCALL) by the compactor's sort, so skipping them
        cannot change any observable state; laneless obs planes
        (op_hist, fu_ctr) ride the narrow loop and replace the full
        state's copies wholesale.

        jit-purity lint target (tools/lint_jit_purity.py): everything
        nested here runs under trace.
        """
        from wasmedge_tpu.batch import ensure_jax_backend, jit_in_place

        ensure_jax_backend()
        import jax.numpy as jnp
        from jax import lax

        step = _make_step(self.img, self.cfg, width,
                          t0kinds=getattr(self, "_t0kinds", None))
        chunk = self.cfg.steps_per_launch
        lanes = self.lanes

        def run_chunk_narrow(state, t0_time):
            fields = {}
            lane_fields = []
            for name in state._fields:
                p = getattr(state, name)
                if p is None:
                    fields[name] = None
                elif p.ndim and p.shape[-1] == lanes:
                    lane_fields.append(name)
                    fields[name] = p[..., :width]
                else:
                    fields[name] = p
            ns = BatchState(**fields)

            def cond(carry):
                i, s = carry
                return (i < chunk) & jnp.any(s.trap == 0)

            def body(carry):
                i, s = carry
                return i + 1, step(s, t0_time)

            i, ns = lax.while_loop(cond, body, (jnp.int32(0), ns))
            updates = {}
            for name in state._fields:
                p = getattr(state, name)
                if p is None:
                    continue
                if name in lane_fields:
                    updates[name] = p.at[..., :width].set(
                        getattr(ns, name))
                else:
                    updates[name] = getattr(ns, name)
            return i, state._replace(**updates)

        return jit_in_place(run_chunk_narrow, 0)

    def initial_state(self, func_idx: int, args_lanes: List[np.ndarray]):
        import jax.numpy as jnp

        obs_conf = getattr(self.conf, "obs", None)
        if obs_conf is not None and obs_conf.enabled:
            # the fu_ctr/tu_ctr allocation decisions (obs_state_planes)
            # need the translation/promotion passes to have run; obs-off
            # states defer them to _build() with the rest of the step
            # compile
            self._plan_fusion()
            self._plan_tierup()
        cfg = self.cfg
        L = self.lanes
        img = self.img
        meta = self.inst.lowered.funcs[func_idx]
        D = cfg.value_stack_depth
        CD = cfg.call_stack_depth
        stack_lo, stack_hi = pack_lane_args(args_lanes, L, D)
        mem_words = max(img.mem_pages_max * _PAGE_WORDS, 1)
        mem = np.zeros((mem_words, L), np.int32)
        if img.mem_init.shape[0] > 1 or img.mem_pages_init:
            mem[: img.mem_init.shape[0]] = img.mem_init[:, None]
        fuel0 = cfg.fuel_per_launch if cfg.fuel_per_launch is not None else 0
        return BatchState(
            pc=jnp.full((L,), meta.entry_pc, jnp.int32),
            sp=jnp.full((L,), meta.nlocals + 0, jnp.int32),
            fp=jnp.zeros((L,), jnp.int32),
            opbase=jnp.full((L,), meta.nlocals, jnp.int32),
            call_depth=jnp.zeros((L,), jnp.int32),
            trap=jnp.zeros((L,), jnp.int32),
            retired=jnp.zeros((L,), jnp.int32),
            fuel=jnp.full((L,), fuel0, jnp.int32),
            mem_pages=jnp.full((L,), img.mem_pages_init, jnp.int32),
            stack_lo=jnp.asarray(stack_lo),
            stack_hi=jnp.asarray(stack_hi),
            fr_ret_pc=jnp.zeros((CD, L), jnp.int32),
            fr_fp=jnp.zeros((CD, L), jnp.int32),
            fr_opbase=jnp.zeros((CD, L), jnp.int32),
            glob_lo=jnp.asarray(np.repeat(img.globals_lo[:, None], L, axis=1)),
            glob_hi=jnp.asarray(np.repeat(img.globals_hi[:, None], L, axis=1)),
            mem=jnp.asarray(mem),
            stack_e2=jnp.zeros((D, L), jnp.int32) if img.has_simd else None,
            stack_e3=jnp.zeros((D, L), jnp.int32) if img.has_simd else None,
            **r05_state_planes(img, L),
            **t0_state_planes(img, cfg, L,
                              kinds=getattr(self, "_t0kinds", None)),
            **obs_state_planes(self.conf, img, mesh=self.mesh),
        )

    def run(self, func_name: str, args_lanes: List[np.ndarray],
            max_steps: int = 10_000_000) -> BatchResult:
        func_idx = self.export_func_idx(func_name)
        if self._run_chunk is None:
            self._build()
        self.hostcall_stats = new_hostcall_stats()
        # a fresh run is a fresh output stream: both cursor halves reset
        from wasmedge_tpu.batch.hostcall import stdout_cursor_reset

        stdout_cursor_reset(self)
        # divergence-aware lane compaction (batch/compact.py): fresh
        # identity mapping per cohort run; off = None = seed path
        from wasmedge_tpu.batch.compact import arm

        arm(self)
        state = self.initial_state(func_idx, args_lanes)
        if self.mesh is not None:
            from wasmedge_tpu.parallel.mesh import shard_batch_state

            state = shard_batch_state(state, self.mesh)
        state, total = self.run_from_state(state, 0, max_steps)
        nres = int(self.inst.lowered.funcs[func_idx].nresults)
        stack_lo = np.asarray(state.stack_lo)
        stack_hi = np.asarray(state.stack_hi)
        # compaction moved lanes: gather mirrors back to original order
        from wasmedge_tpu.batch.compact import restore_mirrors

        stack_lo, stack_hi, trap, retired = restore_mirrors(
            self.compactor, stack_lo, stack_hi,
            np.asarray(state.trap), np.asarray(state.retired))
        results = []
        for r in range(nres):
            lo = stack_lo[r].view(np.uint32).astype(np.uint64)
            hi = stack_hi[r].view(np.uint32).astype(np.uint64)
            results.append((lo | (hi << np.uint64(32))).view(np.int64))
        return BatchResult(
            results=results,
            trap=trap,
            retired=retired,
            steps=total,
        )

    def resolve_func(self, k: int):
        """Concatenated-image func index -> FunctionInstance (overridden by
        the multi-tenant engine, batch/multitenant.py)."""
        return self.inst.funcs[k]

    def export_func_idx(self, func_name: str) -> int:
        """Engine-global function index of an exported batch entry, with
        the shared entry guard (v128 params/results cannot ride the
        64-bit lane cells).  The serving layer's LaneRecycler resolves
        names through this seam so multi-module engines
        (batch/multitenant.py) can rebase qualified names onto the
        concatenated index space.  Raises KeyError for an unknown
        export, ValueError for a v128 signature."""
        return check_batch_entry(self.inst, func_name)

    def func_nresults(self, func_idx: int) -> int:
        """Result arity of an engine-global function index (the other
        half of the export_func_idx seam)."""
        return int(self.inst.lowered.funcs[func_idx].nresults)

    def run_from_state(self, state, total: int, max_steps: int):
        """Chunk loop from an arbitrary state (used directly and by the
        uniform/pallas engines\' divergence handoff), serving host
        outcalls between chunks (batch/hostcall.py)."""
        import jax.numpy as jnp

        from wasmedge_tpu.batch.hostcall import (
            flush_stdout_buffers, serve_batch_state)

        if self._run_chunk is None:
            self._build()
        t0_active = state.t0_ctr is not None
        if t0_active:
            ctr_in = np.asarray(state.t0_ctr, np.int64).sum(axis=1)
        dummy_time = np.zeros((2, 2), np.int32)
        # deterministic fault seam (testing/faults.py): the supervisor
        # arms this before a launch / a tier-1 serve so injected device
        # and host failures raise exactly where real ones would
        fault = getattr(self, "_fault_hook", None)
        # shadow-audit seam (wasmedge_tpu/integrity/audit.py, r24):
        # pre snapshots sampled lane columns before the launch donates
        # the state, post replays the slice and compares bit-exact —
        # a divergence raises out of this loop like a device failure
        auditor = getattr(self, "_audit_hook", None)
        # bit-flip seam (testing/faults.py BitFlip): corrupts the
        # landed state BEFORE the audit's post-slice gather, modelling
        # SDC the audit must catch rather than an error it is told of
        flip = getattr(self, "_flip_hook", None)
        # cooperative cancellation (parallel/supervisor.py): when a mesh
        # run is doomed, sibling devices stop at their next launch
        # boundary instead of driving the slice to completion
        cancel = getattr(self, "_cancel_hook", None)
        # per-device trace attribution for mesh drives (else "simt")
        track = getattr(self, "obs_track", "simt")
        # launch-boundary mirror seam (parallel/shard_drive.py): the
        # single-program mesh drive emits per-shard mesh_round spans
        # from the trap mirror this loop already gathers every round
        round_hook = getattr(self, "_round_hook", None)
        obs = self.obs
        # divergence-aware lane compaction (batch/compact.py): armed by
        # the cohort drivers only — a serving engine's compactor is
        # always None (the server remaps its own binding tables)
        comp = self.compactor
        if obs.enabled:
            prev_ret = int(np.asarray(state.retired, np.int64).sum())
        while total < max_steps:
            if cancel is not None and cancel():
                break
            if comp is not None:
                state = comp.boundary(self, state)
            # per-relaunch time base: host->device only, no round trip
            # (rides the launch as a non-donated argument)
            tt = jnp.asarray(t0_time_planes() if t0_active else dummy_time)
            audit_tok = auditor.pre(self, state, tt) \
                if auditor is not None else None
            if fault is not None:
                fault("launch", total=total)
            t_launch = obs.now()
            run_chunk = self._run_chunk if comp is None \
                else comp.chunk_fn(self)
            with obs.timed("simt/chunk", cat="engine") as chunk_span:
                done_steps, state = run_chunk(state, tt)
                done_steps = int(done_steps)    # waits for the chunk
                total += done_steps
                if flip is not None:
                    state = flip("corrupt_plane", state, lanes=self.lanes,
                                 total=total)
                if audit_tok is not None:
                    auditor.post(self, audit_tok, state, done_steps)
                if comp is not None:
                    comp.note_launch(done_steps)
                trap_host = np.asarray(state.trap)
                chunk_span.set(steps=done_steps)
            parked = int((trap_host == TRAP_HOSTCALL).sum())
            if round_hook is not None:
                round_hook(done_steps, trap_host, t_launch)
            if obs.enabled:
                # per-launch span with lane occupancy + retired delta
                # (one extra device read per LAUNCH, never per step)
                live = int((trap_host == 0).sum())
                ret = int(np.asarray(state.retired, np.int64).sum())
                obs.span("launch", t_launch, cat="engine", track=track,
                         steps=done_steps, live_lanes=live,
                         parked_lanes=parked,
                         retired_delta=ret - prev_ret)
                prev_ret = ret
                obs.counter("live_lanes", live)
                obs.counter("hostcall_queue_depth", parked)
                # per-round convergence metrics (ROADMAP #6a): unique
                # active pcs + largest convergent group among live
                # lanes, one extra [lanes] pc read per launch
                if live:
                    pcs = np.asarray(state.pc)[trap_host == 0]
                    _, counts = np.unique(pcs, return_counts=True)
                    obs.observe_convergence(
                        int(counts.size), float(counts.max()) / live)
            if parked:
                if fault is not None:
                    fault("serve", total=total)
                t_serve = obs.now()
                with obs.timed("simt/hostcalls", cat="engine",
                               lanes=parked):
                    state = serve_batch_state(self, state)
                obs.span("serve", t_serve, cat="engine", track=track,
                         lanes=parked)
                continue
            if not (trap_host == 0).any():
                break
            if done_steps == 0:
                break
        # Never leak the internal TRAP_HOSTCALL sentinel to callers: if the
        # step budget ran out with lanes parked at a stub, serve those
        # pending calls once — the lanes come back as trap == 0 ("still
        # running when max_steps ran out"), the documented semantic.
        parked_end = int((np.asarray(state.trap) == TRAP_HOSTCALL).sum())
        if parked_end:
            t_serve = obs.now()
            with obs.timed("simt/hostcalls", cat="engine",
                           lanes=parked_end):
                state = serve_batch_state(self, state)
            obs.span("serve", t_serve, cat="engine", track=track)
        state = flush_stdout_buffers(self, state)
        state = self._fold_op_hist(state)
        state = self._fold_fuse_ctr(state)
        state = self._fold_tierup_ctr(state)
        if t0_active:
            ctr = np.asarray(state.t0_ctr, np.int64).sum(axis=1) - ctr_in
            st_ = self.hostcall_stats
            st_["tier0_clock"] += int(ctr[0])
            st_["tier0_random"] += int(ctr[1])
            st_["tier0_fd_write"] += int(ctr[2])
            st_["tier0_sys"] += int(ctr[3])
            st_["tier0_calls"] += int(ctr.sum())
        return state, total

    def _fold_op_hist(self, state):
        """Fold + reset the device opcode-histogram plane: per-pc counts
        map through img.op_id into the Statistics cost_table opcode
        domain and land on the flight recorder (VM.execute_batch folds
        them onward into its Statistics)."""
        if getattr(state, "op_hist", None) is None:
            return state
        import jax.numpy as jnp

        from wasmedge_tpu.validator.image import NUM_LOPS

        pc_counts = np.asarray(state.op_hist, np.int64)
        if pc_counts.any():
            out = np.zeros(NUM_LOPS, np.int64)
            np.add.at(out, np.asarray(self.img.op_id, np.int64),
                      pc_counts)
            self.obs.add_opcode_counts(out)
            state = state._replace(op_hist=jnp.zeros_like(state.op_hist))
        return state

    def _fold_fuse_ctr(self, state):
        """Fold + reset the fusion counter plane ([dispatches,
        retired-through-fused-cells, total retired]) into the flight
        recorder; the Prometheus export renders the fused/unfused
        retired split from it (obs/metrics.py)."""
        if getattr(state, "fu_ctr", None) is None:
            return state
        import jax.numpy as jnp

        ctr = np.asarray(state.fu_ctr, np.int64)
        if ctr.any():
            self.obs.add_fused_counts(int(ctr[0]), int(ctr[1]),
                                      int(ctr[2]))
            state = state._replace(fu_ctr=jnp.zeros_like(state.fu_ctr))
        return state

    def _fold_tierup_ctr(self, state):
        """Fold + reset the tier-up counter plane ([compiled-body
        dispatches, retired-through-compiled-bodies, total retired])
        into the flight recorder; the Prometheus export renders the
        compiled/interpreted retired split from it (obs/metrics.py)."""
        if getattr(state, "tu_ctr", None) is None:
            return state
        import jax.numpy as jnp

        ctr = np.asarray(state.tu_ctr, np.int64)
        if ctr.any():
            self.obs.add_tierup_counts(int(ctr[0]), int(ctr[1]),
                                       int(ctr[2]))
            state = state._replace(tu_ctr=jnp.zeros_like(state.tu_ctr))
        return state
