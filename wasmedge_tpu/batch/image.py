"""DeviceImage: lowered module + instance snapshot -> device-resident tables.

The batch engine does not interpret the 180-op lowered ISA directly; at
image-build time every instruction is re-encoded as (class, sub, a, b, c,
imm_lo, imm_hi) where `class` selects one of ~20 vectorized SIMT handlers
and `sub` selects within a handler's fused select tree (e.g. ALU2 sub 0 =
i32.add). This is the two-level dispatch SURVEY.md §7 predicts the 439-op
switch must become to fit a TPU kernel.

`batchability()` is the feature gate: modules using ops outside the batch
subset (f64 arithmetic, i64<->f32 conversions, table mutation, bulk memory,
multi-value arities > 1, host calls) report a reason and fall back to the
scalar/native engine through the Configure seam — the same graceful
degradation the reference uses when an AOT section mismatches
(/root/reference/lib/loader/ast/module.cpp:279-326).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from wasmedge_tpu.common.errors import ErrCode
from wasmedge_tpu.common.opcodes import NAME_TO_ID, Op, name_of
from wasmedge_tpu.common.types import PAGE_SIZE
from wasmedge_tpu.validator.image import LOP_BR, LOP_BRNZ, LOP_BRZ, LoweredModule

# -- opcode classes ---------------------------------------------------------
CLS_NOP = 0
CLS_CONST = 1
CLS_LOCAL_GET = 2
CLS_LOCAL_SET = 3
CLS_LOCAL_TEE = 4
CLS_GLOBAL_GET = 5
CLS_GLOBAL_SET = 6
CLS_ALU1 = 7
CLS_ALU2 = 8
CLS_SELECT = 9
CLS_DROP = 10
CLS_BR = 11
CLS_BRZ = 12
CLS_BRNZ = 13
CLS_BR_TABLE = 14
CLS_RETURN = 15
CLS_CALL = 16
CLS_CALL_INDIRECT = 17
CLS_LOAD = 18
CLS_STORE = 19
CLS_MEMSIZE = 20
CLS_MEMGROW = 21
CLS_TRAP = 22
CLS_HOSTCALL = 23  # synthetic stub: park lane for the host outcall channel
CLS_MEMFILL = 24
CLS_MEMCOPY = 25
# v128 (4x int32 planes per cell; op tables in batch/simdops.py)
CLS_VCONST = 26    # a = v128 table idx -> push
CLS_V2 = 27        # sub = V2_SUB id: pop2 push1
CLS_V1 = 28        # sub = V1_SUB id: pop1 push1
CLS_VTEST = 29     # sub = VTEST_SUB id: pop v128 push i32
CLS_VSHIFT = 30    # sub = VSHIFT_SUB id: pop (v128, i32) push v128
CLS_VSPLAT = 31    # sub = VSPLAT_SUB id: pop scalar push v128
CLS_VEXTRACT = 32  # sub = VEXTRACT_SUB id, a = lane: pop v128 push scalar
CLS_VREPLACE = 33  # sub = VREPLACE_SUB id, a = lane: pop2 push v128
CLS_VSHUFFLE = 34  # a = v128 table idx (16-byte mask): pop2 push1.  The
#   mask is read at RUN time (simdops.vshuffle_dyn: the SIMT engine, the
#   Pallas kernel's own handler, a fused block with a byte-granular
#   mask) but where it moves whole 32-bit lanes and a fused block of the
#   Pallas kernel absorbs the op: pallas_engine.fuse_blocks reads it at
#   BUILD time and the block re-orders rows
CLS_VBITSEL = 35   # pop3 push1
CLS_VLOAD = 36     # a = offset: pop addr push v128
CLS_VSTORE = 37    # a = offset: pop (addr, v128)
# table / bulk-segment / tail-call families (r05).  The reference runs
# all of these inside its one dispatch loop
# (/root/reference/lib/executor/engine/engine.cpp:181-205 +
# lib/executor/engine/tableInstr.cpp); here they are SIMT handlers over
# a per-lane table plane and per-lane segment-dropped flags.  Device
# funcref domain: funcidx+1, 0 = null (same as table0).  c carries the
# lane's table base inside a concatenated multi-tenant plane, b the
# static table size (per-lane tsize plane overrides when table.grow is
# present).
CLS_TABLE_GET = 38   # pop idx, push ref
CLS_TABLE_SET = 39   # pop (idx, ref)
CLS_TABLE_SIZE = 40  # push size
CLS_TABLE_GROW = 41  # pop (init, delta), push old size | -1
CLS_TABLE_FILL = 42  # pop (i, ref, n)
CLS_TABLE_COPY = 43  # pop (dst, src, n)
CLS_TABLE_INIT = 44  # a = elem seg idx; pop (dst, src, n)
CLS_ELEM_DROP = 45   # a = elem seg idx
CLS_MEMINIT = 46     # a = data seg idx; pop (dst, src, n)
CLS_DATA_DROP = 47   # a = data seg idx
CLS_RETCALL = 48     # a = callee (tail call: frame replacement)
CLS_RETCALL_INDIRECT = 49  # a = dense type id, b = size, c = base
CLS_REFFUNC = 50     # a = funcidx: push device handle a+1 (rebasable)
NUM_CLASSES = 51

# -- ALU2 sub-ops (binary: pop2 push1) --------------------------------------
_I32_BIN = ["add", "sub", "mul", "div_s", "div_u", "rem_s", "rem_u", "and",
            "or", "xor", "shl", "shr_s", "shr_u", "rotl", "rotr",
            "eq", "ne", "lt_s", "lt_u", "gt_s", "gt_u", "le_s", "le_u",
            "ge_s", "ge_u"]
_F32_BIN = ["add", "sub", "mul", "div", "min", "max", "copysign",
            "eq", "ne", "lt", "gt", "le", "ge"]
_F64_BIN = list(_F32_BIN)  # same op set, softfloat binary64 kernels

ALU2_I32_BASE = 0
ALU2_I64_BASE = len(_I32_BIN)           # 25
ALU2_F32_BASE = 2 * len(_I32_BIN)       # 50
ALU2_F64_BASE = ALU2_F32_BASE + len(_F32_BIN)  # 63
NUM_ALU2 = ALU2_F64_BASE + len(_F64_BIN)  # 76

# i64 div/rem are "rare" subs: executed under an any-lane cond (64-iter loop)
RARE_ALU2_SUBS = tuple(ALU2_I64_BASE + _I32_BIN.index(n)
                       for n in ("div_s", "div_u", "rem_s", "rem_u"))

# -- ALU1 sub-ops (unary: pop1 push1) ---------------------------------------
_ALU1 = [
    "i32.clz", "i32.ctz", "i32.popcnt", "i32.eqz",
    "i32.extend8_s", "i32.extend16_s",
    "i64.clz", "i64.ctz", "i64.popcnt", "i64.eqz",
    "i64.extend8_s", "i64.extend16_s", "i64.extend32_s",
    "f32.abs", "f32.neg", "f32.ceil", "f32.floor", "f32.trunc",
    "f32.nearest", "f32.sqrt",
    "i32.wrap_i64", "i64.extend_i32_s", "i64.extend_i32_u",
    "i32.trunc_f32_s", "i32.trunc_f32_u",
    "i32.trunc_sat_f32_s", "i32.trunc_sat_f32_u",
    "f32.convert_i32_s", "f32.convert_i32_u",
    "i32.reinterpret_f32", "f32.reinterpret_i32",
    "ref.is_null",
    # binary64 (softfloat lo/hi-plane kernels, batch/softfloat.py)
    "f64.abs", "f64.neg", "f64.ceil", "f64.floor", "f64.trunc",
    "f64.nearest", "f64.sqrt",
    "f32.demote_f64", "f64.promote_f32",
    "i64.reinterpret_f64", "f64.reinterpret_i64",
    "f64.convert_i32_s", "f64.convert_i32_u",
    "f64.convert_i64_s", "f64.convert_i64_u",
    "f32.convert_i64_s", "f32.convert_i64_u",
    "i32.trunc_f64_s", "i32.trunc_f64_u",
    "i64.trunc_f32_s", "i64.trunc_f32_u",
    "i64.trunc_f64_s", "i64.trunc_f64_u",
    "i32.trunc_sat_f64_s", "i32.trunc_sat_f64_u",
    "i64.trunc_sat_f32_s", "i64.trunc_sat_f32_u",
    "i64.trunc_sat_f64_s", "i64.trunc_sat_f64_u",
]
ALU1_SUB = {n: i for i, n in enumerate(_ALU1)}
NUM_ALU1 = len(_ALU1)

# -- loads/stores -----------------------------------------------------------
_LOADS = {
    "i32.load": (4, 0, 0), "i64.load": (8, 0, 1), "f32.load": (4, 0, 0),
    "f64.load": (8, 0, 1),
    "i32.load8_s": (1, 1, 0), "i32.load8_u": (1, 0, 0),
    "i32.load16_s": (2, 1, 0), "i32.load16_u": (2, 0, 0),
    "i64.load8_s": (1, 1, 1), "i64.load8_u": (1, 0, 1),
    "i64.load16_s": (2, 1, 1), "i64.load16_u": (2, 0, 1),
    "i64.load32_s": (4, 1, 1), "i64.load32_u": (4, 0, 1),
}
_STORES = {
    "i32.store": 4, "i64.store": 8, "f32.store": 4, "f64.store": 8,
    "i32.store8": 1, "i32.store16": 2,
    "i64.store8": 1, "i64.store16": 2, "i64.store32": 4,
}

# Ops outside the batch subset. Modules containing them in *reachable
# batched code* fall back to the scalar engine.  The integer v128
# families are batchable (batch/simdops.py SUPPORTED_V128); the float
# families and the widening/narrowing extensions still gate out.
_UNSUPPORTED_PREFIXES = ("v128.", "i8x16.", "i16x8.", "i32x4.",
                         "i64x2.", "f32x4.", "f64x2.")

# Table ops address only table 0 on the batch engines (the reference's
# multi-table support exists, but multi-table modules fall back).
_TABLE0_OPS = {"table.get", "table.set", "table.size", "table.grow",
               "table.fill"}

TRAP_DONE = -1  # lane finished normally (trap plane sentinel)
TRAP_HOSTCALL = -2  # lane waiting on a host outcall
TRAP_PARKED = -3  # lane suspended on a blocking effect (effects/) —
#                   excluded from the runnable mask like any nonzero
#                   trap; the serving boundary swaps it out and frees
#                   the physical lane

# ---------------------------------------------------------------------------
# Tier-0 hostcalls: "pure" WASI imports the batch kernels can retire
# in-kernel (no device->host round trip).  The stub's t0kind plane entry
# names the call; the engine decides per-config whether to trace the
# in-kernel handler (batch/engine.py) or leave the stub parking as usual.
# ---------------------------------------------------------------------------
T0_NONE = 0
T0_CLOCK_TIME_GET = 1   # time from the per-relaunch time base + seq plane
T0_RANDOM_GET = 2       # counter-PRNG plane (deterministic under cfg seed)
T0_SCHED_YIELD = 3      # no-op, errno SUCCESS
T0_PROC_EXIT = 4        # lane terminates (ErrCode.Terminated, code on stack)
T0_FD_WRITE = 5         # fd 1/2 append into the in-device stdout record buf

T0_WASI_KINDS = {
    "clock_time_get": T0_CLOCK_TIME_GET,
    "random_get": T0_RANDOM_GET,
    "sched_yield": T0_SCHED_YIELD,
    "proc_exit": T0_PROC_EXIT,
    "fd_write": T0_FD_WRITE,
}

_WASI_MODULE = "wasi_snapshot_preview1"

# fd_write may only be serviced from the in-device stdout buffer when no
# other import can observe or mutate fd-table state mid-run (a guest that
# can close/renumber/seek fds would make the kernel's "fd 1/2 is a plain
# sink" assumption stale).  Anything in these families other than
# fd_write itself disables the fd_write tier-0 path for the module.
_T0_FD_UNSAFE_PREFIXES = ("fd_", "path_", "sock_", "poll_")

# Tier-0 kinds that write through guest linear memory — serviceable
# in-kernel only when the module has one (engine.t0_effective_kinds and
# the static analyzer share this set).
T0_NEEDS_MEMORY = (T0_CLOCK_TIME_GET, T0_RANDOM_GET, T0_FD_WRITE)


def classify_t0_imports(funcs) -> Tuple[dict, bool]:
    """Per-import tier-0 kind + module-level fd_write safety over a
    FuncMeta list: {func_idx: T0_*} and whether fd_write may buffer
    in-device.  The ONE source for the import-gating rules — consumed
    by build_device_image (t0kind plane, t0_fdwrite_safe) and the
    static analyzer (analysis/analyzer.py), so admission verdicts can
    never drift from what the engine services in-kernel."""
    kinds = {}
    fdwrite_safe = True
    for idx, fn in enumerate(funcs):
        if not fn.is_import:
            continue
        if fn.import_module == _WASI_MODULE:
            kinds[idx] = T0_WASI_KINDS.get(fn.import_name, T0_NONE)
            if fn.import_name != "fd_write" and fn.import_name.startswith(
                    _T0_FD_UNSAFE_PREFIXES):
                fdwrite_safe = False
        else:
            # non-WASI host imports can do anything — a custom import
            # observing output ordering would make in-device stdout
            # buffering visible; keep fd_write conservative.  The
            # "wasmedge" effect-handler module (effects/hostfuncs.py)
            # is OURS and fd-inert: await_event only touches its own
            # guest buffer, so it must not demote a module's stdout to
            # tier-1 — streaming and exactly-once stdout both ride the
            # tier-0 flush cursor
            kinds[idx] = T0_NONE
            if fn.import_module != "wasmedge":
                fdwrite_safe = False
    return kinds, fdwrite_safe




def _i32(v: int) -> np.int32:
    """Wrap an unsigned value into int32 two's complement."""
    v &= 0xFFFFFFFF
    return np.int32(v - (1 << 32) if v >= (1 << 31) else v)


def batchability(image: LoweredModule,
                 host_imports: Optional[set] = None,
                 n_memories: int = 1) -> Optional[str]:
    """None if the module image can run on the batch engine, else reason.

    host_imports: func indices backed by host functions the engine can
    serve through the outcall channel (batch/hostcall.py); imports outside
    it (e.g. cross-module wasm imports) stay unbatchable.
    n_memories: linear memories on the instance — the lane state carries
    exactly one mem plane, so multi-memory modules (MultiMemories
    proposal) fall back rather than silently addressing memory 0."""
    if n_memories > 1:
        return "multiple memories"
    for idx, fn in enumerate(image.funcs):
        if fn.is_import:
            if host_imports is None or idx not in host_imports:
                return (f"unservable imported function "
                        f"{fn.import_module}.{fn.import_name}")
        if fn.nresults > 1:
            return "multi-value results"
    for pc in range(image.code_len):
        op = image.op[pc]
        if op in (LOP_BR, LOP_BRZ, LOP_BRNZ):
            if image.b[pc] > 1:
                return "multi-value branch arity"
            continue
        name = name_of(op)
        if name == "br_table":
            base, n = image.a[pc], image.b[pc]
            for e in range(n + 1):
                if image.br_table[(base + e) * 3 + 1] > 1:
                    return "multi-value branch arity"
            continue
        if name == "return" and image.b[pc] > 1:
            return "multi-value results"
        if any(name.startswith(p) for p in _UNSUPPORTED_PREFIXES):
            from wasmedge_tpu.batch.simdops import SUPPORTED_V128

            if name not in SUPPORTED_V128:
                return f"unsupported op {name}"
        if name in _TABLE0_OPS and image.a[pc] != 0:
            return f"{name} on table != 0"
        if name == "table.copy" and (image.a[pc] != 0 or image.b[pc] != 0):
            return "table.copy on table != 0"
        if name == "table.init" and image.b[pc] != 0:
            return "table.init on table != 0"
        if name in ("call_indirect", "return_call_indirect") \
                and image.b[pc] != 0:
            return f"{name} on table != 0"
    return None


@dataclasses.dataclass
class DeviceImage:
    """Numpy-side image; the engine moves these to device once per module."""

    # per-pc planes [code_len]
    cls: np.ndarray
    sub: np.ndarray
    a: np.ndarray
    b: np.ndarray
    c: np.ndarray
    imm_lo: np.ndarray
    imm_hi: np.ndarray
    br_table: np.ndarray  # [n_entries, 3]
    # per-function planes [n_funcs]
    f_entry: np.ndarray
    f_nparams: np.ndarray
    f_nlocals: np.ndarray
    f_nresults: np.ndarray
    f_frame_top: np.ndarray  # nlocals + max_height: stack room a frame needs
    f_type: np.ndarray  # dense functype id for call_indirect checks
    # instance snapshot
    table0: np.ndarray  # [table_size] funcidx+1, 0=null
    globals_lo: np.ndarray
    globals_hi: np.ndarray
    mem_init: np.ndarray  # [mem_words] int32 initial memory content
    mem_pages_init: int
    mem_pages_max: int
    has_memory: bool
    max_local_zeros: int  # max (nlocals - nparams) over funcs
    code_len: int
    # v128 constant/shuffle-mask table as 4 int32 planes [n, 4]
    v128: np.ndarray = None
    has_simd: bool = False
    # passive/active segment snapshots for table.init / memory.init
    # (funcref domain funcidx+1; data packed little-endian into words)
    elem_flat: np.ndarray = None   # [sum lens] int32
    elem_off: np.ndarray = None    # [nseg] int32
    elem_len: np.ndarray = None    # [nseg] int32
    data_words: np.ndarray = None  # [ceil(bytes/4)] int32
    data_off: np.ndarray = None    # [ndseg] byte offsets
    data_len: np.ndarray = None    # [ndseg] byte lengths
    # original opcode id per pc (Statistics cost-table domain; stubs
    # and padding carry nop) — the per-opcode gas weights gather through
    # this plane (reference CostTab: include/common/statistics.h:85-98)
    op_id: np.ndarray = None
    table_max: int = 0             # declared table0 max (0 = none)
    table_cap: int = 0             # per-lane table plane rows (engine clamps)
    table_size_init: int = 0       # true initial size (table0 is pad>=1)
    has_table_mut: bool = False    # any set/grow/fill/copy/init
    has_table_grow: bool = False
    # tier-0 hostcall kind per pc (T0_* above; nonzero only at HOSTCALL
    # stubs of recognized pure WASI imports).  None = no tier-0 service
    # (e.g. multi-tenant concatenated images keep every call on the
    # per-tenant outcall channel).
    t0kind: np.ndarray = None
    # fd_write tier-0 is additionally gated on the module's import set —
    # see _T0_FD_UNSAFE_PREFIXES
    t0_fdwrite_safe: bool = False
    # --- superinstruction fusion planes (batch/fuse.py plan_fusion) ---
    # fuse_len[pc]: at the HEAD pc of a fused straight-line run, the
    # number of constituent ops (>= 2); 0 everywhere else.  The
    # original per-pc cells are NEVER overwritten — a lane whose pc
    # sits mid-run (residue handoff, hostcall re-arm, swap-in restore)
    # executes the original per-op stream until the next head, and a
    # lane without the fuel to retire the whole run steps through the
    # originals so gas exhaustion lands at the correct op.
    fuse_len: np.ndarray = None
    # fuse_pat[pc]: fused-cell pattern id at run heads, -1 elsewhere.
    fuse_pat: np.ndarray = None
    # Ordered pattern table: tuple of ((cls, sub), ...) per pattern id.
    fuse_patterns: tuple = None
    # Planner report: planned-vs-realized per analyzer candidate plus
    # the realized run list (head pc, len, pattern) — the analyze CLI
    # and the --fuse-smoke guard read it.  None = planning never ran.
    fusion_report: dict = None
    # Static-analysis thunk (wasmedge_tpu/analysis/), bound at build
    # time and evaluated on FIRST ACCESS of `.analysis` — run/serve
    # startups that never read the report never pay for it.  Advisory
    # metadata only: nothing in the execution path reads it
    # (analysis-off runs are bit-identical by construction); the
    # gateway admission policy and the superinstruction tier
    # (ROADMAP #3) are the consumers.
    analysis_builder: object = None

    @property
    def analysis(self):
        """ModuleAnalysis of the lowered module, built lazily and
        cached; None when no builder was bound (e.g. concatenated
        multi-tenant images) or the analyzer failed — admission
        policies treat None as a violation, never as a pass."""
        cached = self.__dict__.get("_analysis", _ANALYSIS_UNSET)
        if cached is _ANALYSIS_UNSET:
            cached = None
            if self.analysis_builder is not None:
                try:
                    cached = self.analysis_builder()
                except Exception:
                    cached = None
            self.__dict__["_analysis"] = cached
        return cached


_ANALYSIS_UNSET = object()


def build_device_image(image: LoweredModule, memories=None, globals_=None,
                       table0=None, mod=None, elem_segs=None,
                       data_segs=None) -> DeviceImage:
    # Imported (host) functions get a 2-instruction synthetic stub after
    # the module code: HOSTCALL (parks the lane; the host writes results
    # at the frame's operand base and re-arms at the next pc) followed by
    # RETURN.  f_entry points imports at their stub, so CALL needs no
    # special casing — the reference's 3-way enterFunction dispatch
    # (helper.cpp:35-97) becomes one extra opcode class.
    imports = [i for i, fn in enumerate(image.funcs) if fn.is_import]
    n = image.code_len + 2 * len(imports)
    cls = np.zeros(n, np.int32)
    sub = np.zeros(n, np.int32)
    a = np.zeros(n, np.int32)
    b = np.zeros(n, np.int32)
    c = np.zeros(n, np.int32)
    imm_lo = np.zeros(n, np.int32)
    imm_hi = np.zeros(n, np.int32)

    # Dense structural functype ids, shared by function table and
    # call_indirect immediates (typecheck is id equality on device).
    type_ids = {}

    def _dense_type(type_idx: int) -> int:
        key = type_idx
        if mod is not None:
            ft = mod.types[type_idx]
            key = (ft.params, ft.results)
        return type_ids.setdefault(key, len(type_ids))

    if table0 is None:
        table0 = np.zeros(1, np.int32)
    else:
        table0 = np.asarray(table0, np.int32)
    # call_indirect's bounds check uses the instruction's `b` (true size);
    # the array itself is padded so a declared-but-empty table still
    # yields a gatherable plane (the padding slot is null and unreachable)
    table_size = len(table0)
    if table_size == 0:
        table0 = np.zeros(1, np.int32)

    from wasmedge_tpu.batch.simdops import (
        V1_SUB, V2_SUB, VEXTRACT_SUB, VREPLACE_SUB, VSHIFT_SUB,
        VSPLAT_SUB, VTEST_SUB)

    v2_ops = {NAME_TO_ID[n]: s for n, s in V2_SUB.items()}
    v1_ops = {NAME_TO_ID[n]: s for n, s in V1_SUB.items()}
    vtest_ops = {NAME_TO_ID[n]: s for n, s in VTEST_SUB.items()}
    vshift_ops = {NAME_TO_ID[n]: s for n, s in VSHIFT_SUB.items()}
    vsplat_ops = {NAME_TO_ID[n]: s for n, s in VSPLAT_SUB.items()}
    vextract_ops = {NAME_TO_ID[n]: s for n, s in VEXTRACT_SUB.items()}
    vreplace_ops = {NAME_TO_ID[n]: s for n, s in VREPLACE_SUB.items()}
    op_vconst = NAME_TO_ID["v128.const"]
    op_vshuffle = NAME_TO_ID["i8x16.shuffle"]
    op_vbitsel = NAME_TO_ID["v128.bitselect"]
    op_vload = NAME_TO_ID["v128.load"]
    op_vstore = NAME_TO_ID["v128.store"]

    i32_bin = {NAME_TO_ID[f"i32.{s}"]: ALU2_I32_BASE + i
               for i, s in enumerate(_I32_BIN)}
    i64_bin = {NAME_TO_ID[f"i64.{s}"]: ALU2_I64_BASE + i
               for i, s in enumerate(_I32_BIN)}
    f32_bin = {NAME_TO_ID[f"f32.{s}"]: ALU2_F32_BASE + i
               for i, s in enumerate(_F32_BIN)}
    f64_bin = {NAME_TO_ID[f"f64.{s}"]: ALU2_F64_BASE + i
               for i, s in enumerate(_F64_BIN)}
    alu1 = {NAME_TO_ID[nm]: s for nm, s in ALU1_SUB.items()}
    loads = {NAME_TO_ID[nm]: v for nm, v in _LOADS.items()}
    stores = {NAME_TO_ID[nm]: v for nm, v in _STORES.items()}
    consts = {Op.i32_const, Op.i64_const, Op.f32_const, Op.f64_const}
    op_return = NAME_TO_ID["return"]

    op_id = np.full(n, int(Op.nop), np.int32)
    op_id[:image.code_len] = np.asarray(
        image.op[:image.code_len], np.int32)

    stub_pc = {}
    t0kind = np.zeros(n, np.int32)
    t0_kind_of, t0_fdwrite_safe = classify_t0_imports(image.funcs)
    for si, k in enumerate(imports):
        at = image.code_len + 2 * si
        stub_pc[k] = at
        cls[at] = CLS_HOSTCALL
        a[at] = k
        cls[at + 1] = CLS_RETURN
        b[at + 1] = image.funcs[k].nresults
        t0kind[at] = t0_kind_of.get(k, T0_NONE)

    for pc in range(image.code_len):
        op = image.op[pc]
        ia, ib, ic, imm = image.a[pc], image.b[pc], image.c[pc], image.imm[pc]
        if op == LOP_BR:
            cls[pc], a[pc], b[pc], c[pc] = CLS_BR, ia, ib, ic
        elif op == LOP_BRZ:
            cls[pc], a[pc] = CLS_BRZ, ia
        elif op == LOP_BRNZ:
            cls[pc], a[pc], b[pc], c[pc] = CLS_BRNZ, ia, ib, ic
        elif op == Op.br_table:
            cls[pc], a[pc], b[pc] = CLS_BR_TABLE, ia, ib
        elif op == op_return:
            cls[pc], b[pc] = CLS_RETURN, ib
        elif op == Op.call:
            cls[pc], a[pc] = CLS_CALL, ia
        elif op == Op.call_indirect:
            # a = dense type id, b = table size, c = table base offset —
            # base/size in the instruction keep multi-tenant concatenated
            # tables addressable per lane (batch/multitenant.py)
            cls[pc], a[pc] = CLS_CALL_INDIRECT, _dense_type(ia)
            b[pc] = table_size
            c[pc] = 0
        elif op in consts:
            cls[pc] = CLS_CONST
            imm_lo[pc] = _i32(imm)
            imm_hi[pc] = _i32(imm >> 32)
        elif op == Op.ref_null:
            cls[pc] = CLS_CONST
        elif op == Op.local_get:
            cls[pc], a[pc] = CLS_LOCAL_GET, ia
        elif op == Op.local_set:
            cls[pc], a[pc] = CLS_LOCAL_SET, ia
        elif op == Op.local_tee:
            cls[pc], a[pc] = CLS_LOCAL_TEE, ia
        elif op == Op.global_get:
            cls[pc], a[pc] = CLS_GLOBAL_GET, ia
        elif op == Op.global_set:
            cls[pc], a[pc] = CLS_GLOBAL_SET, ia
        elif op in i32_bin:
            cls[pc], sub[pc] = CLS_ALU2, i32_bin[op]
        elif op in i64_bin:
            cls[pc], sub[pc] = CLS_ALU2, i64_bin[op]
        elif op in f32_bin:
            cls[pc], sub[pc] = CLS_ALU2, f32_bin[op]
        elif op in f64_bin:
            cls[pc], sub[pc] = CLS_ALU2, f64_bin[op]
        elif op in alu1:
            cls[pc], sub[pc] = CLS_ALU1, alu1[op]
        elif op in loads:
            nbytes, signed, is64 = loads[op]
            cls[pc] = CLS_LOAD
            a[pc] = _i32(imm)  # static offset
            b[pc] = nbytes
            c[pc] = signed | (is64 << 1)
        elif op in stores:
            cls[pc] = CLS_STORE
            a[pc] = _i32(imm)
            b[pc] = stores[op]
        elif op == op_vconst:
            cls[pc], a[pc] = CLS_VCONST, ia
        elif op == op_vshuffle:
            cls[pc], a[pc] = CLS_VSHUFFLE, ia
        elif op == op_vbitsel:
            cls[pc] = CLS_VBITSEL
        elif op == op_vload:
            cls[pc], a[pc] = CLS_VLOAD, _i32(imm)
        elif op == op_vstore:
            cls[pc], a[pc] = CLS_VSTORE, _i32(imm)
        elif op in v2_ops:
            cls[pc], sub[pc] = CLS_V2, v2_ops[op]
        elif op in v1_ops:
            cls[pc], sub[pc] = CLS_V1, v1_ops[op]
        elif op in vtest_ops:
            cls[pc], sub[pc] = CLS_VTEST, vtest_ops[op]
        elif op in vshift_ops:
            cls[pc], sub[pc] = CLS_VSHIFT, vshift_ops[op]
        elif op in vsplat_ops:
            cls[pc], sub[pc] = CLS_VSPLAT, vsplat_ops[op]
        elif op in vextract_ops:
            cls[pc], sub[pc], a[pc] = CLS_VEXTRACT, vextract_ops[op], ia
        elif op in vreplace_ops:
            cls[pc], sub[pc], a[pc] = CLS_VREPLACE, vreplace_ops[op], ia
        elif op == Op.memory_fill:
            cls[pc] = CLS_MEMFILL
        elif op == Op.memory_copy:
            cls[pc] = CLS_MEMCOPY
        elif op == Op.table_get:
            cls[pc], b[pc] = CLS_TABLE_GET, table_size
        elif op == Op.table_set:
            cls[pc], b[pc] = CLS_TABLE_SET, table_size
        elif op == Op.table_size:
            cls[pc], b[pc] = CLS_TABLE_SIZE, table_size
        elif op == Op.table_grow:
            cls[pc], b[pc] = CLS_TABLE_GROW, table_size
        elif op == Op.table_fill:
            cls[pc], b[pc] = CLS_TABLE_FILL, table_size
        elif op == Op.table_copy:
            cls[pc], b[pc] = CLS_TABLE_COPY, table_size
        elif op == Op.table_init:
            cls[pc], a[pc], b[pc] = CLS_TABLE_INIT, ia, table_size
        elif op == Op.elem_drop:
            cls[pc], a[pc] = CLS_ELEM_DROP, ia
        elif op == Op.memory_init:
            cls[pc], a[pc] = CLS_MEMINIT, ia
        elif op == Op.data_drop:
            cls[pc], a[pc] = CLS_DATA_DROP, ia
        elif op == Op.ref_func:
            # device funcref domain: funcidx+1 (matches table0 cells).
            # Own class (not CLS_CONST) so multi-tenant concatenation can
            # rebase the function index (multitenant.py concat_images)
            cls[pc], a[pc] = CLS_REFFUNC, ia
        elif op == Op.return_call:
            cls[pc], a[pc] = CLS_RETCALL, ia
        elif op == Op.return_call_indirect:
            cls[pc], a[pc] = CLS_RETCALL_INDIRECT, _dense_type(ia)
            b[pc] = table_size
            c[pc] = 0
        elif op == Op.memory_size:
            cls[pc] = CLS_MEMSIZE
        elif op == Op.memory_grow:
            cls[pc] = CLS_MEMGROW
        elif op == Op.select:
            cls[pc] = CLS_SELECT
        elif op == Op.drop:
            cls[pc] = CLS_DROP
        elif op == Op.nop:
            cls[pc] = CLS_NOP
        elif op == Op.unreachable:
            cls[pc], a[pc] = CLS_TRAP, int(ErrCode.Unreachable)
        else:
            # batchability() should have rejected; encode a trap as backstop
            cls[pc], a[pc] = CLS_TRAP, int(ErrCode.ExecutionFailed)

    nf = len(image.funcs)
    f_entry = np.zeros(nf, np.int32)
    f_nparams = np.zeros(nf, np.int32)
    f_nlocals = np.zeros(nf, np.int32)
    f_nresults = np.zeros(nf, np.int32)
    f_frame_top = np.zeros(nf, np.int32)
    f_type = np.zeros(nf, np.int32)
    max_zeros = 0
    for i, fn in enumerate(image.funcs):
        if fn.is_import:
            f_entry[i] = stub_pc[i]
            f_nparams[i] = fn.nparams
            f_nlocals[i] = fn.nparams
            f_nresults[i] = fn.nresults
            f_frame_top[i] = fn.nparams + max(fn.nresults, 1)
            f_type[i] = _dense_type(fn.type_idx)
            continue
        f_entry[i] = fn.entry_pc
        f_nparams[i] = fn.nparams
        f_nlocals[i] = fn.nlocals
        f_nresults[i] = fn.nresults
        f_frame_top[i] = fn.nlocals + fn.max_height
        f_type[i] = _dense_type(fn.type_idx)
        max_zeros = max(max_zeros, fn.nlocals - fn.nparams)

    # instance snapshots (table0: [size] of funcidx+1, 0 = null)
    ng = len(globals_) if globals_ else 0
    g_lo = np.zeros(max(ng, 1), np.int32)
    g_hi = np.zeros(max(ng, 1), np.int32)
    for i in range(ng):
        v = globals_[i].value
        g_lo[i] = _i32(v)
        g_hi[i] = _i32(v >> 32)

    if memories:
        m = memories[0]
        raw = np.frombuffer(bytes(m.data), dtype=np.uint8)
        pad = (-len(raw)) % 4
        if pad:
            raw = np.concatenate([raw, np.zeros(pad, np.uint8)])
        mem_init = raw.view(np.int32).astype(np.int32)
        pages_init = m.pages
        pages_max = m.max if m.max is not None else 0  # 0 = no declared max
    else:
        mem_init = np.zeros(1, np.int32)
        pages_init = 0
        pages_max = 0

    v_lo = image.arrays["v128_lo"]
    v_hi = image.arrays["v128_hi"]
    v128 = np.zeros((max(len(v_lo), 1), 4), np.int32)
    for i in range(len(v_lo)):
        v128[i, 0] = _i32(int(v_lo[i]))
        v128[i, 1] = _i32(int(v_lo[i]) >> 32)
        v128[i, 2] = _i32(int(v_hi[i]))
        v128[i, 3] = _i32(int(v_hi[i]) >> 32)
    has_simd = bool(((cls >= CLS_VCONST) & (cls <= CLS_VSTORE)).any())

    # segment snapshots (table.init / memory.init sources; per-lane
    # dropped flags live in engine state, not here)
    esegs = elem_segs or []
    elem_off = np.zeros(max(len(esegs), 1), np.int32)
    elem_len = np.zeros(max(len(esegs), 1), np.int32)
    eflat: list = []
    for i, seg in enumerate(esegs):
        elem_off[i] = len(eflat)
        elem_len[i] = len(seg)
        eflat.extend(int(x) for x in seg)
    elem_flat = np.asarray(eflat or [0], np.int32)
    dsegs = data_segs or []
    data_off = np.zeros(max(len(dsegs), 1), np.int32)
    data_len = np.zeros(max(len(dsegs), 1), np.int32)
    dbytes = bytearray()
    for i, seg in enumerate(dsegs):
        data_off[i] = len(dbytes)
        data_len[i] = len(seg)
        dbytes.extend(seg)
    while len(dbytes) % 4:
        dbytes.append(0)
    data_words = (np.frombuffer(bytes(dbytes), np.uint8).view(np.int32)
                  .astype(np.int32) if dbytes else np.zeros(1, np.int32))

    table_max = 0
    if mod is not None and getattr(mod, "tables", None):
        lim = mod.tables[0].limit
        table_max = lim.max if lim.max is not None else 0
    _TMUT = (CLS_TABLE_SET, CLS_TABLE_GROW, CLS_TABLE_FILL,
             CLS_TABLE_COPY, CLS_TABLE_INIT)
    has_table_mut = bool(np.isin(cls, _TMUT).any())
    has_table_grow = bool((cls == CLS_TABLE_GROW).any())

    # Static analysis rides the image (same lowering the batchability
    # probe used — the gateway never analyzes from scratch), bound as
    # a thunk the `.analysis` property evaluates on first access: a
    # run/serve that never reads the report never pays for it.  The
    # declared (pre-knob-clamp) page values are captured HERE — the
    # engine mutates img.mem_pages_max afterwards and footprint policy
    # must judge what the module declares, not one host's clamp.
    exports = None
    if mod is not None:
        exports = {e.name: e.index for e in mod.exports if e.kind == 0}

    def _analysis_builder(_image=image, _exports=exports,
                          _init=pages_init, _max=pages_max,
                          _has_mem=bool(memories),
                          _globals=[int(g.value) for g in (globals_ or ())]
                          or None):
        from wasmedge_tpu.analysis import analyze_module

        return analyze_module(_image, exports=_exports,
                              mem_pages_init=_init, mem_pages_max=_max,
                              has_memory=_has_mem,
                              globals_init=_globals)

    return DeviceImage(
        cls=cls, sub=sub, a=a, b=b, c=c, imm_lo=imm_lo, imm_hi=imm_hi,
        br_table=image.arrays["br_table"],
        f_entry=f_entry, f_nparams=f_nparams, f_nlocals=f_nlocals,
        f_nresults=f_nresults, f_frame_top=f_frame_top, f_type=f_type,
        table0=table0, globals_lo=g_lo, globals_hi=g_hi,
        mem_init=mem_init, mem_pages_init=pages_init, mem_pages_max=pages_max,
        has_memory=bool(memories),
        max_local_zeros=max_zeros, code_len=n,
        v128=v128, has_simd=has_simd,
        elem_flat=elem_flat, elem_off=elem_off, elem_len=elem_len,
        data_words=data_words, data_off=data_off, data_len=data_len,
        op_id=op_id,
        table_max=table_max, table_cap=len(table0),
        table_size_init=table_size,
        has_table_mut=has_table_mut, has_table_grow=has_table_grow,
        t0kind=t0kind, t0_fdwrite_safe=t0_fdwrite_safe,
        analysis_builder=_analysis_builder,
    )


def image_fingerprint(img: DeviceImage) -> str:
    """Content fingerprint of a DeviceImage's static planes (sha256 over
    the code/function/snapshot arrays plus the fusion/tier attributes).

    The imagestore segment cache (wasmedge_tpu/imagestore/segments.py)
    keys memoized concat segments on this: two engines lowered from
    identical bytes under identical knobs fingerprint identically, and
    a re-planned image (fusion/tierup planes bound later) fingerprints
    differently — a stale segment can never alias a changed image.
    Cached on the instance: the planes are frozen after normalization,
    so one hash per image covers every later generation build."""
    import hashlib

    cached = getattr(img, "_fingerprint", None)
    if cached is not None:
        return cached
    h = hashlib.sha256()
    for name in ("cls", "sub", "a", "b", "c", "imm_lo", "imm_hi",
                 "op_id", "br_table", "f_entry", "f_nparams",
                 "f_nlocals", "f_nresults", "f_frame_top", "f_type",
                 "table0", "globals_lo", "globals_hi", "mem_init",
                 "v128", "elem_flat", "elem_off", "elem_len",
                 "data_words", "data_off", "data_len", "fuse_len",
                 "fuse_pat", "tier_fn", "tier_fuel_bound"):
        arr = getattr(img, name, None)
        h.update(name.encode())
        if arr is None:
            h.update(b"\x00")
            continue
        arr = np.ascontiguousarray(arr)
        h.update(str(arr.dtype).encode() + str(arr.shape).encode())
        h.update(arr.tobytes())
    for scalar in (img.mem_pages_init, img.mem_pages_max,
                   int(img.has_memory), img.max_local_zeros,
                   img.code_len, int(img.has_simd), img.table_cap,
                   img.table_size_init,
                   int(getattr(img, "has_table_mut", False)),
                   int(getattr(img, "has_table_grow", False)),
                   len(getattr(img, "fuse_patterns", None) or ()),
                   len(getattr(img, "tier_fns", None) or ())):
        h.update(str(int(scalar)).encode() + b",")
    for key in getattr(img, "fuse_patterns", None) or ():
        h.update(repr(key).encode())
    fp = h.hexdigest()
    try:
        img._fingerprint = fp
    except Exception:
        pass  # frozen dataclass variants: recompute per call
    return fp
