"""Pallas warp-interpreter: the on-device Wasm dispatch loop.

This is the engine SURVEY.md §7 step 4 calls the north star: the moral
equivalent of the reference's `while (PC != PCEnd) switch (opcode)` hot loop
(/root/reference/lib/executor/engine/engine.cpp:68-1641), rebuilt as a TPU
kernel.  The whole fetch→decode→execute loop runs *inside one Pallas kernel
launch*: code tables live in SMEM (scalar memory), lane state (value stacks,
globals, linear memory, trap plane) lives in VMEM refs that handlers mutate
in place, and control state (pc/sp/fp/...) is a scalar `lax.while_loop`
carry.  One launch retires up to `steps_per_launch` instructions for every
lane with zero host round-trips, which is what removes the ~400µs/step
dispatch overhead the pure-XLA engines pay (every XLA step re-threads
multi-MB state through a conditional).

Execution model (same as batch/uniform.py): lanes are *converged* within a
lane block — pc/sp/fp/call_depth are block-uniform scalars; per-lane data
diverges freely.  The lane axis is tiled into grid blocks so that large
per-lane linear memories still fit VMEM (e.g. 64 KiB/lane × 128 lanes);
different blocks may take different control paths (each grid program runs
its own dispatch loop).  A data-dependent branch (or per-lane trap or
memory fault) that disagrees *within* a block stops that block with
status=DIVERGED and the host hands the whole batch to the general SIMT
engine (batch/engine.py).  Handlers that bail on divergence do so *before*
any ref mutation, so the handed-over state re-executes the divergent
instruction exactly like uniform.py's functional rewind.

Memory: per-lane linear memory is a word-major [W, lanes] VMEM ref.  Loads
and stores take a *uniform-address fast path* (row dynamic-slice — converged
code almost always computes identical addresses in every lane) and, when the
memory is small enough, fall back to a masked compare-reduce gather/scatter
over the whole [W, block] array for divergent addresses.

Dispatch is a binary tree of `lax.cond` over *densely renumbered* handler
ids (Mosaic lowers `lax.switch` to a linear if-chain): only the handlers a
module actually uses are compiled into its kernel, so small modules get
small, fast-compiling kernels.  The time of a dispatch is mostly the scalar
skeleton around a few dozen vector operations, and every scf.if region it
walks, taken or not, costs the scalar core 6-9 ns on a v5e (measured by
PR 27 on fib(30) x 4096 lanes: 11 fewer regions in a leaf + inner call
pair saved 103 ns, the 18 of the per-dispatch commit check with its SMEM
reads 107 ns, both together 247 ns of 771; PERF.md section 5).  So the
tree is planned (plan_dispatch_tree) from ENTRY-SLOT weights: a handler
weighs the number of slots at which a converged dispatch can start with
its id (block heads, unfused slots, jump targets), dense ids are numbered
hot-first, and the handlers that only a resume can reach hang in one cold
subtree.  The optimistic kernel's periodic commit is one more leaf of
that subtree, taken by the loop's next iteration when `steps` says it is
due, so a dispatch pays one scalar compare for it and no region.  Kernels
are cached by (used-handler order, state geometry); modules sharing both
share a compile.
"""

from __future__ import annotations

import collections
import functools
from typing import List, NamedTuple, Optional

import numpy as np

from wasmedge_tpu.common.errors import ErrCode
from wasmedge_tpu.batch.image import (
    ALU1_SUB,
    ALU2_F64_BASE,
    ALU2_I32_BASE,
    ALU2_I64_BASE,
    NUM_ALU1,
    NUM_ALU2,
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
    CLS_GLOBAL_SET,
    CLS_HOSTCALL,
    CLS_LOAD,
    CLS_LOCAL_GET,
    CLS_LOCAL_SET,
    CLS_LOCAL_TEE,
    CLS_MEMCOPY,
    CLS_MEMFILL,
    CLS_MEMGROW,
    CLS_MEMSIZE,
    CLS_NOP,
    CLS_RETURN,
    CLS_SELECT,
    CLS_STORE,
    CLS_TRAP,
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
    DeviceImage,
    _I32_BIN,
)

# ---------------------------------------------------------------------------
# Flat handler-id space (before per-module dense renumbering)
# ---------------------------------------------------------------------------
H_NOP = 0
H_CONST = 1
H_LOCAL_GET = 2
H_LOCAL_SET = 3
H_LOCAL_TEE = 4
H_GLOBAL_GET = 5
H_GLOBAL_SET = 6
H_DROP = 7
H_SELECT = 8
H_BR = 9
H_BRZ = 10
H_BRNZ = 11
H_BR_TABLE = 12
H_RETURN = 13
H_CALL = 14
H_CALL_INDIRECT = 15
H_MEMSIZE = 16
H_MEMGROW = 17
H_TRAP = 18
H_LOAD = 19
H_STORE = 20
H_HOSTCALL = 21
H_MEMFILL = 22
H_MEMCOPY = 23
H_ALU2_BASE = 24                      # + ALU2 sub id
H_ALU1_BASE = H_ALU2_BASE + NUM_ALU2  # + ALU1 sub id
# width-specialized memory ops: plain 32/64-bit loads/stores skip the
# sub-word sign/width machinery — the hot shapes in compiled code
H_LOAD_W = H_ALU1_BASE + NUM_ALU1   # i32.load  (nbytes=4, no extension)
H_LOAD_D = H_LOAD_W + 1             # i64.load  (nbytes=8)
H_STORE_W = H_LOAD_D + 1            # i32.store / f32.store
H_STORE_D = H_STORE_W + 1           # i64.store / f64.store
# v128: cells are 4 int32 planes (lo, hi, e2, e3); op semantics come
# from batch/simdops.py — the same fns the SIMT engine dispatches
# (engine.py "v128 (SIMD)" section), here as per-sub handlers.  Dense
# renumbering means a module compiles only the subs it uses.
H_VCONST = H_STORE_D + 1
H_VSHUFFLE = H_VCONST + 1
H_VBITSEL = H_VSHUFFLE + 1
H_VLOAD = H_VBITSEL + 1
H_VSTORE = H_VLOAD + 1
from wasmedge_tpu.batch.simdops import (   # noqa: E402
    V1_NAMES,
    V2_NAMES,
    VEXTRACT_NAMES,
    VREPLACE_NAMES,
    VSHIFT_NAMES,
    VSPLAT_NAMES,
    VTEST_NAMES,
)
H_V2_BASE = H_VSTORE + 1
H_V1_BASE = H_V2_BASE + len(V2_NAMES)
H_VTEST_BASE = H_V1_BASE + len(V1_NAMES)
H_VSHIFT_BASE = H_VTEST_BASE + len(VTEST_NAMES)
H_VSPLAT_BASE = H_VSHIFT_BASE + len(VSHIFT_NAMES)
H_VEXTRACT_BASE = H_VSPLAT_BASE + len(VSPLAT_NAMES)
H_VREPLACE_BASE = H_VEXTRACT_BASE + len(VEXTRACT_NAMES)
NUM_HANDLERS = H_VREPLACE_BASE + len(VREPLACE_NAMES)

_CLS_TO_HID = {
    CLS_NOP: H_NOP, CLS_CONST: H_CONST, CLS_LOCAL_GET: H_LOCAL_GET,
    CLS_LOCAL_SET: H_LOCAL_SET, CLS_LOCAL_TEE: H_LOCAL_TEE,
    CLS_GLOBAL_GET: H_GLOBAL_GET, CLS_GLOBAL_SET: H_GLOBAL_SET,
    CLS_DROP: H_DROP, CLS_SELECT: H_SELECT, CLS_BR: H_BR, CLS_BRZ: H_BRZ,
    CLS_BRNZ: H_BRNZ, CLS_BR_TABLE: H_BR_TABLE, CLS_RETURN: H_RETURN,
    CLS_CALL: H_CALL, CLS_CALL_INDIRECT: H_CALL_INDIRECT,
    CLS_MEMSIZE: H_MEMSIZE, CLS_MEMGROW: H_MEMGROW, CLS_TRAP: H_TRAP,
    CLS_LOAD: H_LOAD, CLS_STORE: H_STORE, CLS_HOSTCALL: H_HOSTCALL,
    CLS_MEMFILL: H_MEMFILL, CLS_MEMCOPY: H_MEMCOPY,
    CLS_VCONST: H_VCONST, CLS_VSHUFFLE: H_VSHUFFLE,
    CLS_VBITSEL: H_VBITSEL, CLS_VLOAD: H_VLOAD, CLS_VSTORE: H_VSTORE,
}

# sub-indexed v128 classes -> handler base id
_VCLS_TO_BASE = {
    CLS_V2: H_V2_BASE, CLS_V1: H_V1_BASE, CLS_VTEST: H_VTEST_BASE,
    CLS_VSHIFT: H_VSHIFT_BASE, CLS_VSPLAT: H_VSPLAT_BASE,
    CLS_VEXTRACT: H_VEXTRACT_BASE, CLS_VREPLACE: H_VREPLACE_BASE,
}

# status values (shared with batch/uniform.py)
ST_RUNNING = 0
ST_DONE = 1
ST_DIVERGED = 2
ST_HOSTCALL = 3  # block parked at a host outcall stub
# memory.grow needs more rows than the watermark-sized plane holds: the
# grow is legal (<= declared max) but the kernel geometry is too small.
# The block stops un-advanced; the host re-executes on an engine with a
# bigger plane (SIMT today; a re-geometried kernel when the scheduler
# learns to migrate).  Watermark sizing is SURVEY §5.7's design: the
# plane covers *current* pages, not the declared max, so a module that
# declares max=16 pages but touches one page keeps a VMEM-sized state.
ST_REGROW = 4
# optimistic-convergence rollback: the block was rewound to its last
# validated snapshot; the driver re-runs it on the careful kernel
ST_RECHECK = 5
ST_TRAPPED_BASE = 16

_PAGE_WORDS = 65536 // 4
_FUEL_OFF = 0x7FFFFFFF  # fuel column value when gas metering is disabled

# ctrl row layout (SMEM, int32[nblk, ctrl_width(simd, indirect)]: 16
# columns, one more for an image with v128 and one more for an image with
# br_table or call_indirect)
_C_PC, _C_SP, _C_FP, _C_OB, _C_CD, _C_STATUS, _C_PAGES, _C_CHUNK = range(8)
_C_STEPS = 8
_C_FUEL = 9
# per-block optimistic snapshot interval (adaptive: the host halves it
# when a block rolls back — bounding the run-up a divergent block
# discards — and doubles it back toward SNAP_STEPS on clean launches;
# batch/scheduler.py `_SnapPolicy`).  0 means "use the kernel's
# build-time snap_steps".
_C_SNAP = 10
# written by the mem_hbm kernel at exit, per launch (never read by it):
# window fills and dirty-window write-backs, each one CW-row DMA
_C_WFILLS = 11
_C_WWBS = 12
# written by every kernel at exit, per launch (never read by it): the
# handlers the loop dispatched, those a rollback discarded too (a commit
# is none: it retires nothing)
_C_DISPATCHES = 13
# written by the mem_hbm kernel at exit, per launch (never read by it):
# the loads and stores it resolved against the window, hits and misses
_C_WACCESSES = 14
# written at exit, per launch (never read), by a kernel whose image holds
# a binary64 ALU op: the softfloat routines its handlers ran, counted
# along the path taken as _C_STEPS is, those a rollback discarded too
_C_SOFTFLOAT = 15
# written at exit, per launch (never read), by a kernel whose image has
# v128: the instructions of a v128 class (CLS_VCONST .. CLS_VSTORE) its
# handlers and fused blocks ran, counted along the path taken as
# _C_STEPS is, those a rollback discarded too.  The row is full at 16,
# so it is one column wider for such an image and for no other.
_C_SIMD = 16
_CTRL_W = 16


def ctrl_width(simd: bool, indirect: bool = False) -> int:
    """Columns of a ctrl row: what the kernel's ctrl output, the pass
    record and the hosts' rows are all sized by.  16, and one more for
    each counter the image asks for: v128 (_C_SIMD), then br_table and
    call_indirect (`indirect_column`)."""
    return _CTRL_W + int(simd) + int(indirect)


def indirect_column(simd: bool) -> int:
    """The ctrl column written at exit, per launch (never read), by a
    kernel whose image holds a br_table or a call_indirect
    (`holds_indirect`): the two it ran, at their handlers and as a fused
    block's terminal, counted along the path taken as _C_STEPS is, those
    a rollback discarded too.  The row's last: 16, or 17 after _C_SIMD."""
    return _CTRL_W + int(simd)


def decode_result_rows(stack_lo: np.ndarray, stack_hi: np.ndarray,
                       nres: int):
    """Reassemble 64-bit result cells from the lo/hi int32 planes."""
    results = []
    for r in range(nres):
        lo = stack_lo[r].view(np.uint32).astype(np.uint64)
        hi = stack_hi[r].view(np.uint32).astype(np.uint64)
        results.append((lo | (hi << np.uint64(32))).view(np.int64))
    return results


def _jump_targets(img) -> set:
    """Every pc a jump can land on: function entries, br/br_if targets
    and br_table entries.  Fusion never absorbs past one of these."""
    targets = set(int(x) for x in img.f_entry)
    for pc in range(img.code_len):
        cl = int(img.cls[pc])
        if cl in (CLS_BR, CLS_BRZ, CLS_BRNZ):
            targets.add(int(img.a[pc]))
    for e in range(img.br_table.shape[0]):
        targets.add(int(img.br_table[e, 0]))
    return targets


# ---------------------------------------------------------------------------
# Basic-block fusion
# ---------------------------------------------------------------------------
# Every maximal straight-line run of *pure* stack ops (const, local/
# global traffic, drop/select, non-trapping alu) fuses into ONE handler
# that keeps intermediate values in vector registers — dispatch cost
# (128 ns a dispatch on a v5e before PR 27 and 87 ns after, fib(30):
# the lax.cond tree walk plus the VMEM dependency chain between
# consecutive stack ops) is paid once per block instead of once per
# instruction.  Any non-pure op (branch,
# call, return, load/store, div/rem, memory.*, hostcall) is absorbed as
# the block's TERMINAL: the handler flushes its virtual stack to the
# VMEM rows the op expects and delegates to the op's ORIGINAL handler,
# so branch/trap/park/divergence semantics are reused verbatim.
#
# Only the head slot's hid is rewritten; absorbed slots keep their
# original hids and operand fields, so any pc remains independently
# dispatchable — mid-block branch targets, SIMT-handoff resumptions and
# hostcall re-arms execute the original per-op stream until the next
# block head (every jump target starts a fresh block, so hot loop
# bodies always re-enter fused).  A terminal that stops un-advanced
# (divergence, regrow) leaves pc at the terminal's own slot where the
# scheduler sees the ORIGINAL opcode and resolves it with the existing
# split machinery.  This mirrors what the reference's threaded
# interpreter gets from its compiler for free: straight-line runs with
# values in registers (/root/reference/lib/executor/engine/
# engine.cpp:68-1641).
#
# SUPERBLOCKS (PR 29): a block does not end at a forward edge whose
# target is static.  It runs through a forward `br` into the target's
# ops, and the taken side of a forward guard runs the target's block as
# the guard's tail, so fib pays one dispatch a leaf call and three an
# inner call where it paid two and four.  The target's ops are
# DUPLICATED into the block: the target keeps its own head and hid for
# every other way in, and every op still reads its operands at its own
# original slot, so a bail, a rollback or a split child lands where it
# always did.  fuse_blocks holds the rules.
H_BLOCK_BASE = NUM_HANDLERS
# distinct block shapes compiled per kernel: in code order where the
# image's heads want no more, else the shapes that save the most
# dispatches in the deepest loops (fuse_blocks, _hot_shapes)
MAX_BLOCK_SHAPES = 96
MAX_BLOCK_LEN = 24      # ops per block (incl. the terminal)
# loads and stores fused inline into a kernel's blocks, all shapes
# together: each compiles its own fast path and miss path into its
# block (behind the HBM window, some 100 to 200 KB of a v5e program
# each), and past the chip's overlay cliff (tests/test_chip_compile.py)
# the dispatch loop runs across overlays.  An image whose blocks would
# fuse more keeps every load and store on a dispatch of its own
# (CoreMark: 109 inline, 20.0 MB of program; none inline, 2.4 MB)
MAX_INLINE_ACCESSES = 32


def _trapping_alu1_subs():
    from wasmedge_tpu.batch import laneops as lo_ops

    return set(lo_ops.alu1_trap_fns().keys())


# Terminals a superblock may run in code it DUPLICATED (after a followed
# `br`, or in a guard's tail).  Their *_with cores open one region, the
# call two where a callee has locals to zero; everything else (memory.*,
# div/rem, hostcalls, br_table, call_indirect) keeps its own dispatch,
# where it was on every kernel before superblocks, so duplicated code
# never holds a deep handler.
_DUP_TERMS = (H_BR, H_BRZ, H_BRNZ, H_RETURN, H_CALL)


def _path_nesting(ops, call_nest, depth=0):
    """Estimate of the nested regions (scf.if) the optimistic kernel
    opens to run `ops`, a path of a block shape entered `depth` regions
    down: every guard and every inline load/store puts what follows it
    one region deeper, and a terminal opens its own.  (The careful
    kernel puts a guard's taken side one deeper still, and the hbm
    window a load's continuation; the estimate is one function for
    every kernel kind, so the plane stays a function of the image
    alone.)  `call_nest` is what a `call` terminal opens: 2 where a
    callee has locals to zero, else 1."""
    deepest = depth
    for op in ops:
        kind = op[0]
        if kind in ("guardz", "guardnz"):
            depth += 1
            deepest = max(deepest, _path_nesting(op[1], call_nest, depth))
        elif kind in ("loadi", "storei"):
            depth += 1
        elif kind == "term":
            own = {H_BRZ: 0, H_CALL: call_nest}.get(op[1], 1)
            deepest = max(deepest, depth + own)
        deepest = max(deepest, depth)
    return deepest


def word_selectors(mask):
    """(s0, s1, s2, s3) if the `i8x16.shuffle` mask `mask` (four int32
    words, sixteen selector bytes) moves whole 32-bit lanes: output word
    k is source word s_k of the eight of both operands; else None."""
    sel = np.asarray(mask, np.int32).view(np.uint8).reshape(4, 4)
    words = sel[:, 0].astype(np.int32) // 4
    if np.any(words >= 8) or \
            np.any(sel != 4 * words[:, None] + np.arange(4)):
        return None
    return tuple(int(w) for w in words)


def fuse_blocks(hid, img):
    """Rewrite block-head hids to H_BLOCK_BASE + shape id.

    Returns (hid', shapes) where shapes is a tuple of block shapes;
    each shape is a tuple of op descriptors:

      ("nop",) ("const",) ("drop",) ("select",) ("memsize",)
      ("lget", k) ("lset", k) ("ltee", k)   k = local ORDINAL (first-
      ("gget", k) ("gset", k)                occurrence rank, so blocks
      ("alu2", sub) ("alu1", sub)            using different locals in
      ("loadi", nbytes, flags)               the same pattern share)
      ("storei", nbytes)
      ("vshuffle",) ("vshufflew", (s0, s1, s2, s3))
      ("guardz", tail) ("guardnz", tail)     tail = a shape of its own
      ("jump", nkeep)
      ("term", flat_hid)

    loadi/storei are loads/stores fused INLINE (uniform-address fast
    path; divergence/OOB bails un-advanced at the op's own slot).
    guardz/guardnz are FORWARD branches absorbed mid-block: the block
    speculates fallthrough — loop back-edges (backward targets) stay
    terminals so the common taken path pays nothing.  guardnz requires
    nkeep == 0 (no value move on the taken exit).

    SUPERBLOCKS: a block does not end at a forward edge whose target is
    static.  ("jump", nkeep) is a forward `br` absorbed mid-block: the
    ops after it are the TARGET's (tail duplication: the target keeps
    its own head and hid for every other way in).  A guard's `tail` is
    what its TAKEN side runs before it leaves the block: the target's
    block, possibly through jumps, possibly ending in a term; the empty
    tail leaves at the branch with everything before it committed and
    pc at the target.  The rules, all read off the image: forward
    targets only; no path through a shape is longer than MAX_BLOCK_LEN;
    a continuation is taken whole or not at all (one that room would
    cut mid-way is left to its own dispatch); duplicated code holds
    only the terminals of _DUP_TERMS, and a tail no loadi/storei; a
    tail holds no guard with a tail of its own (one level, so code
    grows by 2x at most); a guard of a block that ends in a backward
    branch (a loop's exit) keeps the empty tail; and no path is nested
    deeper (_path_nesting) than the plain block it grew from, so a tail
    is never nested deeper than the fall-through side of its guard.
    Where its full shape is not among the shapes kept (below), a head
    falls back to its plain shape before it goes unfused.

    Immediates/indices are NOT in the shape (handlers read them from
    the SMEM planes at the op's own original slot), except local/global
    ordinals, whose equality structure decides value forwarding, alu
    subs, which pick the compute fn, and the mask of an `i8x16.shuffle`
    that moves whole 32-bit lanes.  That mask is read HERE, at build
    time, from the image's v128 table (img.v128[img.a[pc]], so a
    multitenant image's rebased index is followed): where each output
    word k takes the four bytes of one source word s_k (0..3 the first
    operand's, 4..7 the second's) the op is ("vshufflew", (s0..s3)) and
    the block re-orders the operands' rows at trace time; any other mask
    stays ("vshuffle",), fetched at RUN time from the table by the block
    as by the unfused handler (simdops.vshuffle_dyn).  Deterministic, a
    function of the image alone: tpu.aot artifacts verify the persisted
    hid plane by regeneration (aot/__init__.py).

    Where the blocks would hold more than MAX_INLINE_ACCESSES inline
    loads and stores in all, the plane is made again with none: a
    block then ends before a load or a store (a cut, as for room), and
    each runs its own handler.

    SHAPE IDS: where the image's heads want at most MAX_BLOCK_SHAPES
    distinct shapes, every head takes its full shape and ids go out in
    code order.  Where they want more, the shapes kept are those that
    save the most dispatches in the deepest loops (_hot_shapes: a full
    shape scores, over the heads that want it, 16 ** loop_depth(head)
    x (ops - 1), ties to the lowest head; loop_depth counts the
    backward branches whose range holds the head), and the plane is
    made again: a head takes its full shape if it is kept, else its
    plain shape if that is, else no block.  (CoreMark's heads want
    177; in code order the first 96 went to the cold functions that
    come first in its file.)"""
    return fuse_blocks_counted(hid, img)[:2]


def fuse_blocks_counted(hid, img):
    """fuse_blocks' (hid, shapes) and its shape budget, {"kept": the
    shapes the plane holds, "wanted": the distinct full shapes of the
    image's heads}: wanted > kept where _hot_shapes chose them.  The
    inline accesses counted against MAX_INLINE_ACCESSES are those of
    the shapes wanted, so the choice never decides whether they fuse."""
    inline_mem = True
    fused, shapes, heads = _fuse_blocks(hid, img, inline_mem)
    if sum(1 for shape in shapes for op in shape
           if op[0] in ("loadi", "storei")) > MAX_INLINE_ACCESSES:
        inline_mem = False
        fused, shapes, heads = _fuse_blocks(hid, img, inline_mem)
    wanted = len(shapes)
    if wanted > MAX_BLOCK_SHAPES:
        fused, shapes, _heads = _fuse_blocks(
            hid, img, inline_mem, _hot_shapes(heads, img))
    return fused, shapes, {"kept": len(shapes), "wanted": wanted}


def loop_depths(img) -> np.ndarray:
    """Per slot, how many backward branches (`br`, `br_if` at slot s
    with a[s] <= s: a loop's back-edge) hold it in their range
    [a[s], s]."""
    n = img.code_len
    edge = np.zeros(n + 1, np.int64)
    for s in range(n):
        if int(img.cls[s]) in (CLS_BR, CLS_BRZ, CLS_BRNZ) and \
                int(img.a[s]) <= s:
            edge[int(img.a[s])] += 1
            edge[s + 1] -= 1
    return np.cumsum(edge)[:n]


def _hot_shapes(heads, img) -> set:
    """The MAX_BLOCK_SHAPES full shapes of `heads` (the (pc, full,
    plain) of every head of the plane with no cap) that save the most
    dispatches where loops nest deepest: a head adds 16 **
    loop_depth(pc) x (ops - 1) to its full shape; a tie goes to the
    shape with the lowest head, so the plane stays a function of the
    image."""
    depth = loop_depths(img)
    score, first = {}, {}
    for pc, full, _plain in heads:
        score[full] = score.get(full, 0) + \
            16 ** int(depth[pc]) * (len(full) - 1)
        first.setdefault(full, pc)
    ranked = sorted(score, key=lambda shape: (-score[shape], first[shape]))
    return set(ranked[:MAX_BLOCK_SHAPES])


def _fuse_blocks(hid, img, inline_mem, keep=None):
    """fuse_blocks' plane, with loads and stores fused inline or not:
    every head takes its full shape where `keep` is None, else its full
    shape if `keep` holds it, else its plain one if that is held, else
    no block.  -> (hid, shapes, heads): ids in code order, heads the
    (pc, full shape, plain shape) of every head found."""
    n = img.code_len
    targets = _jump_targets(img)
    # call-return / hostcall-re-arm / trap-partial-resume addresses need
    # no seeding: a non-pure op always ends its block, so the next block
    # starts at its pc+1 anyway, and absorbed slots keep their original
    # hids, so any resume pc stays independently dispatchable.

    trap1 = _trapping_alu1_subs()
    call_nest = 2 if int(img.max_local_zeros) > 0 else 1

    def pure_desc(pc, lmap, gmap):
        """Descriptor if the op at pc is pure (fusible mid-block)."""
        cl = int(img.cls[pc])
        if cl == CLS_NOP:
            return ("nop",)
        if cl == CLS_CONST:
            return ("const",)
        if cl == CLS_DROP:
            return ("drop",)
        if cl == CLS_SELECT:
            return ("select",)
        if cl == CLS_MEMSIZE:
            return ("memsize",)
        if cl in (CLS_LOCAL_GET, CLS_LOCAL_SET, CLS_LOCAL_TEE):
            k = lmap.setdefault(int(img.a[pc]), len(lmap))
            return ({CLS_LOCAL_GET: "lget", CLS_LOCAL_SET: "lset",
                     CLS_LOCAL_TEE: "ltee"}[cl], k)
        if cl in (CLS_GLOBAL_GET, CLS_GLOBAL_SET):
            k = gmap.setdefault(int(img.a[pc]), len(gmap))
            return ("gget" if cl == CLS_GLOBAL_GET else "gset", k)
        if cl == CLS_ALU2:
            sub = int(img.sub[pc])
            if sub in _DIV32_SUBS or sub in _DIV64_SUBS:
                return None
            return ("alu2", sub)
        if cl == CLS_ALU1:
            sub = int(img.sub[pc])
            if sub in trap1:
                return None
            return ("alu1", sub)
        if cl == CLS_V2:
            return ("v2", int(img.sub[pc]))
        if cl == CLS_V1:
            return ("v1", int(img.sub[pc]))
        if cl == CLS_VTEST:
            return ("vtest", int(img.sub[pc]))
        if cl == CLS_VSHIFT:
            return ("vshift", int(img.sub[pc]))
        if cl == CLS_VSPLAT:
            return ("vsplat", int(img.sub[pc]))
        if cl == CLS_VEXTRACT:
            return ("vextract", int(img.sub[pc]))
        if cl == CLS_VREPLACE:
            return ("vreplace", int(img.sub[pc]))
        if cl == CLS_VCONST:
            return ("vconst",)
        if cl == CLS_VSHUFFLE:
            words = word_selectors(img.v128[int(img.a[pc])])
            return ("vshuffle",) if words is None else ("vshufflew", words)
        if cl == CLS_VBITSEL:
            return ("vbitsel",)
        if cl == CLS_LOAD:
            return ("loadi", int(img.b[pc]), int(img.c[pc]))
        if cl == CLS_STORE:
            return ("storei", int(img.b[pc]))
        if cl == CLS_BRZ and int(img.a[pc]) > pc:
            return ("guardz", ())
        if cl == CLS_BRNZ and int(img.a[pc]) > pc and int(img.b[pc]) == 0:
            return ("guardnz", ())
        return None

    def segment(start, room, lmap, gmap, lone_term=False):
        """The straight run from `start`, at most `room` ops with its
        terminal: (path, cut), path a list of (op, slot, ordinals), the
        ordinals a copy of both maps as a guard saw them (a tail
        numbers on from there), else None.  The run absorbs the
        non-pure op it stopped at as its terminal (which may itself be
        a jump target: direct jumps to it dispatch its untouched
        original hid).  A run that stopped at a pure op stopped at a
        jump target, which starts its own block, or for room (cut)."""
        path = []
        j = start
        while (j < n and len(path) < room - 1
               and (j == start or j not in targets)):
            d = pure_desc(j, lmap, gmap)
            if d is None or (not inline_mem
                             and d[0] in ("loadi", "storei")):
                break
            guard = d[0] in ("guardz", "guardnz")
            path.append((d, j, (dict(lmap), dict(gmap)) if guard else None))
            j += 1
        if (path or lone_term) and j < n and room >= 1 and \
                pure_desc(j, {}, {}) is None:
            path.append((("term", int(hid[j])), j, None))
            return path, False
        return path, j < n and j not in targets

    def follow(path, lmap, gmap, limit, depth, before):
        """Run through the forward `br` that `path` ends in, into its
        target's segment, for as long as the rules hold.  The path is
        entered `depth` regions down after `before` ops (none: the
        block's own path; else a tail) and may nest `limit` deep."""
        while path and path[-1][0][0] == "term":
            slot = path[-1][1]
            if int(img.cls[slot]) != CLS_BR or int(img.a[slot]) <= slot \
                    or int(img.b[slot]) not in (0, 1):
                break
            lm, gm = dict(lmap), dict(gmap)
            more, cut = segment(
                int(img.a[slot]), MAX_BLOCK_LEN - before - len(path),
                lm, gm, lone_term=True)
            new = path[:-1] + [(("jump", int(img.b[slot])), slot, None)] \
                + more
            if cut or not more or not duplicable(more, before > 0) or \
                    nesting(new, depth) > limit:
                break
            path = new
            lmap.update(lm)
            gmap.update(gm)
        return path

    def duplicable(path, in_tail):
        return not any((in_tail and op[0] in ("loadi", "storei"))
                       or (op[0] == "term" and op[1] not in _DUP_TERMS)
                       for op, _slot, _ord in path)

    def nesting(path, depth=0):
        return _path_nesting([p[0] for p in path], call_nest, depth)

    hid = hid.copy()
    shape_ids = {}
    heads = []
    pc = 0
    while pc < n:
        # the plain block at pc: one segment, as before superblocks
        lmap, gmap = {}, {}
        path, _cut = segment(pc, MAX_BLOCK_LEN, lmap, gmap)
        nfirst = len(path)
        if nfirst < 2:
            pc += 1
            continue
        plain = tuple(p[0] for p in path)
        limit = nesting(path)
        path = follow(path, lmap, gmap, limit, 0, 0)
        ops = [p[0] for p in path]
        # a block that ends in a backward branch is a loop's body, and
        # a guard in it the loop's exit: taken once a loop where the
        # fall-through side runs once an iteration, so it keeps the
        # empty tail (the memory guest's three exits: with tails its
        # two loops ran 7-9 ns a dispatch slower on a v5e, PR 29)
        last, end = path[-1][0], path[-1][1]
        loops = last[0] == "term" and int(img.a[end]) <= end and \
            int(img.cls[end]) in (CLS_BR, CLS_BRZ, CLS_BRNZ)
        depth = 0
        for i, (op, slot, ordinals) in enumerate(path):
            if op[0] in ("loadi", "storei"):
                depth += 1
            if ordinals is None or loops:
                continue
            depth += 1
            lm, gm = dict(ordinals[0]), dict(ordinals[1])
            tail, cut = segment(int(img.a[slot]), MAX_BLOCK_LEN - (i + 1),
                                lm, gm, lone_term=True)
            if cut or not tail or not duplicable(tail, True) or \
                    nesting(tail, depth) > limit:
                continue
            tail = follow(tail, lm, gm, limit, depth, i + 1)
            ops[i] = (op[0], tuple(p[0] for p in tail))
        full = tuple(ops)
        heads.append((pc, full, plain))
        for shape in (full, plain):
            if keep is None or shape in keep:
                sid = shape_ids.setdefault(shape, len(shape_ids))
                hid[pc] = H_BLOCK_BASE + sid
                pc += nfirst
                break
        else:
            pc += 1
    return hid, tuple(shape_ids), heads


def _successors(img, slot) -> set:
    """Where the op at `slot`, dispatched alone or as a terminal, can
    leave pc (but for function entries and br_table targets, which
    entry_slots seeds)."""
    cl = int(img.cls[slot])
    if cl == CLS_BR:
        return {int(img.a[slot])}
    if cl in (CLS_BRZ, CLS_BRNZ):
        return {int(img.a[slot]), slot + 1}
    if cl in (CLS_RETURN, CLS_TRAP, CLS_BR_TABLE):
        return set()
    return {slot + 1}


def walk_shape(shape, head, img):
    """(op, original slot, in_tail) of every op of `shape` dispatched at
    `head`, tails after their guards; ("end",) marks a path that falls
    off its last op at that slot."""
    def walk(ops, slot, in_tail):
        for op in ops:
            yield op, slot, in_tail
            if op[0] == "term":
                return
            if op[0] == "jump":
                slot = int(img.a[slot])
                continue
            if op[0] in ("guardz", "guardnz") and op[1]:
                yield from walk(op[1], int(img.a[slot]), True)
            slot += 1
        yield ("end",), slot, in_tail

    return walk(shape, head, False)


def superblock_edges(hid, shapes, img) -> dict:
    """Forward edges the plane's blocks run through, by kind."""
    edges = {"jump": 0, "guard_tail": 0}
    for head in np.flatnonzero(hid >= H_BLOCK_BASE):
        for op, _slot, _in_tail in walk_shape(
                shapes[int(hid[head]) - H_BLOCK_BASE], int(head), img):
            if op[0] == "jump":
                edges["jump"] += 1
            elif op[0] in ("guardz", "guardnz") and op[1]:
                edges["guard_tail"] += 1
    return edges


def shuffle_sites(hid, shapes, img) -> Optional[dict]:
    """The image's `i8x16.shuffle` slots by the lowering they run under:
    "word" the slots a fused block runs as row moves (a slot once,
    however many tails duplicate it), "dynamic" the rest, which fetch
    their mask at run time (a byte-granular mask, or a slot that no
    block absorbed and its own handler runs).  None for an image
    without one."""
    total = int(np.count_nonzero(
        np.asarray(img.cls[:img.code_len]) == CLS_VSHUFFLE))
    if not total:
        return None
    word = set()
    for head in np.flatnonzero(hid >= H_BLOCK_BASE):
        for op, slot, _in_tail in walk_shape(
                shapes[int(hid[head]) - H_BLOCK_BASE], int(head), img):
            if op[0] == "vshufflew":
                word.add(slot)
    return {"word": len(word), "dynamic": total - len(word)}


def entry_slots(hid, shapes, img) -> np.ndarray:
    """Mask of the slots at which a dispatch can START while a block's
    lanes stay converged, for the plane fuse_blocks wrote: function
    entries, br_table targets, and from there on every slot at which a
    handler can leave pc: where a block falls off its last op or leaves
    through a guard with no tail, and the successors of a terminal or
    of an op dispatched alone.  A slot that every way in runs THROUGH
    (a forward target whose every in-edge a superblock absorbed: fib's
    6 and 15) is, like every other absorbed slot, reached only by a
    resume (a bail, a split child, a SIMT handoff coming back), so its
    handler is compiled but cold."""
    n = img.code_len
    entry = np.zeros(n, bool)
    todo = [int(x) for x in img.f_entry] + \
        [int(img.br_table[e, 0]) for e in range(img.br_table.shape[0])]
    while todo:
        pc = todo.pop()
        if not 0 <= pc < n or entry[pc]:
            continue
        entry[pc] = True
        h = int(hid[pc])
        if h < H_BLOCK_BASE:
            todo.extend(_successors(img, pc))
            continue
        for op, slot, _in_tail in walk_shape(
                shapes[h - H_BLOCK_BASE], pc, img):
            if op[0] == "term":
                todo.extend(_successors(img, slot))
            elif op[0] == "end" or \
                    (op[0] in ("guardz", "guardnz") and not op[1]):
                todo.append(slot if op[0] == "end" else int(img.a[slot]))
    return entry


def plan_dispatch_tree(weights):
    """Plan the kernel's dispatch tree over dense handler ids 0..n-1,
    given in hot-first order (weights non-increasing, zeros last).

    Returns (tree, depths): tree is a leaf `i` or a node
    `(mid, left, right)` read as "id < mid ? left : right" over a
    contiguous id range; depths[i] is the number of branches walked to
    reach handler i.  The split point of a range is the one that best
    halves its WEIGHT, ties going to the one that best halves its
    COUNT (equal weights give the midpoint tree).  The zero-weight tail
    is one leaf of that tree, so cold handlers never sit between hot
    ones, and is itself split by count alone, so it cannot degenerate
    into a chain.  No leaf lies deeper than ceil(log2 n) + 2, however
    skewed the weights: the chip's compiler survives only so many
    nested regions (tests/test_chip_compile.py), and a handler's own
    nesting comes on top of its depth here."""
    n = len(weights)
    w = [int(x) for x in weights]
    if n == 0 or any(x < 0 for x in w) or \
            any(w[i] < w[i + 1] for i in range(n - 1)):
        raise ValueError(f"weights must be hot-first: {weights!r}")
    nhot = sum(1 for x in w if x > 0)
    cap = (n - 1).bit_length() + 2
    depths = [0] * n

    def by_count(lo, hi, d):
        if hi - lo == 1:
            depths[lo] = d
            return lo
        mid = lo + (hi - lo) // 2
        return (mid, by_count(lo, mid, d + 1), by_count(mid, hi, d + 1))

    # items of the weighted part: one per hot handler, then the whole
    # cold tail (if any) as a single zero-weight item
    items = [(i, i + 1, w[i]) for i in range(nhot)]
    if nhot < n:
        items.append((nhot, n, 0))

    def by_weight(a, b, d):
        lo, hi = items[a][0], items[b - 1][1]
        if b - a == 1:
            return by_count(lo, hi, d)
        room = 1 << (cap - d - 1)    # leaves a child may still hold
        total = sum(it[2] for it in items[a:b])
        best, acc = None, 0
        for m in range(a + 1, b):
            acc += items[m - 1][2]
            mid = items[m][0]
            if mid - lo > room or hi - mid > room:
                continue
            key = (abs(2 * acc - total), abs((m - a) - (b - m)))
            if best is None or key < best[0]:
                best = (key, m)
        if best is None:
            return by_count(lo, hi, d)
        m = best[1]
        return (items[m][0], by_weight(a, m, d + 1), by_weight(m, b, d + 1))

    return by_weight(0, len(items), 0), tuple(depths)


def kernel_dispatch_plan(hid_weights, optimistic):
    """The tree one kernel is built with: its handlers and, in the
    optimistic kernel, the periodic commit as one more cold leaf (the
    dense id after the last handler)."""
    return plan_dispatch_tree(
        tuple(hid_weights) + ((0,) if optimistic else ()))


def expected_and_max_depth(weights, depths):
    """(expected, max) depth of a planned tree; expected over the
    weights, which are static entry counts, not a profile."""
    return (sum(w * d for w, d in zip(weights, depths))
            / max(sum(weights), 1), max(depths))


# SMEM budget for the 7 code planes — the ONE code-size limit shared by
# the engine (PallasUniformEngine.MAX_CODE_LEN) and the tpu.aot
# serializer via pallas_image_eligibility's default.
MAX_CODE_LEN = 16384


def pallas_image_eligibility(img: DeviceImage,
                             max_code_len: int = MAX_CODE_LEN
                             ) -> Optional[str]:
    """Static (lane-count-independent) Pallas eligibility of a device
    image — the ONE source of truth shared by the engine, the scheduler
    and the tpu.aot serializer, so those layers can never disagree about
    what the kernel can execute.  Returns a reason string when the image
    must stay on the SIMT engine, None when the Pallas kernel can run it.
    Mirrors the reference's never-crash AOT fallback seam
    (/root/reference/lib/loader/ast/module.cpp:279-326)."""
    if img.code_len > max_code_len:
        return f"code too large for SMEM ({img.code_len} instrs)"
    unhandled = (set(np.unique(img.cls).tolist())
                 - set(_CLS_TO_HID) - set(_VCLS_TO_BASE)
                 - {CLS_ALU2, CLS_ALU1})
    if unhandled:
        return f"classes without Pallas handlers: {sorted(unhandled)}"
    return None


def hid_plane(img: DeviceImage) -> np.ndarray:
    """Per-pc flat handler id from the (class, sub) encoding."""
    hid = np.zeros(img.code_len, np.int32)
    for pc in range(img.code_len):
        c = int(img.cls[pc])
        if c == CLS_ALU2:
            hid[pc] = H_ALU2_BASE + int(img.sub[pc])
        elif c == CLS_ALU1:
            hid[pc] = H_ALU1_BASE + int(img.sub[pc])
        elif c in _VCLS_TO_BASE:
            hid[pc] = _VCLS_TO_BASE[c] + int(img.sub[pc])
        elif c == CLS_LOAD and int(img.b[pc]) == 4 \
                and int(img.c[pc]) in (0, 2):
            # i32.load / f32.load / i64.load32_u: lo = raw word, hi = 0
            hid[pc] = H_LOAD_W
        elif c == CLS_LOAD and int(img.b[pc]) == 8:
            hid[pc] = H_LOAD_D
        elif c == CLS_STORE and int(img.b[pc]) == 4:
            hid[pc] = H_STORE_W
        elif c == CLS_STORE and int(img.b[pc]) == 8:
            hid[pc] = H_STORE_D
        else:
            hid[pc] = _CLS_TO_HID[c]
    return hid




# ALU2 subs that can trap (div/rem)
_DIV32_SUBS = {ALU2_I32_BASE + _I32_BIN.index(n) for n in
               ("div_s", "div_u", "rem_s", "rem_u")}
_DIV64_SUBS = {ALU2_I64_BASE + _I32_BIN.index(n) for n in
               ("div_s", "div_u", "rem_s", "rem_u")}
_DIVS_SUBS = {ALU2_I32_BASE + _I32_BIN.index("div_s"),
              ALU2_I64_BASE + _I32_BIN.index("div_s")}
# trapping ALU1 subs come from the shared table (laneops.alu1_trap_fns)
# fused-block ops of a v128 class, and the ops at which a straight run
# of a block ends (mk_block's emit counts the former a run at a time)
_SIMD_BLOCK_OPS = frozenset(("v2", "v1", "vtest", "vshift", "vsplat",
                             "vextract", "vreplace", "vconst", "vshuffle",
                             "vshufflew", "vbitsel"))
_RUN_ENDS = frozenset(("guardz", "guardnz", "loadi", "storei", "jump"))
# ALU subs that run a binary64 routine of batch/softfloat.py (a
# reinterpret moves bits and runs none)
_F64_ALU2_SUBS = frozenset(range(ALU2_F64_BASE, NUM_ALU2))
_F64_ALU1_SUBS = frozenset(i for n, i in ALU1_SUB.items()
                           if "f64" in n and "reinterpret" not in n)


def holds_softfloat(img) -> bool:
    """Whether the image holds a binary64 ALU op: its kernel then
    counts the softfloat routines it runs (ctrl column _C_SOFTFLOAT)."""
    cls, sub = np.asarray(img.cls), np.asarray(img.sub)
    return bool(
        np.any((cls == CLS_ALU2) & np.isin(sub, list(_F64_ALU2_SUBS)))
        or np.any((cls == CLS_ALU1) & np.isin(sub, list(_F64_ALU1_SUBS))))


def holds_indirect(img) -> bool:
    """Whether the image holds a br_table or a call_indirect: its kernel
    then counts the two it runs (`indirect_column`)."""
    return bool(np.any(np.isin(np.asarray(img.cls),
                               (CLS_BR_TABLE, CLS_CALL_INDIRECT))))


def lane_stripe(Lblk: int, interpret: bool) -> int:
    """Lanes a stripe of the kernel's 8-sublane remap (`_build_kernel`):
    Lblk / 8 where the lane block splits into 8 stripes of whole lane
    tiles, else Lblk (no remap).  Interpret mode (CPU tests) takes the
    remap whenever Lblk divides by 8, so the suite runs the 3-d layout
    at small lane counts."""
    return Lblk // 8 if Lblk % (8 if interpret else 1024) == 0 else Lblk


def plane_shape(rows: int, lanes: int, stripe: Optional[int]) -> tuple:
    """The shape a full-lane plane of `rows` lives in, between launches
    as inside the kernel: [rows, lanes / stripe, stripe] under the remap,
    lane l at (l // stripe, l % stripe), so that a C-order reshape to
    [rows, lanes] is the plane lane by lane; [rows, lanes] without it
    (`stripe` None).  On the chip the two are different tilings (8 rows
    a tile against 8 stripes), so a reshape between them is a
    plane-sized copy."""
    if stripe is None:
        return (rows, lanes)
    return (rows, lanes // stripe, stripe)


def lanes_of(x):
    """A plane (or rows cut from one), host or device, as [rows, L]:
    free on the host, a copy of what it is given on the device."""
    return x.reshape(x.shape[0], -1) if x.ndim == 3 else x


def take_cols(plane, idx):
    """The lanes `idx` of every row of `plane`, [rows, len(idx)]."""
    if plane.ndim == 3:
        return plane[:, idx // plane.shape[2], idx % plane.shape[2]]
    return plane[:, idx]


def cut_cols(plane, r0, k, c0, n):
    """Rows r0.. (k of them) of lanes c0.. (n) of `plane`, [k, n]; under
    the remap c0 and n are whole stripes."""
    from jax import lax

    if plane.ndim == 3:
        p = plane.shape[2]
        return lax.dynamic_slice(plane, (r0, c0 // p, c0 * 0),
                                 (k, n // p, p)).reshape(k, n)
    return lax.dynamic_slice(plane, (r0, c0), (k, n))


def put_cols(plane, new, r0, c0):
    """`plane` with `new` [k, n] set at rows r0.., lanes c0..; under
    the remap c0 and n are whole stripes (a lane block's are)."""
    from jax import lax

    if plane.ndim == 3:
        p = plane.shape[2]
        return lax.dynamic_update_slice(
            plane, new.reshape(new.shape[0], -1, p), (r0, c0 // p, c0 * 0))
    return lax.dynamic_update_slice(plane, new, (r0, c0))


# The kernel's operands: 14 tables and ctrl (its 15 scalar-prefetch
# arguments), frames_in, then the planes from this index on, each
# aliased to an output
_PLANE_ARG0 = 16
# The planes a launch donates: the memory plane and its rollback
# shadow, the two that linear memory sizes (up to most of HBM), which
# the kernel then writes where they lie.  The others (stacks, globals,
# the trap row and their shadows: a few MiB) are not donated: XLA copies
# each into VMEM around the kernel (`S(1)` in the compiled launch),
# where the kernel's snapshot and row DMAs are faster than from HBM (a
# launch that donated every plane read them from HBM: 7 to 8 % longer
# kernels in the memory cells, PR 42)
_DONATED_PLANES = (_PLANE_ARG0 + 4, _PLANE_ARG0 + 11)


@functools.lru_cache(maxsize=64)
def _build_kernel(used_hids: tuple, D: int, CD: int, W: int, L: int,
                  Lblk: int, NG: int, code_len: int, nf: int, tsize: int,
                  max_local_zeros: int, mem_pages_cap: int,
                  mem_pages_hard: int, gatherable: bool, interpret: bool,
                  mem_hbm: bool = False, CW: int = 0,
                  block_shapes: tuple = (),
                  simd: bool = False, NV: int = 1,
                  optimistic: bool = False, snap_steps: int = 8192,
                  shadow_full: bool = None, hid_weights: tuple = (),
                  softfloat: bool = False, indirect: bool = False):
    """Compile the chunk-runner for one kernel geometry.

    Returns a jitted callable over
      (hid, a, b, c, ilo, ihi, fent, fnpar, fnloc, ftop, ftyp, brt, tbl,
       ctrl, frames, stack_lo, stack_hi, glob_lo, glob_hi, mem, trap)
    yielding (ctrl, frames, stack_lo, stack_hi, glob_lo, glob_hi, mem,
    trap); the VMEM planes are aliased in-place.

    mem_hbm=True is the large-block memory mode: the [W, L] linear-memory
    plane stays HBM-resident instead of being DMA'd wholesale into VMEM
    scratch, and loads/stores go through a 2-way LRU *window cache* of CW
    rows per way in VMEM.  Uniform-address accesses that hit a resident
    window are direct row ops (the common case — converged code computes
    identical addresses); misses write back the dirty victim way and DMA
    a fresh CW-row window; per-lane address divergence that still fits
    one window is served by compare-reduce inside the window.  This
    removes the W-words-per-lane term from the VMEM budget, so a 1-page
    module runs thousands of lanes per block instead of 128 — the
    reference's guard-page slab redesign
    (/root/reference/include/runtime/instance/memory.h:34-332) rebuilt a
    second time around the HBM/VMEM split instead of virtual-memory
    protection.  memory.fill streams aligned GR-row chunks through
    scratch; memory.copy runs through the windows (single-window when
    the whole src+dst span fits, way-per-region when src and dst are
    ≥CW+8 rows apart, SIMT handoff for large overlapping moves).

    optimistic=True is the *optimistic-convergence* mode, the engine's
    core TPU perf move: every cross-lane agreement reduction (branch
    conds, load/store address uniformity, trap uniformity — each a
    vector→scalar sync costing ~Lblk-linear time in Mosaic, measured
    ~1.7µs at Lblk=4096) is replaced by a lane-0 decision plus a pure
    vector *canary* accumulation (canary |= lane ^ lane0).  The canary
    is validated by ONE reduction per commit point: every `snap_steps`
    steps (the commit is a cold leaf of the dispatch tree that the loop
    takes in place of an instruction when `steps - ls` reaches the
    interval; see commit_due/commit below), before any dirty-window
    writeback, and at kernel exit.
    A clean validation writes a snapshot (stacks/globals/trap → shadow
    HBM planes, frames/carry → SMEM); a dirty one rolls back to the
    previous snapshot and exits with ST_RECHECK, and the driver re-runs
    the block on the non-optimistic ("careful", optimistic=False)
    kernel for one short chunk to reach the divergent instruction with
    exact per-step semantics — the scheduler then splits as usual.
    memory.fill/copy and in-window divergent addressing always exit to
    the careful kernel.  Convergence validation thus costs O(1)
    reductions per ~snap_steps instructions instead of O(1) per
    instruction, which is what lets one TensorCore retire thousands of
    converged lanes per dispatch at row-op cost.

    used_hids is the dense handler order (hot-first) and hid_weights
    its entry-slot weights: dispatch() follows
    kernel_dispatch_plan(hid_weights, optimistic), so both are part of
    what a kernel is (and of the export-cache key)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from wasmedge_tpu.batch import laneops as lo_ops

    I32 = jnp.int32
    u_lt = lo_ops.u_lt
    alu2 = lo_ops.alu2_fns()
    alu1 = lo_ops.alu1_fns()
    alu1_traps = lo_ops.alu1_trap_fns()
    nblk = L // Lblk
    NGp = max(NG, 1)
    # Divergent-address memory ops scan memory in row chunks so the scan
    # temporaries stay bounded (~512 KiB) instead of materializing a full
    # [W, Lblk] iota next to the state.
    GR = W
    while GR > 8 and GR * Lblk * 4 > 512 * 1024:
        GR //= 2
    while GR > 8 and W % GR != 0:
        GR //= 2
    if mem_hbm:
        # fill/copy chunks stage through the CW-row window scratch
        while GR > 8 and GR > CW:
            GR //= 2
    GATHER_CHUNKS = W // GR if W % GR == 0 else 0

    # ---- 8-sublane lane remap ------------------------------------------
    # A (1, Lblk) int32 row occupies Lblk/128 vregs at 1/8 sublane
    # utilization.  When the lane block splits
    # into 8 stripes of whole lane tiles (Lpb % 128 == 0), kernel state
    # is laid out [rows, 8, Lpb] instead of [rows, Lblk]: every row op
    # then runs on an (8, Lpb) array = Lblk/1024 fully-packed vregs, an
    # 8x denser vector layout for identical state.  The HBM planes
    # live as [rows, L/Lpb, Lpb] (`plane_shape`) so that block b's lanes are
    # exactly stripes [8b, 8b+8) and each plane DMA stays one copy.
    # Lane l maps to (stripe l//Lpb, column l%Lpb): lane 0 stays at
    # (0, 0), so scal()/lane-0 optimistic decisions are unchanged.
    Lpb = lane_stripe(Lblk, interpret)
    SUB = Lblk // Lpb
    three_d = SUB > 1
    ROW = (SUB, Lpb)

    # inputs/outputs: frames + 12 base planes (+4 v128 planes: stack
    # e2/e3 and their rollback shadows, appended LAST so every existing
    # index — scheduler plane map, hostcall serving, checkpointing —
    # stays stable whether or not the module uses v128)
    N_IN = 13 + (4 if simd else 0)

    def kernel(*kargs_):
        (hid_r, a_r, b_r, c_r, ilo_r, ihi_r,
         fent_r, fnpar_r, fnloc_r, ftop_r, ftyp_r, brt_r, tbl_r,
         v128t_r, ctrl_r) = kargs_[:15]
        ins_ = kargs_[15:15 + N_IN]
        (frames_in, s_lo_in, s_hi_in, g_lo_in, g_hi_in, mem_in,
         trap_in, sh_slo_in, sh_shi_in, sh_glo_in, sh_ghi_in,
         sh_trap_in, sh_mem_in) = ins_[:13]
        outs_ = kargs_[15 + N_IN: 15 + N_IN + 1 + N_IN]
        (ctrl_out, frames_out, s_lo_out, s_hi_out, g_lo_out, g_hi_out,
         mem_out, trap_out, sh_slo, sh_shi, sh_glo, sh_ghi, sh_trap,
         sh_mem) = outs_[:14]
        if simd:
            se2_in, se3_in, sh_se2_in, sh_se3_in = ins_[13:17]
            se2_out, se3_out, sh_se2, sh_se3 = outs_[14:18]
        scr = kargs_[15 + N_IN + 1 + N_IN:]
        # sh_* are the rollback-snapshot shadow planes (HBM, aliased
        # in/out, only touched in optimistic mode; degenerate [1, L]
        # sh_mem when the memory plane is HBM-resident — the plane
        # itself then already holds last-commit state).
        it_ = iter(scr)
        slo, shi = next(it_), next(it_)
        se2s = next(it_) if simd else None
        se3s = next(it_) if simd else None
        glo, ghi = next(it_), next(it_)
        if mem_hbm:
            mwin, wcnt, wacc = next(it_), next(it_), next(it_)
            memr = None
        else:
            memr = next(it_)
        trapr, sems = next(it_), next(it_)
        if optimistic:
            canr, flag, snapf, snapc = (next(it_), next(it_),
                                        next(it_), next(it_))
        turns = next(it_)
        sfc = next(it_) if softfloat else None
        sdc = next(it_) if simd else None
        idc = next(it_) if indirect else None
        blk = pl.program_id(0)
        lo = blk * Lblk
        # lane-block slices of the HBM planes (`plane_shape`): in
        # three_d mode a plane is [rows, L/Lpb, Lpb] and the block's
        # lanes are stripes [8b, 8b+8) (whole (8,128) tiles, so the
        # slice start is statically 8-aligned for Mosaic).
        if three_d:
            lo3 = pl.multiple_of(blk * SUB, SUB)

            def lslice(ref):
                return ref.at[:, pl.ds(lo3, SUB)]

            def lsliceR(ref, r0, n):
                return ref.at[pl.ds(r0, n), pl.ds(lo3, SUB)]
        else:
            def lslice(ref):
                return ref.at[:, pl.ds(lo, Lblk)]

            def lsliceR(ref, r0, n):
                return ref.at[pl.ds(r0, n), pl.ds(lo, Lblk)]

        # State planes live in HBM (pl.ANY); the working copy is VMEM
        # scratch, DMA'd in per lane block and DMA'd back at the end.
        # Keeping VMEM usage at 1x state size (no separate input/output
        # windows, no automatic double buffering) is what lets a
        # memory-free module run all lanes in a single block.  In
        # mem_hbm mode the memory plane is NOT staged: handlers DMA
        # CW-row windows of mem_out (aliased with mem_in) on demand.
        def dma(i, src, dst):
            return pltpu.make_async_copy(src, dst, sems.at[i])

        ins = [dma(0, lslice(s_lo_in), slo),
               dma(1, lslice(s_hi_in), shi),
               dma(2, lslice(g_lo_in), glo),
               dma(3, lslice(g_hi_in), ghi),
               dma(5, lslice(trap_in), trapr)]
        if not mem_hbm:
            ins.append(dma(4, lslice(mem_in), memr))
        if simd:
            # sems 6/7 are reused for the e2/e3 planes here and in the
            # snapshot paths: window DMAs (the other users of 6/7) are
            # never in flight across those batches, and semaphores of
            # their own change nothing (PR 39's control on the chip: a
            # ChaCha20 job 0.531 s for 0.528)
            ins += [dma(6, lslice(se2_in), se2s),
                    dma(7, lslice(se3_in), se3s)]
        for c in ins:
            c.start()
        for c in ins:
            c.wait()

        # frames: whole-array SMEM refs [nblk, 3, CD]; each grid program
        # copies and mutates only its own block's rows.
        def cp_frame(i, _):
            frames_out[blk, 0, i] = frames_in[blk, 0, i]
            frames_out[blk, 1, i] = frames_in[blk, 1, i]
            frames_out[blk, 2, i] = frames_in[blk, 2, i]
            return 0

        lax.fori_loop(0, CD, cp_frame, 0)

        chunk = ctrl_r[blk, _C_CHUNK]
        # per-block fuel (gas analog, block-uniform like all control state);
        # _FUEL_OFF disables.  The loop stops at the fuel boundary and the
        # post-loop check below converts exhaustion into CostLimitExceeded —
        # same per-instruction decrement semantics as the SIMT engine's
        # per-lane fuel plane.  Fused dispatches may overshoot the
        # boundary by their block length (< MAX_BLOCK_LEN instructions);
        # the kill itself is always delivered — only the exact stopping
        # instruction is block-granular, like the reference's
        # per-codeblock cost check (lib/executor/engine/engine.cpp).
        fuel_in = ctrl_r[blk, _C_FUEL]
        chunk_eff = jnp.minimum(chunk, fuel_in)
        snap_in = ctrl_r[blk, _C_SNAP]
        snap_dyn = jnp.where(snap_in > 0, snap_in, I32(snap_steps))

        def full(v):
            return jnp.full(ROW, v, I32)

        # row access: a logical row is always a 2-d (SUB, Lpb) array —
        # (1, Lblk) in legacy mode, (8, Lpb) fully tiled in three_d mode
        if three_d:
            def srow(ref, i):
                return ref[pl.ds(i, 1)][0]

            def wrow(ref, i, v):
                ref[pl.ds(i, 1)] = v[None]

            def srows(ref, r0, n):
                return ref[pl.ds(r0, n)]            # (n, SUB, Lpb)

            def wrows(ref, r0, n, v):
                ref[pl.ds(r0, n)] = v

            def riota(n):
                # row-index iota over an (n, SUB, Lpb) row stack
                return jax.lax.broadcasted_iota(I32, (n,) + ROW, 0)

            def rsum(x):
                # reduce an (n, SUB, Lpb) stack to one row
                return jnp.sum(x, axis=0)
        else:
            def srow(ref, i):
                return ref[pl.ds(i, 1), :]

            def wrow(ref, i, v):
                ref[pl.ds(i, 1), :] = v

            def srows(ref, r0, n):
                return ref[pl.ds(r0, n), :]

            def wrows(ref, r0, n, v):
                ref[pl.ds(r0, n), :] = v

            def riota(n):
                return jax.lax.broadcasted_iota(I32, (n, Lblk), 0)

            def rsum(x):
                return jnp.sum(x, axis=0, keepdims=True)

        def scal(vec):
            return vec[0, 0]

        def trap_where(cond_row, code_row):
            """Per-lane trap-code write: codes where cond, else keep."""
            wrow(trapr, 0, jnp.where(cond_row, code_row, srow(trapr, 0)))

        # 4-plane cell accessors (v128 cells span lo/hi/e2/e3; scalar
        # cells leave e2/e3 don't-care — copies move whatever is there)
        def srow4(i):
            if simd:
                return (srow(slo, i), srow(shi, i),
                        srow(se2s, i), srow(se3s, i))
            return (srow(slo, i), srow(shi, i))

        def wrow4(i, v):
            wrow(slo, i, v[0])
            wrow(shi, i, v[1])
            if simd:
                wrow(se2s, i, v[2])
                wrow(se3s, i, v[3])

        def allsame(vec, s):
            return jnp.all(vec == s)

        def shifted_store_triples(m_lo, m_hi, vl, vh, shB):
            """(mask, value) pairs for the up-to-3 words a (possibly
            unaligned) store touches, shifted into word lanes.  The ONE
            copy of this construction — scalar or vector masks/shifts
            both broadcast through."""
            sm0, sm1 = lo_ops.shl64(m_lo, m_hi, shB)
            sm2 = jnp.where(shB == 0, 0,
                            lo_ops.shr64_u(m_lo, m_hi, 64 - shB)[0])
            sv0, sv1 = lo_ops.shl64(vl, vh, shB)
            sv2 = jnp.where(shB == 0, 0,
                            lo_ops.shr64_u(vl, vh, 64 - shB)[0])
            return ((sm0, sv0), (sm1, sv1), (sm2, sv2))

        # carry: (steps, pc, sp, fp, ob, cd, pages, status) — mem_hbm
        # mode appends the window-cache fields (wb0, wd0, wb1, wd1, mru):
        # per-way window base row / dirty flag + the MRU way for LRU
        # victim choice.  optimistic mode appends ls (step count at the
        # last snapshot).  Block-uniform scalars like the rest of ctrl.
        _CARRY = ("steps", "pc", "sp", "fp", "ob", "cd", "pages", "status")
        if mem_hbm:
            _CARRY = _CARRY + ("wb0", "wd0", "wb1", "wd1", "mru")
        if optimistic:
            _CARRY = _CARRY + ("ls",)
        IDX = {n: i for i, n in enumerate(_CARRY)}
        NCARRY = len(_CARRY)

        def keep(c, **kw):
            d = dict(zip(_CARRY, c))
            d.update(kw)
            return tuple(d[k] for k in _CARRY)

        # ---- optimistic-convergence machinery -------------------------
        # (see _build_kernel docstring) canr is the divergence canary;
        # snapc/snapf/shadow planes hold the rollback point.
        if optimistic:
            SENT_W = I32(-(1 << 30))

            def agree_i32(vec):
                """lane-0 value decision; exact-mismatch canary."""
                s = scal(vec)
                wrow(canr, 0, srow(canr, 0) | (vec ^ s))
                return s

            def opt_addr_prolog(ea, off, nbytes, pages):
                """Lane-0 effective-address decision plus a fully
                SCALAR bounds check (address agreement is the
                optimistic assumption, so OOB agreement follows; lane
                mismatches go to the canary and roll back).  The ONE
                copy of this math, shared by the width-specialized
                unfused handlers and the fused inline loads/stores.
                Returns (ea0, oob0, word index u, bit shift shB)."""
                ea0 = agree_i32(ea)
                addr0 = ea0 - off
                mem_bytes = pages * I32(65536)
                end0 = ea0 + nbytes
                oob0 = u_lt(ea0, addr0) | u_lt(ea0, off) | \
                    u_lt(end0, ea0) | u_lt(mem_bytes, end0)
                u = jnp.clip(lax.shift_right_logical(ea0, 2), 0, W - 1)
                shB = (ea0 & 3) * 8
                return ea0, oob0, u, shB

            def agree_nz(vec):
                """lane-0 zeroness decision (branch conditions agree when
                their zeroness agrees, not their values)."""
                s = scal(vec)
                wrow(canr, 0, srow(canr, 0) | jnp.where(
                    (vec != 0) != (s != 0), I32(1), I32(0)))
                return s

            def copy_e_planes(restore):
                """The v128 planes e2/e3 to their shadows, or back, in
                8-row pieces: a loop of fixed-shape DMAs.  One DMA a
                plane lowers to a descriptor a row wherever a snapshot
                is inlined (every windowed access has one), and with
                four stack planes that put the ChaCha20 kernel at
                118,121 bundles: over about 108,000 the TPU compiler
                cuts this program into 10 to 18 instruction overlays
                for 5, the hot loop crosses them, and a job took
                0.529 s for 0.199 (PR 39, PERF.md section 5;
                tests/test_chip_compile.py holds the size).  The
                bytes and the semaphores were never the cost."""
                n = 8 if D % 8 == 0 else D

                def piece(i, _):
                    r0 = pl.multiple_of(i * n, n)
                    cps = []
                    for k, (plane, shadow) in enumerate(
                            ((se2s, sh_se2), (se3s, sh_se3))):
                        src, dst = (plane.at[pl.ds(r0, n)],
                                    lsliceR(shadow, r0, n))
                        if restore:
                            src, dst = dst, src
                        cps.append(dma(6 + k, src, dst))
                    for cp_ in cps:
                        cp_.start()
                    for cp_ in cps:
                        cp_.wait()
                    return 0

                lax.fori_loop(0, D // n, piece, 0)

            def do_snapshot(c):
                """Record the rollback point = the CURRENT (validated)
                state: planes -> shadow HBM, live frames + carry ->
                SMEM, canary reset."""
                cps = [dma(0, slo, lslice(sh_slo)),
                       dma(1, shi, lslice(sh_shi)),
                       dma(2, glo, lslice(sh_glo)),
                       dma(3, ghi, lslice(sh_ghi)),
                       dma(5, trapr, lslice(sh_trap))]
                if not mem_hbm and W > 1:
                    cps.append(dma(4, memr, lslice(sh_mem)))
                for cp_ in cps:
                    cp_.start()
                for cp_ in cps:
                    cp_.wait()
                if simd:
                    copy_e_planes(restore=False)
                cd_now = c[IDX["cd"]]

                def cpf(i, _):
                    for j in range(3):
                        snapf[j, i] = frames_out[blk, j, i]
                    return 0

                lax.fori_loop(0, jnp.clip(cd_now, 0, CD), cpf, 0)
                for k in range(NCARRY):
                    snapc[k] = c[k]
                wrow(canr, 0, full(0))

            def do_restore():
                """Rewind to the last snapshot (inverse of do_snapshot)."""
                cps = [dma(0, lslice(sh_slo), slo),
                       dma(1, lslice(sh_shi), shi),
                       dma(2, lslice(sh_glo), glo),
                       dma(3, lslice(sh_ghi), ghi),
                       dma(5, lslice(sh_trap), trapr)]
                if not mem_hbm and W > 1:
                    cps.append(dma(4, lslice(sh_mem), memr))
                for cp_ in cps:
                    cp_.start()
                for cp_ in cps:
                    cp_.wait()
                if simd:
                    copy_e_planes(restore=True)
                cd_snap = snapc[IDX["cd"]]

                def cpf(i, _):
                    for j in range(3):
                        frames_out[blk, j, i] = snapf[j, i]
                    return 0

                lax.fori_loop(0, jnp.clip(cd_snap, 0, CD), cpf, 0)
                wrow(canr, 0, full(0))

            def rolled_carry():
                """Post-restore carry: snapshot scalars, ST_RECHECK, and
                (hbm) invalidated windows — their VMEM contents are
                stale relative to the restored plane."""
                vals = {n: snapc[i] for i, n in enumerate(_CARRY)}
                vals["status"] = I32(ST_RECHECK)
                if mem_hbm:
                    vals["wb0"] = SENT_W
                    vals["wd0"] = I32(0)
                    vals["wb1"] = SENT_W
                    vals["wd1"] = I32(0)
                return tuple(vals[n] for n in _CARRY)

            def _opt_bulk_exit(c):
                """Ops the optimistic kernel defers to the careful one
                (memory.fill/copy: per-lane ranged, reduction-heavy).
                Validate; roll back if a stale decision is pending; exit
                at this exact instruction with ST_RECHECK."""
                flag[0] = jnp.any(srow(canr, 0) != 0).astype(jnp.int32)
                dirty = flag[0] != 0

                @pl.when(dirty)
                def _():
                    do_restore()

                return lax.cond(
                    dirty, rolled_carry,
                    lambda: keep(c, status=I32(ST_RECHECK)))

        # ------------------- handlers ---------------------------------
        def h_nop(c):
            return keep(c, pc=c[1] + 1)

        def h_const(c):
            pc, sp = c[1], c[2]
            wrow(slo, sp, full(ilo_r[pc]))
            wrow(shi, sp, full(ihi_r[pc]))
            return keep(c, pc=pc + 1, sp=sp + 1)

        def h_local_get(c):
            pc, sp, fp = c[1], c[2], c[3]
            src = fp + a_r[pc]
            wrow4(sp, srow4(src))
            return keep(c, pc=pc + 1, sp=sp + 1)

        def h_local_set(c):
            pc, sp, fp = c[1], c[2], c[3]
            dst = fp + a_r[pc]
            wrow4(dst, srow4(sp - 1))
            return keep(c, pc=pc + 1, sp=sp - 1)

        def h_local_tee(c):
            pc, sp, fp = c[1], c[2], c[3]
            dst = fp + a_r[pc]
            wrow4(dst, srow4(sp - 1))
            return keep(c, pc=pc + 1)

        def h_global_get(c):
            pc, sp = c[1], c[2]
            g = a_r[pc]
            wrow(slo, sp, srow(glo, g))
            wrow(shi, sp, srow(ghi, g))
            return keep(c, pc=pc + 1, sp=sp + 1)

        def h_global_set(c):
            pc, sp = c[1], c[2]
            g = a_r[pc]
            wrow(glo, g, srow(slo, sp - 1))
            wrow(ghi, g, srow(shi, sp - 1))
            return keep(c, pc=pc + 1, sp=sp - 1)

        def h_drop(c):
            return keep(c, pc=c[1] + 1, sp=c[2] - 1)

        def h_select(c):
            pc, sp = c[1], c[2]
            cond = srow(slo, sp - 1)
            v1 = srow4(sp - 2)
            v2 = srow4(sp - 3)
            wrow4(sp - 3, tuple(jnp.where(cond == 0, a, b)
                                for a, b in zip(v1, v2)))
            return keep(c, pc=pc + 1, sp=sp - 2)

        def br_with(c, top1=None):
            pc, sp, ob = c[1], c[2], c[4]
            tgt, nkeep, pop_to = a_r[pc], b_r[pc], c_r[pc]
            tgt_sp = ob + pop_to
            kept = top1 if top1 is not None else srow4(sp - 1)

            @pl.when(nkeep == 1)
            def _():
                wrow4(tgt_sp, kept)

            return keep(c, pc=tgt, sp=tgt_sp + nkeep)

        def h_br(c):
            return br_with(c)

        # The *_with cores take optional vreg views of the top one/two
        # stack cells (top1 = value at sp-1, top2 = at sp-2, each a
        # (lo, hi) pair).  Fused blocks pass values still held in
        # vector registers, skipping the VMEM round trip between the
        # producing op and the branch (~100ns of store-load dependency
        # per block); the unfused h_* wrappers pass None and read rows.
        # `spill` marks vreg-passed inputs that are NOT yet in their
        # rows: careful-mode divergence bails write them back so the
        # scheduler's split machinery sees the exact pre-op stack.
        def _spill_tops(sp, top1, top2, spill):
            if not spill:
                return
            if top1 is not None:
                wrow4(sp - 1, top1)
            if top2 is not None:
                wrow4(sp - 2, top2)

        def brz_with(c, top1=None, spill=False):
            pc, sp = c[1], c[2]
            cond = top1[0] if top1 is not None else srow(slo, sp - 1)
            if optimistic:
                t0 = agree_nz(cond)
                new_pc = jnp.where(t0 == 0, a_r[pc], pc + 1)
                return keep(c, pc=new_pc, sp=sp - 1)
            t0 = scal(cond)
            agree = allsame(cond, t0)
            new_pc = jnp.where(t0 == 0, a_r[pc], pc + 1)

            def diverge():
                _spill_tops(sp, top1, None, spill)
                return keep(c, status=I32(ST_DIVERGED))

            return lax.cond(
                agree,
                lambda: keep(c, pc=new_pc, sp=sp - 1),
                diverge)

        def h_brz(c):
            return brz_with(c)

        def brnz_with(c, top1=None, top2=None, spill=False):
            pc, sp, ob = c[1], c[2], c[4]
            cond = top1[0] if top1 is not None else srow(slo, sp - 1)
            kept = top2 if top2 is not None else srow4(sp - 2)
            tgt, nkeep, pop_to = a_r[pc], b_r[pc], c_r[pc]
            tgt_sp = ob + pop_to
            if optimistic:
                t0 = agree_nz(cond)
                taken = t0 != 0

                @pl.when(taken & (nkeep == 1))
                def _():
                    wrow4(tgt_sp, kept)

                return lax.cond(
                    taken,
                    lambda: keep(c, pc=tgt, sp=tgt_sp + nkeep),
                    lambda: keep(c, pc=pc + 1, sp=sp - 1))
            t0 = scal(cond)
            agree = allsame(cond, t0)
            taken = t0 != 0

            @pl.when(agree & taken & (nkeep == 1))
            def _():
                wrow4(tgt_sp, kept)

            def diverge():
                _spill_tops(sp, top1, top2, spill)
                return keep(c, status=I32(ST_DIVERGED))

            return lax.cond(
                agree,
                lambda: lax.cond(
                    taken,
                    lambda: keep(c, pc=tgt, sp=tgt_sp + nkeep),
                    lambda: keep(c, pc=pc + 1, sp=sp - 1)),
                diverge)

        def h_brnz(c):
            return brnz_with(c)

        def br_table_with(c, top1=None, top2=None, spill=False):
            pc, sp, ob = c[1], c[2], c[4]
            idx = top1[0] if top1 is not None else srow(slo, sp - 1)
            kept = top2 if top2 is not None else srow4(sp - 2)
            i0 = agree_i32(idx) if optimistic else scal(idx)
            agree = True if optimistic else allsame(idx, i0)
            count_indirect(agree)
            base, n = a_r[pc], b_r[pc]
            ii = jnp.where(u_lt(n, i0), n, i0)
            e = (base + ii) * 3
            tgt, nkeep, pop_to = brt_r[e], brt_r[e + 1], brt_r[e + 2]
            tgt_sp = ob + pop_to

            @pl.when(agree & (nkeep == 1))
            def _():
                wrow4(tgt_sp, kept)

            def diverge():
                _spill_tops(sp, top1, top2, spill)
                return keep(c, status=I32(ST_DIVERGED))

            return lax.cond(
                agree,
                lambda: keep(c, pc=tgt, sp=tgt_sp + nkeep),
                diverge)

        def h_br_table(c):
            return br_table_with(c)

        def return_with(c, top1=None):
            pc, sp, fp, cd = c[1], c[2], c[3], c[5]
            nres = b_r[pc]
            res = top1 if top1 is not None else srow4(sp - 1)

            @pl.when(nres == 1)
            def _():
                wrow4(fp, res)

            new_sp = fp + nres
            rd = jnp.clip(cd - 1, 0, CD - 1)
            return lax.cond(
                cd == 0,
                lambda: keep(c, sp=new_sp, status=I32(ST_DONE)),
                lambda: keep(c, pc=frames_out[blk, 0, rd], sp=new_sp,
                             fp=frames_out[blk, 1, rd],
                             ob=frames_out[blk, 2, rd], cd=cd - 1))

        def h_return(c):
            return return_with(c)

        def _do_call(c, callee, sp_eff):
            pc, fp, ob, cd = c[1], c[3], c[4], c[5]
            nargs = fnpar_r[callee]
            nloc = fnloc_r[callee]
            ftop = ftop_r[callee]
            fp_new = sp_eff - nargs
            ob_new = fp_new + nloc
            ovf = (cd >= CD - 1) | (fp_new + ftop > D)

            def trap_fn():
                code = jnp.where(cd >= CD - 1,
                                 I32(int(ErrCode.CallStackExhausted)),
                                 I32(int(ErrCode.StackOverflow)))
                wrow(trapr, 0, full(code))
                return keep(c, status=I32(ST_TRAPPED_BASE) + code)

            def go_fn():
                slot = jnp.clip(cd, 0, CD - 1)
                frames_out[blk, 0, slot] = pc + 1
                frames_out[blk, 1, slot] = fp
                frames_out[blk, 2, slot] = ob
                zrow = full(0)
                z4 = (zrow, zrow, zrow, zrow) if simd else (zrow, zrow)
                for k in range(max_local_zeros):
                    @pl.when(k < (nloc - nargs))
                    def _(k=k):
                        wrow4(fp_new + nargs + k, z4)
                return keep(c, pc=fent_r[callee], sp=ob_new, fp=fp_new,
                            ob=ob_new, cd=cd + 1)

            return lax.cond(ovf, trap_fn, go_fn)

        def h_call(c):
            return _do_call(c, a_r[c[1]], c[2])

        def calli_with(c, top1=None, spill=False):
            pc, sp = c[1], c[2]
            idx = top1[0] if top1 is not None else srow(slo, sp - 1)
            i0 = agree_i32(idx) if optimistic else scal(idx)
            agree = True if optimistic else allsame(idx, i0)
            count_indirect(agree)
            tb_size, tb_base = b_r[pc], c_r[pc]
            oob = ~u_lt(i0, tb_size)  # unsigned; tb_size == 0 always oob
            h = tbl_r[jnp.clip(tb_base + jnp.clip(i0, 0,
                                                  jnp.maximum(tb_size - 1, 0)),
                               0, tsize - 1)]
            null = h == 0
            callee = jnp.clip(h - 1, 0, nf - 1)
            sig_bad = ftyp_r[callee] != a_r[pc]

            def bad():
                code = jnp.where(
                    oob, I32(int(ErrCode.UndefinedElement)),
                    jnp.where(null, I32(int(ErrCode.UninitializedElement)),
                              I32(int(ErrCode.IndirectCallTypeMismatch))))
                wrow(trapr, 0, full(code))
                return keep(c, status=I32(ST_TRAPPED_BASE) + code)

            def diverge():
                _spill_tops(sp, top1, None, spill)
                return keep(c, status=I32(ST_DIVERGED))

            return lax.cond(
                agree,
                lambda: lax.cond(
                    oob | null | sig_bad, bad,
                    lambda: _do_call(keep(c, sp=sp - 1), callee, sp - 1)),
                diverge)

        def h_call_indirect(c):
            return calli_with(c)

        def h_memsize(c):
            pc, sp, pages = c[1], c[2], c[6]
            wrow(slo, sp, full(pages))
            wrow(shi, sp, full(0))
            return keep(c, pc=pc + 1, sp=sp + 1)

        def h_memgrow(c):
            pc, sp, pages = c[1], c[2], c[6]
            delta = srow(slo, sp - 1)
            d0 = agree_i32(delta) if optimistic else scal(delta)
            agree = True if optimistic else allsame(delta, d0)
            legal = (d0 >= 0) & ((pages + d0) <= mem_pages_hard) & \
                ((pages + d0) >= pages)
            # legal but beyond the watermark plane: stop un-advanced so the
            # host re-executes on a bigger-plane engine (ST_REGROW)
            fits = legal & ((pages + d0) <= mem_pages_cap)
            res = jnp.where(legal, pages, I32(-1))
            settled = fits | ~legal

            @pl.when(agree & settled)
            def _():
                wrow(slo, sp - 1, full(res))
                wrow(shi, sp - 1, full(0))

            return lax.cond(
                agree,
                lambda: lax.cond(
                    settled,
                    lambda: keep(c, pc=pc + 1,
                                 pages=jnp.where(fits, pages + d0, pages)),
                    lambda: keep(c, status=I32(ST_REGROW))),
                lambda: keep(c, status=I32(ST_DIVERGED)))

        def h_trap(c):
            code = a_r[c[1]]
            wrow(trapr, 0, full(code))
            return keep(c, status=I32(ST_TRAPPED_BASE) + code)

        def h_memfill(c):
            if optimistic:
                return _opt_bulk_exit(c)
            pc, sp, pages = c[1], c[2], c[6]
            n = srow(slo, sp - 1)
            val = srow(slo, sp - 2)
            dst = srow(slo, sp - 3)
            mem_bytes = pages * I32(65536)
            end = dst + n
            oob = u_lt(end, dst) | u_lt(full(mem_bytes), end)
            go = (~oob) & (n != 0)
            fill_word = (val & 0xFF) * I32(0x01010101)
            # scan only the touched row window (a small fill must not pay
            # a whole-plane pass)
            dst_ok = jnp.where(go, dst, I32(0x7FFFFFFF))
            end_ok = jnp.where(go, end, I32(0))
            c_lo = jnp.clip(
                lax.div(lax.shift_right_logical(jnp.min(dst_ok), 2),
                        I32(GR)), 0, GATHER_CHUNKS)
            c_hi = jnp.clip(
                lax.div(lax.shift_right_logical(jnp.max(end_ok) + 3, 2)
                        + I32(GR - 1), I32(GR)), 0, GATHER_CHUNKS)

            def chunk(i, _):
                base = i * GR
                rows = srows(memr, base, GR)
                wi = base + riota(GR)
                byte0 = wi * 4
                mask = jnp.zeros_like(rows)
                for bpos in range(4):
                    ba = byte0 + bpos
                    inr = (~u_lt(ba, dst)) & u_lt(ba, end)
                    mask = mask | jnp.where(
                        inr, jnp.int32(lo_ops.BYTE_MASKS[bpos]), 0)
                write = (mask != 0) & go
                wrows(memr, base, GR, jnp.where(
                    write, (rows & ~mask) | (fill_word & mask), rows))
                return 0

            lax.fori_loop(c_lo, c_hi, chunk, 0)
            any_oob = jnp.any(oob)

            @pl.when(any_oob)
            def _():
                trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

            return lax.cond(
                any_oob,
                lambda: keep(c, pc=pc + 1, sp=sp - 3,
                             status=I32(ST_DIVERGED)),
                lambda: keep(c, pc=pc + 1, sp=sp - 3))

        def h_memcopy(c):
            if optimistic:
                return _opt_bulk_exit(c)
            # In-kernel memmove when every lane agrees on (src - dst): the
            # byte shift between source and destination is then a scalar,
            # so each destination row is two shifted source rows under the
            # same per-lane byte masks h_memfill uses.  Row order follows
            # the copy direction (backward when dst > src) for overlap
            # correctness — the same memmove discipline as the reference's
            # std::memmove in runDataCopy.  Per-lane divergent deltas (one
            # lane copying up, another down) hand off un-advanced.
            pc, sp, pages = c[1], c[2], c[6]
            n = srow(slo, sp - 1)
            src = srow(slo, sp - 2)
            dst = srow(slo, sp - 3)
            mem_bytes = pages * I32(65536)
            send = src + n
            dend = dst + n
            oob = u_lt(send, src) | u_lt(full(mem_bytes), send) | \
                u_lt(dend, dst) | u_lt(full(mem_bytes), dend)
            delta = src - dst
            live = (~oob) & (n != 0)
            # lanes with nothing to copy don't constrain the shift
            d_eff = jnp.where(live, delta, I32(0x7FFFFFFF))
            d0 = jnp.min(d_eff)
            agree = jnp.all(jnp.where(live, delta, d0) == d0)
            any_live = jnp.any(live)
            d0 = jnp.where(any_live, d0, I32(0))

            def go():
                sm = d0 & 3
                qv = lax.shift_right_arithmetic(d0 - sm, 2)
                shB = sm * 8
                inv = (32 - shB) & 31
                hi_or = jnp.where(shB == 0, 0, -1)
                dst_ok = jnp.where(live, dst, I32(0x7FFFFFFF))
                dend_ok = jnp.where(live, dend, I32(0))
                row_lo = lax.shift_right_logical(jnp.min(dst_ok), 2)
                row_hi = lax.shift_right_logical(jnp.max(dend_ok) + 3, 2)
                row_lo = jnp.minimum(row_lo, I32(W))
                row_hi = jnp.minimum(row_hi, I32(W))
                nrows = jnp.maximum(row_hi - row_lo, 0)
                fwd = d0 >= 0

                def body(i, _):
                    r = jnp.where(fwd, row_lo + i, row_hi - 1 - i)
                    m0 = srow(memr, jnp.clip(r + qv, 0, W - 1))
                    m1 = srow(memr, jnp.clip(r + qv + 1, 0, W - 1))
                    val = lax.shift_right_logical(m0, shB) | \
                        (lax.shift_left(m1, inv) & hi_or)
                    mask = full(0)
                    for bpos in range(4):
                        ba = full(r * 4 + bpos)
                        inr = (~u_lt(ba, dst)) & u_lt(ba, dend)
                        mask = mask | jnp.where(
                            inr & live, jnp.int32(lo_ops.BYTE_MASKS[bpos]),
                            0)
                    old = srow(memr, jnp.clip(r, 0, W - 1))
                    wrow(memr, jnp.clip(r, 0, W - 1),
                         jnp.where(mask != 0, (old & ~mask) | (val & mask),
                                   old))
                    return 0

                lax.fori_loop(0, nrows, body, 0)
                any_oob = jnp.any(oob)

                @pl.when(any_oob)
                def _():
                    trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

                return lax.cond(
                    any_oob,
                    lambda: keep(c, pc=pc + 1, sp=sp - 3,
                                 status=I32(ST_DIVERGED)),
                    lambda: keep(c, pc=pc + 1, sp=sp - 3))

            return lax.cond(agree, go,
                            lambda: keep(c, status=I32(ST_DIVERGED)))

        def h_hostcall(c):
            # park the block; the host serves every lane then re-arms at
            # pc+1 (the stub RETURN) with sp = opbase + nresults
            return keep(c, status=I32(ST_HOSTCALL))

        # ---- memory access ------------------------------------------
        # NOTE predication discipline: a `lax.cond` whose branches
        # RETURN vectors is discharged into execute-both-and-select, so
        # a "rare" divergent-gather branch would run its whole-memory
        # scan on every access.  Vector results below therefore stay
        # inside the region that computes them (`pl.when`, or a
        # `lax.cond` branch that writes them to their rows) and only
        # the scalar carry comes out of a lax.cond: such a cond is one
        # scf.if whose untaken side costs nothing, which the dispatch
        # tree and the window's miss and exit regions (_opt_window,
        # _opt_leave) rely on.

        def _gather_word(widx, row_lo, row_hi):
            """Per-lane word gather from [W, Lblk] by chunked
            compare-reduce over the touched row window only."""
            c_lo = jnp.clip(lax.div(row_lo, I32(GR)), 0, GATHER_CHUNKS)
            c_hi = jnp.clip(lax.div(row_hi + I32(GR - 1), I32(GR)),
                            0, GATHER_CHUNKS)

            def chunk(i, acc):
                base = i * GR
                rows = srows(memr, base, GR)
                wi = base + riota(GR)
                return acc + rsum(jnp.where(wi == widx, rows, 0))

            return lax.fori_loop(c_lo, c_hi, chunk, full(0))

        def _load_put(c, mw0, mw1, mw2, shB):
            """The loaded cell, from the three words it may span, into
            the row of the address it replaces."""
            pc, sp = c[1], c[2]
            nbytes, flags = b_r[pc], c_r[pc]
            inv = (32 - shB) & 31
            hi_or = jnp.where(shB == 0, 0, -1)
            raw_lo = lax.shift_right_logical(mw0, shB) | \
                (lax.shift_left(mw1, inv) & hi_or)
            raw_hi = lax.shift_right_logical(mw1, shB) | \
                (lax.shift_left(mw2, inv) & hi_or)
            signed = (flags & 1) != 0
            is64 = (flags & 2) != 0
            b1 = nbytes == 1
            b2_ = nbytes == 2
            lraw = jnp.where(b1, raw_lo & 0xFF,
                             jnp.where(b2_, raw_lo & 0xFFFF, raw_lo))
            lsext = jnp.where(
                b1,
                lax.shift_right_arithmetic(lax.shift_left(raw_lo, 24), 24),
                jnp.where(
                    b2_,
                    lax.shift_right_arithmetic(lax.shift_left(raw_lo, 16),
                                               16),
                    raw_lo))
            ll = jnp.where(signed, lsext, lraw)
            lh = jnp.where(
                is64,
                jnp.where(nbytes == 8, raw_hi,
                          jnp.where(signed,
                                    lax.shift_right_arithmetic(ll, 31),
                                    full(0))),
                full(0))
            wrow(slo, sp - 1, ll)
            wrow(shi, sp - 1, lh)

        def _load_finish(c, mw0, mw1, mw2, shB, oob, any_oob):
            _load_put(c, mw0, mw1, mw2, shB)

            @pl.when(any_oob)
            def _():
                trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

        def h_load(c):
            pc, sp, pages = c[1], c[2], c[6]
            off, nbytes = a_r[pc], b_r[pc]
            addr = srow(slo, sp - 1)
            ea = addr + off
            carry_ = u_lt(ea, addr) | u_lt(ea, full(off))
            mem_bytes = pages * I32(65536)
            end = ea + nbytes
            oob = carry_ | u_lt(end, ea) | u_lt(full(mem_bytes), end)
            if optimistic:
                # lane-0 address decision; the canary covers widx/shB/oob
                # agreement at once (all derive from ea and scalars)
                ea0 = agree_i32(ea)
                oob0 = jnp.where(oob, I32(1), I32(0))[0, 0] != 0
                u = jnp.clip(lax.shift_right_logical(ea0, 2), 0, W - 1)
                shB0 = (ea0 & 3) * 8
                _load_finish(c, srow(memr, u),
                             srow(memr, jnp.minimum(u + 1, W - 1)),
                             srow(memr, jnp.minimum(u + 2, W - 1)),
                             shB0, oob, oob0)
                return lax.cond(
                    oob0,
                    lambda: keep(c, pc=pc + 1, status=I32(ST_DIVERGED)),
                    lambda: keep(c, pc=pc + 1))
            widx = jnp.clip(lax.shift_right_logical(ea, 2), 0, W - 1)
            shB = (ea & 3) * 8
            u0 = scal(widx)
            uni = allsame(widx, u0) & allsame(shB, scal(shB))
            commit = jnp.bool_(True) if gatherable else uni
            any_oob = jnp.any(oob)

            @pl.when(uni)
            def _():
                u = jnp.clip(u0, 0, W - 1)
                _load_finish(c, srow(memr, u),
                             srow(memr, jnp.clip(u + 1, 0, W - 1)),
                             srow(memr, jnp.clip(u + 2, 0, W - 1)),
                             shB, oob, any_oob)

            if gatherable:
                @pl.when(~uni)
                def _():
                    r_lo = jnp.min(widx)
                    r_hi = jnp.max(widx) + 3
                    w1 = jnp.clip(widx + 1, 0, W - 1)
                    w2 = jnp.clip(widx + 2, 0, W - 1)
                    _load_finish(c, _gather_word(widx, r_lo, r_hi),
                                 _gather_word(w1, r_lo, r_hi),
                                 _gather_word(w2, r_lo, r_hi),
                                 shB, oob, any_oob)

            return lax.cond(
                commit,
                lambda: lax.cond(
                    any_oob,
                    lambda: keep(c, pc=pc + 1, status=I32(ST_DIVERGED)),
                    lambda: keep(c, pc=pc + 1)),
                lambda: keep(c, status=I32(ST_DIVERGED)))

        def h_store(c):
            pc, sp, pages = c[1], c[2], c[6]
            off, nbytes = a_r[pc], b_r[pc]
            vl, vh = srow(slo, sp - 1), srow(shi, sp - 1)
            addr = srow(slo, sp - 2)
            ea = addr + off
            carry_ = u_lt(ea, addr) | u_lt(ea, full(off))
            mem_bytes = pages * I32(65536)
            end = ea + nbytes
            oob = carry_ | u_lt(end, ea) | u_lt(full(mem_bytes), end)
            ok = ~oob
            if optimistic:
                ea0 = agree_i32(ea)
                oob0 = jnp.where(oob, I32(1), I32(0))[0, 0] != 0
                u = jnp.clip(lax.shift_right_logical(ea0, 2), 0, W - 1)
                shB0 = (ea0 & 3) * 8
                b1 = nbytes == 1
                b2_ = nbytes == 2
                # scalar byte masks (address is block-uniform by
                # assumption); value planes stay per-lane vectors
                m_lo = jnp.where(b1, I32(0xFF),
                                 jnp.where(b2_, I32(0xFFFF), I32(-1)))
                m_hi = jnp.where(nbytes == 8, I32(-1), I32(0))
                for k, (m, v) in enumerate(
                        shifted_store_triples(m_lo, m_hi, vl, vh, shB0)):
                    w = jnp.minimum(u + k, W - 1)

                    @pl.when(m != 0)
                    def _(m=m, v=v, w=w):
                        cur = srow(memr, w)
                        wrow(memr, w,
                             jnp.where(ok, (cur & ~m) | (v & m), cur))

                @pl.when(oob0)
                def _():
                    trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

                return lax.cond(
                    oob0,
                    lambda: keep(c, pc=pc + 1, sp=sp - 2,
                                 status=I32(ST_DIVERGED)),
                    lambda: keep(c, pc=pc + 1, sp=sp - 2))
            widx = jnp.clip(lax.shift_right_logical(ea, 2), 0, W - 1)
            shB = (ea & 3) * 8
            b1 = nbytes == 1
            b2_ = nbytes == 2
            full_lo = jnp.where(b1, 0xFF, jnp.where(b2_, 0xFFFF, I32(-1)))
            full_hi = jnp.where(nbytes == 8, I32(-1), 0)
            full_lo = jnp.broadcast_to(full_lo, ROW)
            full_hi = jnp.broadcast_to(full_hi, ROW)
            ((sm0, sv0), (sm1, sv1), (sm2, sv2)) = shifted_store_triples(
                full_lo, full_hi, vl, vh, shB)
            u0 = scal(widx)
            uni = allsame(widx, u0) & allsame(shB, scal(shB))
            commit = jnp.bool_(True) if gatherable else uni
            any_oob = jnp.any(oob)

            @pl.when(uni)
            def _():
                for k, (m, v) in enumerate(((sm0, sv0), (sm1, sv1),
                                            (sm2, sv2))):
                    w = jnp.clip(u0 + k, 0, W - 1)

                    @pl.when(jnp.any(m != 0))
                    def _(m=m, v=v, w=w):
                        cur = srow(memr, w)
                        wrow(memr, w,
                             jnp.where(ok & (m != 0), (cur & ~m) | (v & m),
                                       cur))

            if gatherable:
                @pl.when(~uni)
                def _():
                    c_lo = jnp.clip(lax.div(jnp.min(widx), I32(GR)),
                                    0, GATHER_CHUNKS)
                    c_hi = jnp.clip(
                        lax.div(jnp.max(widx) + I32(2 + GR), I32(GR)),
                        0, GATHER_CHUNKS)
                    for k, (m, v) in enumerate(((sm0, sv0), (sm1, sv1),
                                                (sm2, sv2))):
                        wk = jnp.clip(widx + k, 0, W - 1)

                        def chunk(i, _, m=m, v=v, wk=wk):
                            base = i * GR
                            rows = srows(memr, base, GR)
                            wi = base + riota(GR)
                            hit = (wi == wk) & (ok & (m != 0))
                            wrows(memr, base, GR, jnp.where(
                                hit, (rows & ~m) | (v & m), rows))
                            return 0

                        lax.fori_loop(c_lo, c_hi, chunk, 0)

            @pl.when(commit & any_oob)
            def _():
                trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

            return lax.cond(
                commit,
                lambda: lax.cond(
                    any_oob,
                    lambda: keep(c, pc=pc + 1, sp=sp - 2,
                                 status=I32(ST_DIVERGED)),
                    lambda: keep(c, pc=pc + 1, sp=sp - 2)),
                lambda: keep(c, status=I32(ST_DIVERGED)))

        # ---- mem_hbm mode: window-cached memory handlers --------------
        # The memory plane stays HBM-resident; h_load/h_store/h_memfill/
        # h_memcopy are shadowed below with window-cache versions.  The
        # invariant maintained by _win_select is that at most ONE way
        # holds any given plane row (a fetch overlapping the other way
        # writes that way back and invalidates it first), so hit
        # priority and flush order can never replay stale rows.
        if mem_hbm:
            SENT = I32(-(1 << 30))  # "window invalid" base sentinel

            def a8(v):
                # every HBM row offset here is 8-aligned by construction
                # (window bases are align8'd; W, CW, GR are multiples of
                # 8) but Mosaic needs the divisibility stated to slice
                # the (8,128)-tiled HBM memref at a dynamic row
                return pl.multiple_of(v, 8)

            # wcnt counts the window's DMAs of this launch: [0] fills,
            # [1] dirty write-backs.  Every one of them already sits in
            # a branch of its own, so the count costs the converged
            # path nothing and adds no region (one more nesting level
            # kills the chip's compiler).  wacc counts the accesses the
            # launch resolved against the window, hits and misses: one
            # vreg in VMEM that goes up by one at each, as `turns` does
            # at each dispatch (a carry scalar or an SMEM cell on the
            # converged path is not free, PR 29).
            wcnt[0] = I32(0)
            wcnt[1] = I32(0)
            wacc[...] = jnp.zeros_like(wacc)

            # the two ways are the halves of one scratch: way k holds
            # rows [k * CW, (k + 1) * CW), so a resident row is one
            # dynamic row of `mwin` and the DMAs move a half
            def _way(k):
                return mwin.at[pl.ds(k * CW, CW)]

            def _wb_way(k, wb):
                cp = dma(6 + k, _way(k),
                         lsliceR(mem_out, a8(jnp.clip(wb, 0, W - CW)), CW))
                cp.start()
                cp.wait()
                wcnt[1] = wcnt[1] + 1

            def _fill_way(k, nb):
                cp = dma(6 + k, lsliceR(mem_out, a8(nb), CW), _way(k))
                cp.start()
                cp.wait()
                wcnt[0] = wcnt[0] + 1

            def _win_select(wfs, rlo, rhi, en):
                """Make rows [rlo, rhi] resident in one way; returns
                (way, wfs').  All DMAs are predicated on `en`; callers
                must have checked (rhi - align8(rlo)) < CW.  INVARIANT
                SYNC: _opt_window holds the optimistic kernel's copy of
                these formulas, the hit predicates on its hot path and
                the rest in its miss()."""
                wb0, wd0, wb1, wd1, mru = wfs
                wacc[...] = wacc[...] + jnp.where(en, I32(1), I32(0))
                hit0 = (rlo >= wb0) & (rhi < wb0 + CW)
                hit1 = (rlo >= wb1) & (rhi < wb1 + CW)
                nb = jnp.clip(rlo - lax.rem(rlo, 8), 0, W - CW)
                miss = en & ~(hit0 | hit1)
                vic1 = mru == 0
                repl0 = miss & ~vic1
                repl1 = miss & vic1
                # the single-resident-copy invariant: evict the OTHER way
                # when the incoming window overlaps it
                ov0 = repl1 & (wb0 < nb + CW) & (nb < wb0 + CW)
                ov1 = repl0 & (wb1 < nb + CW) & (nb < wb1 + CW)

                @pl.when(ov0 & (wd0 != 0))
                def _():
                    _wb_way(0, wb0)

                @pl.when(ov1 & (wd1 != 0))
                def _():
                    _wb_way(1, wb1)

                @pl.when(repl0 & (wd0 != 0))
                def _():
                    _wb_way(0, wb0)

                @pl.when(repl0)
                def _():
                    _fill_way(0, nb)

                @pl.when(repl1 & (wd1 != 0))
                def _():
                    _wb_way(1, wb1)

                @pl.when(repl1)
                def _():
                    _fill_way(1, nb)

                wb0n = jnp.where(repl0, nb, jnp.where(ov0, SENT, wb0))
                wd0n = jnp.where(repl0 | ov0, I32(0), wd0)
                wb1n = jnp.where(repl1, nb, jnp.where(ov1, SENT, wb1))
                wd1n = jnp.where(repl1 | ov1, I32(0), wd1)
                way = jnp.where(hit0, I32(0),
                                jnp.where(hit1, I32(1),
                                          jnp.where(vic1, I32(1), I32(0))))
                mrun = jnp.where(en, way, mru)
                return way, (wb0n, wd0n, wb1n, wd1n, mrun)

            def _win_flush(wfs):
                """Write back both dirty ways and invalidate (used before
                chunk-streaming ops that bypass the cache)."""
                wb0, wd0, wb1, wd1, _ = wfs

                @pl.when(wd0 != 0)
                def _():
                    _wb_way(0, wb0)

                @pl.when(wd1 != 0)
                def _():
                    _wb_way(1, wb1)

                return (SENT, I32(0), SENT, I32(0), I32(0))

            def win_at(way, wfs):
                """Where the resident way lies: (its first row of `mwin`,
                the plane row that one holds)."""
                return way * CW, jnp.where(way == 0, wfs[0], wfs[2])

            def _win_row(win, r):
                return win[0] + jnp.clip(r - win[1], 0, CW - 1)

            def win_read_row(win, r):
                return srow(mwin, _win_row(win, r))

            def win_write_row(win, r, v):
                wrow(mwin, _win_row(win, r), v)

            def _win_gather(win, wk):
                """Per-lane word gather from the selected resident way."""
                rel = wk - win[1]
                wi = riota(CW)
                return rsum(jnp.where(wi == rel,
                                      srows(mwin, a8(win[0]), CW), 0))

            def win_store_words(win, u, shB, triples, nbytes):
                """Merge a store's words into the resident way.  Which
                of its up-to-three words a store of `nbytes` touches
                follows from its width but for one: the first (two of
                an i64) always, the next where the byte shift spills
                into it, the rest never, and a word's mask is zero
                exactly where it is not touched.  So only that one
                word's write needs a region of its own."""
                n_whole = 2 if nbytes == 8 else 1
                fits_upto = 32 * n_whole - 8 * nbytes   # shB that fits
                for k, (m, v) in enumerate(triples):
                    w = jnp.minimum(u + k, W - 1)

                    def put(m=m, v=v, w=w):
                        cur = win_read_row(win, w)
                        win_write_row(win, w, (cur & ~m) | (v & m))

                    if k < n_whole:
                        put()
                    elif k == n_whole and fits_upto < 24:
                        pl.when(shB > fits_upto)(put)

            def _wfs_of(c):
                return (c[8], c[9], c[10], c[11], c[12])

            def _keep_win(c, wfs, **kw):
                return keep(c, wb0=wfs[0], wd0=wfs[1], wb1=wfs[2],
                            wd1=wfs[3], mru=wfs[4], **kw)

            def _win_dirtied(way, wfs, en=True):
                """wfs after a store into `way` (where `en`)."""
                return (wfs[0],
                        jnp.where(en & (way == 0), I32(1), wfs[1]),
                        wfs[2],
                        jnp.where(en & (way == 1), I32(1), wfs[3]),
                        wfs[4])

            def _opt_window(c, u, rhi):
                """Optimistic scalar window select: resolve [u, rhi] to
                a resident way with all decisions scalar.  An access
                that hits computes hit0, hit1 and the way, and walks one
                region: everything a miss needs lies in that region's
                cold branch.  A dirty eviction there is a commit point:
                validate the canary first, roll back on a pending stale
                decision, snapshot otherwise (`c` is the carry that
                snapshot pairs with the planes: positioned at THIS
                access).  Returns (dirty, way, wfs', ls') where wfs' has
                the new window fields with mru=way and ls' is c[0] after
                a snapshot; callers touch no ref and return
                rolled_carry() when dirty (_opt_leave).

                INVARIANT SYNC: the hit predicates here, and in miss()
                the victim choice, the overlap eviction (single-
                resident-copy rule) and the wb/wd/mru update formulas,
                MUST match _win_select above: the careful kernel runs
                that one against the same window state this one leaves
                behind."""
                wb0, wd0, wb1, wd1, mru = _wfs_of(c)
                hit0 = (u >= wb0) & (rhi < wb0 + CW)
                hit1 = (u >= wb1) & (rhi < wb1 + CW)
                wacc[...] = wacc[...] + 1

                def hit():
                    return (I32(0), wb0, wd0, wb1, wd1,
                            jnp.where(hit0, I32(0), I32(1)),
                            c[IDX["ls"]])

                def miss():
                    vic1 = mru == 0
                    repl0, repl1 = ~vic1, vic1
                    nb = jnp.clip(u - lax.rem(u, 8), 0, W - CW)
                    ov0 = repl1 & (wb0 < nb + CW) & (nb < wb0 + CW)
                    ov1 = repl0 & (wb1 < nb + CW) & (nb < wb1 + CW)
                    needs_wb = ((repl0 | ov0) & (wd0 != 0)) | \
                        ((repl1 | ov1) & (wd1 != 0))

                    @pl.when(needs_wb)
                    def _():
                        flag[0] = jnp.any(
                            srow(canr, 0) != 0).astype(jnp.int32)

                    dirty = needs_wb & (flag[0] != 0)
                    flushed = needs_wb & ~dirty

                    @pl.when(dirty)
                    def _():
                        do_restore()

                    # publish BOTH dirty ways before the snapshot so the
                    # HBM plane IS the snapshot's memory state: otherwise
                    # a later rollback would discard the non-victim way's
                    # validated stores (same discipline as the periodic
                    # commit in body())
                    @pl.when(flushed & (wd0 != 0))
                    def _():
                        _wb_way(0, wb0)

                    @pl.when(flushed & (wd1 != 0))
                    def _():
                        _wb_way(1, wb1)

                    @pl.when(flushed)
                    def _():
                        do_snapshot(c)

                    @pl.when(~dirty & repl0)
                    def _():
                        _fill_way(0, nb)

                    @pl.when(~dirty & repl1)
                    def _():
                        _fill_way(1, nb)

                    return (
                        jnp.where(dirty, I32(1), I32(0)),
                        jnp.where(repl0, nb, jnp.where(ov0, SENT, wb0)),
                        jnp.where(flushed | repl0 | ov0, I32(0), wd0),
                        jnp.where(repl1, nb, jnp.where(ov1, SENT, wb1)),
                        jnp.where(flushed | repl1 | ov1, I32(0), wd1),
                        jnp.where(vic1, I32(1), I32(0)),
                        jnp.where(flushed, c[0], c[IDX["ls"]]))

                dirty, wb0n, wd0n, wb1n, wd1n, way, lsn = lax.cond(
                    hit0 | hit1, hit, miss)
                return dirty != 0, way, (wb0n, wd0n, wb1n, wd1n, way), lsn

            def _opt_leave(dirty, oob0, on_oob, go):
                """The one exit of a windowed access: `go` is what
                follows it, one region below it, and the two rare
                outcomes share the other branch: the rollback
                _opt_window made (dirty) and lane 0 out of bounds."""
                return lax.cond(
                    dirty | oob0,
                    lambda: lax.cond(dirty, rolled_carry, on_oob),
                    go)

            def _opt_ls_prolog(c, addr_row, nb_extra):
                """Shared optimistic load/store address computation."""
                pc, pages = c[1], c[6]
                off, nbytes = a_r[pc], b_r[pc]
                ea = addr_row + off
                carry_ = u_lt(ea, addr_row) | u_lt(ea, full(off))
                mem_bytes = pages * I32(65536)
                end = ea + nbytes
                oob = carry_ | u_lt(end, ea) | u_lt(full(mem_bytes), end)
                ea0 = agree_i32(ea)
                oob0 = jnp.where(oob, I32(1), I32(0))[0, 0] != 0
                u = jnp.clip(lax.shift_right_logical(ea0, 2), 0, W - 1)
                shB0 = (ea0 & 3) * 8
                rhi = jnp.minimum(u + nb_extra, W - 1)
                return oob, oob0, u, shB0, rhi, nbytes

            def _opt_ls_scalar(c, addr_row, nbytes, want_rows):
                """Reduction-free load/store prolog (opt_addr_prolog
                plus the window row bound the hbm handlers need)."""
                pc, pages = c[1], c[6]
                off = a_r[pc]
                ea = addr_row + off
                _ea0, oob0, u, shB0 = opt_addr_prolog(
                    ea, off, nbytes, pages)
                rhi = jnp.minimum(u + want_rows, W - 1)
                return ea, oob0, u, shB0, rhi

            def _trap_oob_lanes(c, ea, nbytes):
                """Per-lane OOB trap plane write, only materialized on
                the (rare) lane-0-oob path."""
                pages = c[6]
                addr = ea - a_r[c[1]]
                carry_ = u_lt(ea, addr) | u_lt(ea, full(a_r[c[1]]))
                end = ea + nbytes
                oob = carry_ | u_lt(end, ea) | \
                    u_lt(full(pages * I32(65536)), end)
                trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

            def _mk_load_wd(is64):
                nbytes = 8 if is64 else 4
                want = 2 if is64 else 1

                def h(c):
                    pc, sp = c[1], c[2]
                    ea, oob0, u, shB0, rhi = _opt_ls_scalar(
                        c, srow(slo, sp - 1), nbytes, want)
                    dirty, way, wfs2, ls2 = _opt_window(c, u, rhi)
                    c2 = _keep_win(c, wfs2, ls=ls2)

                    def load():
                        win = win_at(way, wfs2)
                        inv = (32 - shB0) & 31
                        hi_or = jnp.where(shB0 == 0, 0, -1)
                        m0 = win_read_row(win, u)
                        m1 = win_read_row(win, jnp.minimum(u + 1, W - 1))
                        ll = lax.shift_right_logical(m0, shB0) | \
                            (lax.shift_left(m1, inv) & hi_or)
                        wrow(slo, sp - 1, ll)
                        if is64:
                            m2 = win_read_row(win,
                                              jnp.minimum(u + 2, W - 1))
                            lh = lax.shift_right_logical(m1, shB0) | \
                                (lax.shift_left(m2, inv) & hi_or)
                            wrow(shi, sp - 1, lh)
                        else:
                            wrow(shi, sp - 1, full(0))

                    def go():
                        load()
                        return keep(c2, pc=pc + 1)

                    def oob():
                        load()
                        _trap_oob_lanes(c, ea, nbytes)
                        return keep(c2, pc=pc + 1,
                                    status=I32(ST_DIVERGED))

                    return _opt_leave(dirty, oob0, oob, go)
                return h

            def _mk_store_wd(is64):
                nbytes = 8 if is64 else 4
                want = 2 if is64 else 1

                def h(c):
                    pc, sp = c[1], c[2]
                    vl, vh = srow(slo, sp - 1), srow(shi, sp - 1)
                    ea, oob0, u, shB0, rhi = _opt_ls_scalar(
                        c, srow(slo, sp - 2), nbytes, want)
                    dirty, way, wfs2, ls2 = _opt_window(c, u, rhi)
                    m_lo = I32(-1)
                    m_hi = I32(-1) if is64 else I32(0)
                    triples = shifted_store_triples(m_lo, m_hi, vl, vh,
                                                    shB0)
                    c2 = _keep_win(c, _win_dirtied(way, wfs2), ls=ls2)

                    def go():
                        # common path: no lane traps assumed — write
                        # unmasked (a lane disagreeing on the address is
                        # already canary-marked and will roll back)
                        win_store_words(win_at(way, wfs2), u, shB0,
                                        triples, nbytes)
                        return keep(c2, pc=pc + 1, sp=sp - 2)

                    def oob():
                        _trap_oob_lanes(c, ea, nbytes)
                        return keep(c2, pc=pc + 1, sp=sp - 2,
                                    status=I32(ST_DIVERGED))

                    return _opt_leave(dirty, oob0, oob, go)
                return h

            h_load_w = _mk_load_wd(False)
            h_load_d = _mk_load_wd(True)
            h_store_w = _mk_store_wd(False)
            h_store_d = _mk_store_wd(True)

            def h_load(c):
                if optimistic:
                    pc, sp = c[1], c[2]
                    oob, oob0, u, shB0, rhi, _nb = _opt_ls_prolog(
                        c, srow(slo, sp - 1), 2)
                    dirty, way, wfs2, ls2 = _opt_window(c, u, rhi)
                    c2 = _keep_win(c, wfs2, ls=ls2)

                    def load():
                        win = win_at(way, wfs2)
                        _load_put(
                            c, win_read_row(win, u),
                            win_read_row(win, jnp.minimum(u + 1, W - 1)),
                            win_read_row(win, jnp.minimum(u + 2, W - 1)),
                            shB0)

                    def go():
                        load()
                        return keep(c2, pc=pc + 1)

                    def oob_():
                        load()
                        trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))
                        return keep(c2, pc=pc + 1,
                                    status=I32(ST_DIVERGED))

                    return _opt_leave(dirty, oob0, oob_, go)
                pc, sp, pages = c[1], c[2], c[6]
                off, nbytes = a_r[pc], b_r[pc]
                addr = srow(slo, sp - 1)
                ea = addr + off
                carry_ = u_lt(ea, addr) | u_lt(ea, full(off))
                mem_bytes = pages * I32(65536)
                end = ea + nbytes
                oob = carry_ | u_lt(end, ea) | u_lt(full(mem_bytes), end)
                widx = jnp.clip(lax.shift_right_logical(ea, 2), 0, W - 1)
                shB = (ea & 3) * 8
                rlo = jnp.min(widx)
                rhi = jnp.minimum(jnp.max(widx) + 2, W - 1)
                fits = (rhi - (rlo - lax.rem(rlo, 8))) < CW
                any_oob = jnp.any(oob)
                way, wfs = _win_select(_wfs_of(c), rlo, rhi, fits)
                u0 = scal(widx)
                uni = allsame(widx, u0) & allsame(shB, scal(shB))

                win = win_at(way, wfs)

                @pl.when(fits & uni)
                def _():
                    _load_finish(
                        c, win_read_row(win, u0),
                        win_read_row(win, jnp.minimum(u0 + 1, W - 1)),
                        win_read_row(win, jnp.minimum(u0 + 2, W - 1)),
                        shB, oob, any_oob)

                @pl.when(fits & ~uni)
                def _():
                    w1 = jnp.clip(widx + 1, 0, W - 1)
                    w2 = jnp.clip(widx + 2, 0, W - 1)
                    _load_finish(c, _win_gather(win, widx),
                                 _win_gather(win, w1),
                                 _win_gather(win, w2),
                                 shB, oob, any_oob)

                c = _keep_win(c, wfs)
                return lax.cond(
                    fits,
                    lambda: lax.cond(
                        any_oob,
                        lambda: keep(c, pc=pc + 1, status=I32(ST_DIVERGED)),
                        lambda: keep(c, pc=pc + 1)),
                    lambda: keep(c, status=I32(ST_DIVERGED)))

            def h_store(c):
                if optimistic:
                    pc, sp = c[1], c[2]
                    vl, vh = srow(slo, sp - 1), srow(shi, sp - 1)
                    oob, oob0, u, shB0, rhi, nbytes = _opt_ls_prolog(
                        c, srow(slo, sp - 2), 2)
                    ok = ~oob
                    dirty, way, wfs2, ls2 = _opt_window(c, u, rhi)
                    b1 = nbytes == 1
                    b2_ = nbytes == 2
                    m_lo = jnp.where(b1, I32(0xFF),
                                     jnp.where(b2_, I32(0xFFFF), I32(-1)))
                    m_hi = jnp.where(nbytes == 8, I32(-1), I32(0))
                    triples = shifted_store_triples(m_lo, m_hi, vl, vh,
                                                    shB0)
                    c2 = _keep_win(c, _win_dirtied(way, wfs2), ls=ls2)

                    def store():
                        win = win_at(way, wfs2)
                        for k, (m, v) in enumerate(triples):
                            w = jnp.minimum(u + k, W - 1)

                            @pl.when(m != 0)
                            def _(m=m, v=v, w=w):
                                cur = win_read_row(win, w)
                                win_write_row(
                                    win, w,
                                    jnp.where(ok, (cur & ~m) | (v & m),
                                              cur))

                    def go():
                        store()
                        return keep(c2, pc=pc + 1, sp=sp - 2)

                    def oob_():
                        store()
                        trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))
                        return keep(c2, pc=pc + 1, sp=sp - 2,
                                    status=I32(ST_DIVERGED))

                    return _opt_leave(dirty, oob0, oob_, go)
                pc, sp, pages = c[1], c[2], c[6]
                off, nbytes = a_r[pc], b_r[pc]
                vl, vh = srow(slo, sp - 1), srow(shi, sp - 1)
                addr = srow(slo, sp - 2)
                ea = addr + off
                carry_ = u_lt(ea, addr) | u_lt(ea, full(off))
                mem_bytes = pages * I32(65536)
                end = ea + nbytes
                oob = carry_ | u_lt(end, ea) | u_lt(full(mem_bytes), end)
                ok = ~oob
                widx = jnp.clip(lax.shift_right_logical(ea, 2), 0, W - 1)
                shB = (ea & 3) * 8
                b1 = nbytes == 1
                b2_ = nbytes == 2
                full_lo = jnp.where(b1, 0xFF,
                                    jnp.where(b2_, 0xFFFF, I32(-1)))
                full_hi = jnp.where(nbytes == 8, I32(-1), 0)
                full_lo = jnp.broadcast_to(full_lo, ROW)
                full_hi = jnp.broadcast_to(full_hi, ROW)
                ((sm0, sv0), (sm1, sv1), (sm2, sv2)) = \
                    shifted_store_triples(full_lo, full_hi, vl, vh, shB)
                rlo = jnp.min(widx)
                rhi = jnp.minimum(jnp.max(widx) + 2, W - 1)
                fits = (rhi - (rlo - lax.rem(rlo, 8))) < CW
                any_oob = jnp.any(oob)
                way, wfs = _win_select(_wfs_of(c), rlo, rhi, fits)
                u0 = scal(widx)
                uni = allsame(widx, u0) & allsame(shB, scal(shB))

                win = win_at(way, wfs)

                @pl.when(fits & uni)
                def _():
                    for k, (m, v) in enumerate(((sm0, sv0), (sm1, sv1),
                                                (sm2, sv2))):
                        w = jnp.minimum(u0 + k, W - 1)

                        @pl.when(jnp.any(m != 0))
                        def _(m=m, v=v, w=w):
                            cur = win_read_row(win, w)
                            win_write_row(
                                win, w,
                                jnp.where(ok & (m != 0),
                                          (cur & ~m) | (v & m), cur))

                @pl.when(fits & ~uni)
                def _():
                    wi = riota(CW) + win[1]
                    for k, (m, v) in enumerate(((sm0, sv0), (sm1, sv1),
                                                (sm2, sv2))):
                        wk = jnp.clip(widx + k, 0, W - 1)
                        hit = (wi == wk) & (ok & (m != 0))
                        cur = srows(mwin, a8(win[0]), CW)
                        wrows(mwin, a8(win[0]), CW, jnp.where(
                            hit, (cur & ~m) | (v & m), cur))

                c = _keep_win(c, _win_dirtied(way, wfs, fits))

                @pl.when(fits & any_oob)
                def _():
                    trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

                return lax.cond(
                    fits,
                    lambda: lax.cond(
                        any_oob,
                        lambda: keep(c, pc=pc + 1, sp=sp - 2,
                                     status=I32(ST_DIVERGED)),
                        lambda: keep(c, pc=pc + 1, sp=sp - 2)),
                    lambda: keep(c, status=I32(ST_DIVERGED)))

            def h_memfill(c):
                if optimistic:
                    return _opt_bulk_exit(c)
                pc, sp, pages = c[1], c[2], c[6]
                n = srow(slo, sp - 1)
                val = srow(slo, sp - 2)
                dst = srow(slo, sp - 3)
                mem_bytes = pages * I32(65536)
                end = dst + n
                oob = u_lt(end, dst) | u_lt(full(mem_bytes), end)
                go = (~oob) & (n != 0)
                fill_word = (val & 0xFF) * I32(0x01010101)
                dst_ok = jnp.where(go, dst, I32(0x7FFFFFFF))
                end_ok = jnp.where(go, end, I32(0))
                c_lo = jnp.clip(
                    lax.div(lax.shift_right_logical(jnp.min(dst_ok), 2),
                            I32(GR)), 0, GATHER_CHUNKS)
                c_hi = jnp.clip(
                    lax.div(lax.shift_right_logical(jnp.max(end_ok) + 3, 2)
                            + I32(GR - 1), I32(GR)), 0, GATHER_CHUNKS)
                # stream aligned GR-row chunks through scratch; the window
                # cache is flushed+invalidated first so it cannot hold
                # stale copies of the filled rows
                wfs = _win_flush(_wfs_of(c))

                def chunk(i, _):
                    base = a8(i * GR)
                    cin = dma(6, lsliceR(mem_out, base, GR),
                              mwin.at[pl.ds(0, GR)])
                    cin.start()
                    cin.wait()
                    rows = srows(mwin, 0, GR)
                    wi = base + riota(GR)
                    byte0 = wi * 4
                    mask = jnp.zeros_like(rows)
                    for bpos in range(4):
                        ba = byte0 + bpos
                        inr = (~u_lt(ba, dst)) & u_lt(ba, end)
                        mask = mask | jnp.where(
                            inr, jnp.int32(lo_ops.BYTE_MASKS[bpos]), 0)
                    write = (mask != 0) & go
                    wrows(mwin, 0, GR, jnp.where(
                        write, (rows & ~mask) | (fill_word & mask), rows))
                    cout = dma(6, mwin.at[pl.ds(0, GR)],
                               lsliceR(mem_out, base, GR))
                    cout.start()
                    cout.wait()
                    return 0

                lax.fori_loop(c_lo, c_hi, chunk, 0)
                any_oob = jnp.any(oob)

                @pl.when(any_oob)
                def _():
                    trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

                c = _keep_win(c, wfs)
                return lax.cond(
                    any_oob,
                    lambda: keep(c, pc=pc + 1, sp=sp - 3,
                                 status=I32(ST_DIVERGED)),
                    lambda: keep(c, pc=pc + 1, sp=sp - 3))

            def h_memcopy(c):
                if optimistic:
                    return _opt_bulk_exit(c)
                pc, sp, pages = c[1], c[2], c[6]
                n = srow(slo, sp - 1)
                src = srow(slo, sp - 2)
                dst = srow(slo, sp - 3)
                mem_bytes = pages * I32(65536)
                send = src + n
                dend = dst + n
                oob = u_lt(send, src) | u_lt(full(mem_bytes), send) | \
                    u_lt(dend, dst) | u_lt(full(mem_bytes), dend)
                delta = src - dst
                live = (~oob) & (n != 0)
                d_eff = jnp.where(live, delta, I32(0x7FFFFFFF))
                d0 = jnp.min(d_eff)
                agree = jnp.all(jnp.where(live, delta, d0) == d0)
                any_live = jnp.any(live)
                d0 = jnp.where(any_live, d0, I32(0))
                sm = d0 & 3
                qv = lax.shift_right_arithmetic(d0 - sm, 2)
                shB = sm * 8
                inv = (32 - shB) & 31
                hi_or = jnp.where(shB == 0, 0, -1)
                dst_ok = jnp.where(live, dst, I32(0x7FFFFFFF))
                dend_ok = jnp.where(live, dend, I32(0))
                row_lo = lax.shift_right_logical(jnp.min(dst_ok), 2)
                row_hi = lax.shift_right_logical(jnp.max(dend_ok) + 3, 2)
                row_lo = jnp.minimum(row_lo, I32(W))
                row_hi = jnp.minimum(row_hi, I32(W))
                nrows = jnp.maximum(row_hi - row_lo, 0)
                fwd = d0 >= 0
                # whole src+dst span in one window / disjoint regions a
                # way apart; large *overlapping* moves hand off to SIMT
                lo_all = jnp.clip(jnp.minimum(row_lo, row_lo + qv),
                                  0, W - 1)
                hi_all = jnp.clip(jnp.maximum(row_hi, row_hi + qv + 1) - 1,
                                  0, W - 1)
                one_win = (hi_all - (lo_all - lax.rem(lo_all, 8))) < CW
                disjoint = jnp.abs(qv) >= I32(CW + 8)
                feasible = agree & (one_win | disjoint | (nrows == 0))

                def row_mask(r):
                    mask = full(0)
                    for bpos in range(4):
                        ba = full(r * 4 + bpos)
                        inr = (~u_lt(ba, dst)) & u_lt(ba, dend)
                        mask = mask | jnp.where(
                            inr & live,
                            jnp.int32(lo_ops.BYTE_MASKS[bpos]), 0)
                    return mask

                def shift_val(m0, m1):
                    return lax.shift_right_logical(m0, shB) | \
                        (lax.shift_left(m1, inv) & hi_or)

                useA = agree & one_win & (nrows > 0)
                wayA, wfsA = _win_select(_wfs_of(c), lo_all, hi_all, useA)

                winA = win_at(wayA, wfsA)

                def bodyA(i, _):
                    r = jnp.where(fwd, row_lo + i, row_hi - 1 - i)
                    rc = jnp.clip(r, 0, W - 1)
                    m0 = win_read_row(winA, jnp.clip(r + qv, 0, W - 1))
                    m1 = win_read_row(winA,
                                      jnp.clip(r + qv + 1, 0, W - 1))
                    val = shift_val(m0, m1)
                    mask = row_mask(r)
                    old = win_read_row(winA, rc)
                    win_write_row(
                        winA, rc,
                        jnp.where(mask != 0, (old & ~mask) | (val & mask),
                                  old))
                    return 0

                lax.fori_loop(0, jnp.where(useA, nrows, 0), bodyA, 0)
                wfsA = _win_dirtied(wayA, wfsA, useA)

                useB = agree & ~one_win & disjoint & (nrows > 0)

                def bodyB(i, wfs):
                    r = jnp.where(fwd, row_lo + i, row_hi - 1 - i)
                    rs0 = jnp.clip(r + qv, 0, W - 1)
                    rs1 = jnp.clip(r + qv + 1, 0, W - 1)
                    ws, wfs = _win_select(wfs, jnp.minimum(rs0, rs1),
                                          jnp.maximum(rs0, rs1),
                                          jnp.bool_(True))
                    src = win_at(ws, wfs)
                    m0 = win_read_row(src, rs0)
                    m1 = win_read_row(src, rs1)
                    val = shift_val(m0, m1)
                    rc = jnp.clip(r, 0, W - 1)
                    wd_, wfs = _win_select(wfs, rc, rc, jnp.bool_(True))
                    dst_ = win_at(wd_, wfs)
                    mask = row_mask(r)
                    old = win_read_row(dst_, rc)
                    win_write_row(
                        dst_, rc,
                        jnp.where(mask != 0, (old & ~mask) | (val & mask),
                                  old))
                    return _win_dirtied(wd_, wfs)

                wfsB = lax.fori_loop(0, jnp.where(useB, nrows, 0), bodyB,
                                     wfsA)
                any_oob = jnp.any(oob)

                @pl.when(feasible & any_oob)
                def _():
                    trap_where(oob, I32(int(ErrCode.MemoryOutOfBounds)))

                c = _keep_win(c, wfsB)
                return lax.cond(
                    feasible,
                    lambda: lax.cond(
                        any_oob,
                        lambda: keep(c, pc=pc + 1, sp=sp - 3,
                                     status=I32(ST_DIVERGED)),
                        lambda: keep(c, pc=pc + 1, sp=sp - 3)),
                    lambda: keep(c, status=I32(ST_DIVERGED)))

        def count_softfloat(subs, sub):
            """One more binary64 routine run, where the kernel counts
            them: a vreg in VMEM that goes up by one, as `wacc` does
            at a windowed access."""
            if softfloat and sub in subs:
                sfc[...] = sfc[...] + 1

        def count_simd(n=1):
            """`n` more instructions of a v128 class run (only a kernel
            whose image has v128 holds one): a vreg in VMEM again."""
            if n:
                sdc[...] = sdc[...] + n

        def count_indirect(agree):
            """One more br_table or call_indirect run where the kernel
            counts them (an image that holds one): a vreg in VMEM once
            more.  One that diverges stops un-advanced and counts
            nothing, as it retires no step."""
            if not indirect:
                return
            if agree is True:
                idc[...] = idc[...] + 1
                return

            @pl.when(agree)
            def _():
                idc[...] = idc[...] + 1

        def mk_alu2(sub):
            fn = alu2[sub]
            can_trap = sub in _DIV32_SUBS or sub in _DIV64_SUBS

            def h(c):
                pc, sp = c[1], c[2]
                count_softfloat(_F64_ALU2_SUBS, sub)
                xl, xh = srow(slo, sp - 2), srow(shi, sp - 2)
                yl, yh = srow(slo, sp - 1), srow(shi, sp - 1)
                rl, rh = fn(xl, xh, yl, yh)
                wrow(slo, sp - 2, rl)
                wrow(shi, sp - 2, rh)
                if not can_trap:
                    return keep(c, pc=pc + 1, sp=sp - 1)
                if sub in _DIV32_SUBS:
                    dz = yl == 0
                    ovf = (xl == jnp.int32(-0x80000000)) & (yl == -1) \
                        if sub in _DIVS_SUBS else jnp.zeros_like(dz)
                else:
                    dz = (yl | yh) == 0
                    ovf = ((xl == 0) & (xh == jnp.int32(-0x80000000)) &
                           (yl == -1) & (yh == -1)) \
                        if sub in _DIVS_SUBS else jnp.zeros_like(dz)
                bad = dz | ovf
                kind = jnp.where(dz, I32(1), jnp.where(ovf, I32(2), I32(0)))
                if optimistic:
                    k0 = agree_i32(kind)
                    code0 = jnp.where(k0 == 1,
                                      I32(int(ErrCode.DivideByZero)),
                                      I32(int(ErrCode.IntegerOverflow)))

                    @pl.when(k0 != 0)
                    def _():
                        codes = jnp.where(dz,
                                          I32(int(ErrCode.DivideByZero)),
                                          I32(int(ErrCode.IntegerOverflow)))
                        trap_where(bad, codes)

                    return lax.cond(
                        k0 != 0,
                        lambda: keep(c, status=I32(ST_TRAPPED_BASE) + code0),
                        lambda: keep(c, pc=pc + 1, sp=sp - 1))
                any_bad = jnp.any(bad)
                k0 = scal(kind)
                code0 = jnp.where(k0 == 1, I32(int(ErrCode.DivideByZero)),
                                  I32(int(ErrCode.IntegerOverflow)))

                @pl.when(any_bad)
                def _():
                    codes = jnp.where(dz, I32(int(ErrCode.DivideByZero)),
                                      I32(int(ErrCode.IntegerOverflow)))
                    trap_where(bad, codes)

                return lax.cond(
                    any_bad,
                    lambda: lax.cond(
                        jnp.all(bad) & allsame(kind, k0),
                        lambda: keep(c, status=I32(ST_TRAPPED_BASE) + code0),
                        lambda: keep(c, pc=pc + 1, sp=sp - 1,
                                     status=I32(ST_DIVERGED))),
                    lambda: keep(c, pc=pc + 1, sp=sp - 1))
            return h

        def mk_alu1(sub):
            fn = alu1[sub]
            trap_fn = alu1_traps.get(sub)

            def h(c):
                pc, sp = c[1], c[2]
                count_softfloat(_F64_ALU1_SUBS, sub)
                wl, wh = srow(slo, sp - 1), srow(shi, sp - 1)
                rl, rh = fn(wl, wh)
                wrow(slo, sp - 1, rl)
                wrow(shi, sp - 1, rh)
                if trap_fn is None:
                    return keep(c, pc=pc + 1)
                bad, codes = trap_fn(wl, wh)
                if optimistic:
                    # one canary covers both badness and code agreement
                    badk = jnp.where(bad, codes, 0)
                    k0 = agree_i32(badk)

                    @pl.when(k0 != 0)
                    def _():
                        trap_where(bad, codes)

                    return lax.cond(
                        k0 != 0,
                        lambda: keep(c, status=I32(ST_TRAPPED_BASE) + k0),
                        lambda: keep(c, pc=pc + 1))
                any_bad = jnp.any(bad)
                code0 = scal(codes)

                @pl.when(any_bad)
                def _():
                    trap_where(bad, codes)

                return lax.cond(
                    any_bad,
                    lambda: lax.cond(
                        jnp.all(bad) & allsame(codes, code0),
                        lambda: keep(c, status=I32(ST_TRAPPED_BASE) + code0),
                        lambda: keep(c, pc=pc + 1,
                                     status=I32(ST_DIVERGED))),
                    lambda: keep(c, pc=pc + 1))
            return h

        def mk_block(shape):
            """Fused block: pure ops run with intermediates in vregs
            (virtual stack resolved at trace time); local/global/
            memory writes commit immediately in op order.  Forward
            branches absorbed as GUARDS speculate fallthrough — the
            taken path is a lax.cond branch that runs the guard's tail
            (the target's ops, on the guard-point virtual stack) and
            leaves, so nothing after the guard commits; with an empty
            tail it flushes and leaves at once.  A forward `br`
            absorbed as a JUMP moves no data: the kept cell stays in
            vregs, the virtual stack is rebased to the target's
            height, and the ops that follow are the target's.  Every
            op reads its immediates at its own ORIGINAL slot (`At`: the
            head's pc, or a followed edge's target, plus a static
            offset), and counts in `steps` what its own path retired.
            Inline loads/stores take the uniform-address fast
            path; address divergence (careful kernel) or a lane-0 OOB
            bails un-advanced at the op's own slot with everything
            before it committed, which is exactly the state the
            scheduler's split machinery expects for the op's ORIGINAL
            opcode (a jump's or a tail's first slot is a block head:
            the splitter reads its original opcode as it does a bail
            at any head).  A terminal runs via the *_with cores,
            consuming the virtual-stack top directly from vregs."""
            def h(c):
                pc, fp = c[1], c[3]

                class At:
                    """Where a path stands: op `i` of `seq`, whose
                    original slot is base + off (base a run-time
                    scalar, off static), after `r` retired ops."""
                    __slots__ = ("seq", "i", "base", "off", "r")

                    def __init__(self, seq, i, base, off, r):
                        self.seq, self.i, self.base = seq, i, base
                        self.off, self.r = off, r

                    def op(self):
                        return self.seq[self.i]

                    def slot(self):
                        return self.base + self.off

                    def next(self):
                        return At(self.seq, self.i + 1, self.base,
                                  self.off + 1, self.r + 1)

                    def enter(self, seq, i=0):
                        """The followed edge of this op: `seq` from
                        `i` on are the ops at its target."""
                        return At(seq, i, a_r[self.slot()], 0,
                                  self.r + 1)

                class VS:
                    """Trace-time virtual stack over the rows from
                    `base` (immutable snapshots: guard/bail closures
                    capture the state at their point)."""
                    __slots__ = ("base", "items", "nbelow")

                    def __init__(self, base, items=(), nbelow=0):
                        self.base = base
                        self.items = tuple(items)
                        self.nbelow = nbelow

                    def push(self, v):
                        return VS(self.base, self.items + (v,),
                                  self.nbelow)

                    def pop(self):
                        if self.items:
                            return self.items[-1], VS(
                                self.base, self.items[:-1], self.nbelow)
                        k = self.nbelow
                        idx = self.base - 1 - k
                        return srow4(idx), VS(self.base, (), k + 1)

                    def drop1(self):
                        if self.items:
                            return VS(self.base, self.items[:-1],
                                      self.nbelow)
                        return VS(self.base, (), self.nbelow + 1)

                    def peek(self):
                        if self.items:
                            return self.items[-1]
                        idx = self.base - 1 - self.nbelow
                        return srow4(idx)

                    def sp(self):
                        return self.base + (len(self.items) - self.nbelow)

                    def flush(self, skip_top=0):
                        base = self.base - self.nbelow
                        n = len(self.items) - skip_top
                        for i in range(n):
                            wrow4(base + i, self.items[i])

                def cell2(lo_v, hi_v):
                    """A scalar-result cell: e2/e3 cleared when the
                    module carries v128 planes (scalar consumers never
                    read them; clearing beats stale garbage)."""
                    if simd:
                        z = full(0)
                        return (lo_v, hi_v, z, z)
                    return (lo_v, hi_v)

                def bail(cb, at, vs):
                    """Un-advanced stop at the op `at`: everything
                    before it is committed; flush the virtual stack so
                    VMEM holds the exact pre-op state, leave pc at the
                    op's slot (original hid) for the scheduler/SIMT."""
                    vs.flush()
                    return keep(cb, steps=cb[0] + at.r, pc=at.slot(),
                                sp=vs.sp(), status=I32(ST_DIVERGED))

                def simd_in_run(j):
                    """The v128 ops of the straight run that starts at
                    `j`, if one starts there: a path leaves a block
                    only at a guard, an inline load or store or its
                    last op, so a run is retired whole or not at all
                    and one add counts it along the path taken."""
                    if j.i and j.seq[j.i - 1][0] not in _RUN_ENDS:
                        return 0
                    n = 0
                    for op in j.seq[j.i:]:
                        if op[0] in _RUN_ENDS or op[0] == "term":
                            break
                        n += op[0] in _SIMD_BLOCK_OPS
                    return n

                def emit(j, cb, vs, pend_l, pend_g):
                    if simd:
                        count_simd(simd_in_run(j))
                    if j.i == len(j.seq):
                        # the path falls off its last op
                        vs.flush()
                        return keep(cb, steps=cb[0] + j.r - 1,
                                    pc=j.slot(), sp=vs.sp())
                    pcj = j.slot()
                    op = j.op()
                    kind = op[0]
                    if kind == "term":
                        return finish(j, cb, vs)
                    if kind == "jump":
                        # br: the kept cell stays in vregs, the rest of
                        # the virtual stack goes to its rows (what lies
                        # above the target's height is dead, what lies
                        # below it is live), and the stack restarts at
                        # the target's height
                        kept = ()
                        if op[1]:
                            top, vs = vs.pop()
                            kept = (top,)
                        vs.flush()
                        vs = VS(cb[4] + c_r[pcj], kept)
                        return emit(j.enter(j.seq, j.i + 1), cb, vs,
                                    pend_l, pend_g)
                    if kind == "nop":
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "const":
                        vs = vs.push(cell2(full(ilo_r[pcj]),
                                           full(ihi_r[pcj])))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "lget":
                        v = pend_l.get(op[1])
                        if v is None:
                            v = srow4(fp + a_r[pcj])
                        return emit(j.next(), cb, vs.push(v), pend_l, pend_g)
                    if kind in ("lset", "ltee"):
                        if kind == "lset":
                            v, vs = vs.pop()
                        else:
                            v = vs.peek()
                        wrow4(fp + a_r[pcj], v)
                        return emit(j.next(), cb, vs,
                                    {**pend_l, op[1]: v}, pend_g)
                    if kind == "gget":
                        v = pend_g.get(op[1])
                        if v is None:
                            g = a_r[pcj]
                            v = cell2(srow(glo, g), srow(ghi, g))
                        return emit(j.next(), cb, vs.push(v), pend_l, pend_g)
                    if kind == "gset":
                        v, vs = vs.pop()
                        g = a_r[pcj]
                        wrow(glo, g, v[0])
                        wrow(ghi, g, v[1])
                        return emit(j.next(), cb, vs, pend_l,
                                    {**pend_g, op[1]: v})
                    if kind == "drop":
                        return emit(j.next(), cb, vs.drop1(), pend_l, pend_g)
                    if kind == "select":
                        cnd, vs = vs.pop()
                        x2, vs = vs.pop()
                        x1, vs = vs.pop()
                        z = cnd[0] == 0
                        vs = vs.push(tuple(jnp.where(z, a, b)
                                           for a, b in zip(x2, x1)))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "memsize":
                        vs = vs.push(cell2(full(cb[6]), full(0)))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "alu2":
                        count_softfloat(_F64_ALU2_SUBS, op[1])
                        y, vs = vs.pop()
                        x, vs = vs.pop()
                        vs = vs.push(cell2(*alu2[op[1]](x[0], x[1],
                                                        y[0], y[1])))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "alu1":
                        count_softfloat(_F64_ALU1_SUBS, op[1])
                        x, vs = vs.pop()
                        vs = vs.push(cell2(*alu1[op[1]](x[0], x[1])))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "v2":
                        y, vs = vs.pop()
                        x, vs = vs.pop()
                        vs = vs.push(sops.v2_fn(op[1])(x, y))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "v1":
                        x, vs = vs.pop()
                        vs = vs.push(sops.v1_fn(op[1])(x))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vtest":
                        x, vs = vs.pop()
                        vs = vs.push(cell2(sops.vtest_fn(op[1])(x),
                                           full(0)))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vshift":
                        cnt, vs = vs.pop()
                        x, vs = vs.pop()
                        vs = vs.push(sops.vshift_fn(op[1])(x, cnt[0]))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vsplat":
                        v, vs = vs.pop()
                        vs = vs.push(sops.vsplat_fn(op[1])(v[0], v[1]))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vextract":
                        x, vs = vs.pop()
                        rl, rh = sops.vextract_dyn(op[1])(x, a_r[pcj])
                        vs = vs.push(cell2(rl, rh))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vreplace":
                        v, vs = vs.pop()
                        x, vs = vs.pop()
                        vs = vs.push(sops.vreplace_dyn(op[1])(
                            x, a_r[pcj], v[0], v[1]))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vconst":
                        vs = vs.push(_vconst4(a_r[pcj]))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vshuffle":
                        y, vs = vs.pop()
                        x, vs = vs.pop()
                        vs = vs.push(sops.vshuffle_dyn()(
                            x, y, _vconst4(a_r[pcj])))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vshufflew":
                        # a mask that moves whole 32-bit lanes: the
                        # eight rows of x and y, re-ordered
                        y, vs = vs.pop()
                        x, vs = vs.pop()
                        rows = tuple(x) + tuple(y)
                        vs = vs.push(tuple(rows[s] for s in op[1]))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind == "vbitsel":
                        y, vs = vs.pop()
                        x, vs = vs.pop()
                        w_, vs = vs.pop()
                        vs = vs.push(sops.vbitselect()(w_, x, y))
                        return emit(j.next(), cb, vs, pend_l, pend_g)
                    if kind in ("guardz", "guardnz"):
                        return emit_guard(j, cb, vs, pend_l, pend_g)
                    if kind == "loadi":
                        return emit_load(j, cb, vs, pend_l, pend_g)
                    if kind == "storei":
                        return emit_store(j, cb, vs, pend_l, pend_g)
                    raise AssertionError(f"unknown block op {kind}")

                def emit_guard(j, cb, vs, pend_l, pend_g):
                    pcj = j.slot()
                    nz, tail = j.op()[0] == "guardnz", j.op()[1]
                    vs_pre = vs           # incl. cond (careful bail)
                    cond, vs = vs.pop()

                    def exit_taken():
                        # the tail (empty: leave at once, pc at the
                        # target) runs on the guard-point stack.  brz
                        # taken: sp = post-pop, the stack stays in
                        # vregs; brnz (nkeep==0) taken: unwind to
                        # ob + pop_to
                        vt = vs
                        if nz:
                            vs.flush()
                            vt = VS(cb[4] + c_r[pcj])
                        return emit(j.enter(tail), cb, vt, pend_l,
                                    pend_g)

                    if optimistic:
                        t0 = agree_nz(cond[0])
                        taken = (t0 != 0) if nz else (t0 == 0)
                        return lax.cond(
                            taken, exit_taken,
                            lambda: emit(j.next(), cb, vs, pend_l, pend_g))
                    t0 = scal(cond[0])
                    agree = allsame(cond[0], t0)
                    taken = (t0 != 0) if nz else (t0 == 0)
                    return lax.cond(
                        agree & ~taken,
                        lambda: emit(j.next(), cb, vs, pend_l, pend_g),
                        lambda: lax.cond(
                            agree, exit_taken,
                            lambda: bail(cb, j, vs_pre)))

                def _load_val(m0, m1, m2, shB, nbytes, flags):
                    """Static-width load value extraction (the runtime
                    where-chains of _load_finish specialized away)."""
                    inv = (32 - shB) & 31
                    hi_or = jnp.where(shB == 0, 0, -1)
                    raw_lo = lax.shift_right_logical(m0, shB) | \
                        (lax.shift_left(m1, inv) & hi_or)
                    signed = (flags & 1) != 0
                    is64 = (flags & 2) != 0
                    if nbytes == 8:
                        raw_hi = lax.shift_right_logical(m1, shB) | \
                            (lax.shift_left(m2, inv) & hi_or)
                        return raw_lo, raw_hi
                    if nbytes == 4:
                        ll = raw_lo
                    elif nbytes == 2:
                        ll = lax.shift_right_arithmetic(
                            lax.shift_left(raw_lo, 16), 16) if signed \
                            else raw_lo & 0xFFFF
                    else:
                        ll = lax.shift_right_arithmetic(
                            lax.shift_left(raw_lo, 24), 24) if signed \
                            else raw_lo & 0xFF
                    if is64:
                        lh = lax.shift_right_arithmetic(ll, 31) if signed \
                            else jnp.zeros_like(ll)
                    else:
                        lh = jnp.zeros_like(ll)
                    return ll, lh

                def emit_load(j, cb, vs, pend_l, pend_g):
                    pcj = j.slot()
                    nbytes, flags = j.op()[1], j.op()[2]
                    want = 2 if nbytes == 8 else 1
                    vs_pre = vs
                    addr, vs = vs.pop()
                    off = a_r[pcj]
                    ea = addr[0] + off
                    if optimistic:
                        _ea0, oob0, u, shB = opt_addr_prolog(
                            ea, off, nbytes, cb[6])
                        if mem_hbm:
                            rhi = jnp.minimum(u + want, W - 1)
                            # _opt_window may SNAPSHOT (dirty-way
                            # eviction): the snapshot must pair the
                            # planes with a carry positioned at THIS
                            # op — flush the pre-op virtual stack and
                            # hand it a mid-block-consistent carry, so
                            # a later rollback re-enters at pcj (an
                            # absorbed slot with the original hid) and
                            # never re-runs the committed prefix.
                            vs_pre.flush()
                            cb_snap = keep(cb, steps=cb[0] + j.r,
                                           pc=pcj, sp=vs_pre.sp())
                            dirty, way, wfs2, ls2 = _opt_window(
                                cb_snap, u, rhi)
                            cb2 = _keep_win(cb, wfs2, ls=ls2)

                            def go():
                                win = win_at(way, wfs2)
                                m0 = win_read_row(win, u)
                                m1 = win_read_row(
                                    win, jnp.minimum(u + 1, W - 1))
                                m2 = win_read_row(
                                    win, jnp.minimum(u + 2, W - 1)) \
                                    if nbytes == 8 else None
                                vs2 = vs.push(cell2(*_load_val(
                                    m0, m1, m2, shB, nbytes, flags)))
                                return emit(j.next(), cb2, vs2,
                                            pend_l, pend_g)

                            return _opt_leave(
                                dirty, oob0,
                                lambda: bail(cb2, j, vs_pre), go)
                        m0 = srow(memr, u)
                        m1 = srow(memr, jnp.minimum(u + 1, W - 1))
                        m2 = srow(memr, jnp.minimum(u + 2, W - 1)) \
                            if nbytes == 8 else None
                        vs2 = vs.push(cell2(*_load_val(m0, m1, m2, shB,
                                                       nbytes, flags)))
                        return lax.cond(
                            oob0,
                            lambda: bail(cb, j, vs_pre),
                            lambda: emit(j.next(), cb, vs2, pend_l, pend_g))
                    # careful kernel: flush and delegate to the original
                    # handler (keeps its divergent-address gather paths
                    # and trap-partial semantics); execution continues
                    # UNFUSED at pcj+1 until the next block head —
                    # careful runs only on recheck rounds, so parity
                    # beats speed here.
                    return _delegate_mem(j, cb, vs_pre,
                                         _load_flat_hid(nbytes, flags))

                def _load_flat_hid(nbytes, flags):
                    if nbytes == 4 and flags in (0, 2):
                        return H_LOAD_W
                    if nbytes == 8:
                        return H_LOAD_D
                    return H_LOAD

                def _delegate_mem(j, cb, vs_pre, flat_hid):
                    vs_pre.flush()
                    c2 = keep(cb, steps=cb[0] + j.r, pc=j.slot(),
                              sp=vs_pre.sp())
                    return handler_for(flat_hid)(c2)

                def emit_store(j, cb, vs, pend_l, pend_g):
                    pcj = j.slot()
                    nbytes = j.op()[1]
                    want = 2 if nbytes == 8 else 1
                    vs_pre = vs
                    val, vs = vs.pop()
                    addr, vs = vs.pop()
                    off = a_r[pcj]
                    ea = addr[0] + off
                    m_lo = I32(-1) if nbytes >= 4 else \
                        I32(0xFF if nbytes == 1 else 0xFFFF)
                    m_hi = I32(-1) if nbytes == 8 else I32(0)

                    def masks_vals(shB):
                        return shifted_store_triples(m_lo, m_hi,
                                                     val[0], val[1], shB)

                    if optimistic:
                        _ea0, oob0, u, shB = opt_addr_prolog(
                            ea, off, nbytes, cb[6])
                        if mem_hbm:
                            rhi = jnp.minimum(u + want, W - 1)
                            # snapshot-consistency: see emit_load
                            vs_pre.flush()
                            cb_snap = keep(cb, steps=cb[0] + j.r,
                                           pc=pcj, sp=vs_pre.sp())
                            dirty, way, wfs2, ls2 = _opt_window(
                                cb_snap, u, rhi)
                            cb2 = _keep_win(
                                cb, _win_dirtied(way, wfs2), ls=ls2)

                            def go():
                                win_store_words(
                                    win_at(way, wfs2), u, shB,
                                    masks_vals(shB), nbytes)
                                return emit(j.next(), cb2, vs,
                                            pend_l, pend_g)

                            return _opt_leave(
                                dirty, oob0,
                                lambda: bail(cb2, j, vs_pre), go)
                        for k, (m, v) in enumerate(masks_vals(shB)):
                            w = jnp.minimum(u + k, W - 1)

                            @pl.when(~oob0 & (m != 0))
                            def _(m=m, v=v, w=w):
                                cur = srow(memr, w)
                                wrow(memr, w, (cur & ~m) | (v & m))

                        return lax.cond(
                            oob0,
                            lambda: bail(cb, j, vs_pre),
                            lambda: emit(j.next(), cb, vs, pend_l, pend_g))
                    # careful kernel: flush + delegate (see emit_load)
                    return _delegate_mem(
                        j, cb, vs_pre,
                        H_STORE_W if nbytes == 4 else
                        H_STORE_D if nbytes == 8 else H_STORE)

                def finish(at, cb, vs):
                    sp_t = vs.sp()
                    t_hid = at.op()[1]
                    # Only the cell the terminal POPS (or that dies
                    # with the unwind: return/br kept values) may skip
                    # its flush; a brnz fallthrough keeps sp-2 live, so
                    # deeper cells always flush even when also passed
                    # as vregs.
                    nvreg = 0
                    if t_hid in (H_BRZ, H_BRNZ, H_BR_TABLE, H_RETURN,
                                 H_BR, H_CALL_INDIRECT):
                        nvreg = min(1, len(vs.items))
                    vs.flush(skip_top=nvreg)
                    top1 = vs.items[-1] if len(vs.items) >= 1 else None
                    top2 = vs.items[-2] if len(vs.items) >= 2 else None
                    c2 = keep(cb, steps=cb[0] + at.r, pc=at.slot(),
                              sp=sp_t)
                    if t_hid == H_BRZ:
                        return brz_with(c2, top1, spill=top1 is not None)
                    if t_hid == H_BRNZ:
                        return brnz_with(c2, top1, top2,
                                         spill=top1 is not None)
                    if t_hid == H_BR_TABLE:
                        return br_table_with(c2, top1, top2,
                                             spill=top1 is not None)
                    if t_hid == H_RETURN:
                        return return_with(c2, top1)
                    if t_hid == H_BR:
                        return br_with(c2, top1)
                    if t_hid == H_CALL_INDIRECT:
                        return calli_with(c2, top1,
                                          spill=top1 is not None)
                    return handler_for(t_hid)(c2)

                return emit(At(shape, 0, pc, 0, 0), c, VS(c[2]), {}, {})
            return h

        # ------------------- v128 handlers ----------------------------
        # Same 4-plane cell model and simdops semantics as the SIMT
        # engine (engine.py "v128 (SIMD)" section), executed in the one
        # hot loop like the reference's interpreter runs the whole 0xFD
        # page in its dispatch loop (lib/executor/engine/engine.cpp
        # ~700-1610).  Only traced when the module's image uses them.
        if simd:
            from wasmedge_tpu.batch import simdops as sops

            def _vconst4(idx):
                i = jnp.clip(idx, 0, NV - 1)
                return tuple(full(v128t_r[i, k]) for k in range(4))

            def h_vconst(c):
                pc, sp = c[1], c[2]
                wrow4(sp, _vconst4(a_r[pc]))
                return keep(c, pc=pc + 1, sp=sp + 1)

            def mk_v2(sub):
                fn = sops.v2_fn(sub)

                def h(c):
                    pc, sp = c[1], c[2]
                    wrow4(sp - 2, fn(srow4(sp - 2), srow4(sp - 1)))
                    return keep(c, pc=pc + 1, sp=sp - 1)
                return h

            def mk_v1(sub):
                fn = sops.v1_fn(sub)

                def h(c):
                    pc, sp = c[1], c[2]
                    wrow4(sp - 1, fn(srow4(sp - 1)))
                    return keep(c, pc=pc + 1)
                return h

            def mk_vtest(sub):
                fn = sops.vtest_fn(sub)

                def h(c):
                    pc, sp = c[1], c[2]
                    r = fn(srow4(sp - 1))
                    wrow(slo, sp - 1, r)
                    wrow(shi, sp - 1, full(0))
                    return keep(c, pc=pc + 1)
                return h

            def mk_vshift(sub):
                fn = sops.vshift_fn(sub)

                def h(c):
                    pc, sp = c[1], c[2]
                    cnt = srow(slo, sp - 1)
                    wrow4(sp - 2, fn(srow4(sp - 2), cnt))
                    return keep(c, pc=pc + 1, sp=sp - 1)
                return h

            def mk_vsplat(sub):
                fn = sops.vsplat_fn(sub)

                def h(c):
                    pc, sp = c[1], c[2]
                    wrow4(sp - 1, fn(srow(slo, sp - 1),
                                     srow(shi, sp - 1)))
                    return keep(c, pc=pc + 1)
                return h

            def mk_vextract(sub):
                fn = sops.vextract_dyn(sub)

                def h(c):
                    pc, sp = c[1], c[2]
                    rl, rh = fn(srow4(sp - 1), a_r[pc])
                    wrow(slo, sp - 1, rl)
                    wrow(shi, sp - 1, rh)
                    return keep(c, pc=pc + 1)
                return h

            def mk_vreplace(sub):
                fn = sops.vreplace_dyn(sub)

                def h(c):
                    pc, sp = c[1], c[2]
                    r = fn(srow4(sp - 2), a_r[pc],
                           srow(slo, sp - 1), srow(shi, sp - 1))
                    wrow4(sp - 2, r)
                    return keep(c, pc=pc + 1, sp=sp - 1)
                return h

            def h_vshuffle(c):
                pc, sp = c[1], c[2]
                r = sops.vshuffle_dyn()(srow4(sp - 2), srow4(sp - 1),
                                        _vconst4(a_r[pc]))
                wrow4(sp - 2, r)
                return keep(c, pc=pc + 1, sp=sp - 1)

            def h_vbitsel(c):
                pc, sp = c[1], c[2]
                r = sops.vbitselect()(srow4(sp - 3), srow4(sp - 2),
                                      srow4(sp - 1))
                wrow4(sp - 3, r)
                return keep(c, pc=pc + 1, sp=sp - 2)

            def _vmem_rows(u, n_rows, win):
                """Read n_rows consecutive memory words starting at
                scalar row u (resident rows, or rows of the resident
                way `win`)."""
                if mem_hbm:
                    return [win_read_row(win, jnp.minimum(u + k, W - 1))
                            for k in range(n_rows)]
                return [srow(memr, jnp.minimum(u + k, W - 1))
                        for k in range(n_rows)]

            def _v128_from_words(m, shB):
                """Compose 4 planes from 5 words shifted right by shB
                bits (the 16-byte unaligned window)."""
                inv = (32 - shB) & 31
                hi_or = jnp.where(shB == 0, 0, -1)
                return tuple(
                    lax.shift_right_logical(m[k], shB) |
                    (lax.shift_left(m[k + 1], inv) & hi_or)
                    for k in range(4))

            def h_vload(c):
                pc, sp = c[1], c[2]
                addr = srow(slo, sp - 1)
                off = a_r[pc]
                ea = addr + off
                if optimistic:
                    _ea0, oob0, u, shB = opt_addr_prolog(
                        ea, off, 16, c[6])
                    if mem_hbm:
                        rhi = jnp.minimum(u + 4, W - 1)
                        dirty, way, wfs2, ls2 = _opt_window(c, u, rhi)
                        c2 = _keep_win(c, wfs2, ls=ls2)

                        def go():
                            m = _vmem_rows(u, 5, win_at(way, wfs2))
                            wrow4(sp - 1, _v128_from_words(m, shB))
                            return keep(c2, pc=pc + 1)

                        return _opt_leave(
                            dirty, oob0,
                            lambda: keep(c2, status=I32(ST_DIVERGED)),
                            go)
                    m = _vmem_rows(u, 5, None)

                    @pl.when(~oob0)
                    def _():
                        wrow4(sp - 1, _v128_from_words(m, shB))

                    return lax.cond(
                        oob0,
                        lambda: keep(c, status=I32(ST_DIVERGED)),
                        lambda: keep(c, pc=pc + 1))
                # careful: uniform-address fast path, else hand the
                # block to SIMT (full per-lane v128 over there)
                carry_ = u_lt(ea, addr) | u_lt(ea, full(off))
                end = ea + 16
                mem_bytes = c[6] * I32(65536)
                oob = carry_ | u_lt(end, ea) | u_lt(mem_bytes, end)
                widx = jnp.clip(lax.shift_right_logical(ea, 2),
                                0, W - 1)
                shBv = (ea & 3) * 8
                u0 = scal(widx)
                ok = allsame(widx, u0) & allsame(shBv, scal(shBv)) & \
                    ~jnp.any(oob)
                shB = scal(shBv)
                if mem_hbm:
                    rhi = jnp.minimum(u0 + 4, W - 1)
                    way, wfs = _win_select(_wfs_of(c), u0, rhi, ok)
                    c2 = _keep_win(c, wfs)
                    m = _vmem_rows(u0, 5, win_at(way, wfs))
                else:
                    c2 = c
                    m = _vmem_rows(u0, 5, None)

                @pl.when(ok)
                def _():
                    wrow4(sp - 1, _v128_from_words(m, shB))

                return lax.cond(
                    ok,
                    lambda: keep(c2, pc=pc + 1),
                    lambda: keep(c2, status=I32(ST_DIVERGED)))

            def h_vstore(c):
                pc, sp = c[1], c[2]
                v4 = srow4(sp - 1)
                addr = srow(slo, sp - 2)
                off = a_r[pc]
                ea = addr + off

                def word_val_mask(k, shB):
                    """Word k (0..4) of the 128-bit value shifted left
                    by shB bits, and its byte mask."""
                    inv = (32 - shB) & 31
                    hi_or = jnp.where(shB == 0, 0, -1)
                    lo_p = lax.shift_left(v4[k], shB) if k < 4 else 0
                    hi_p = (lax.shift_right_logical(v4[k - 1], inv)
                            & hi_or) if k > 0 else 0
                    m_lo = lax.shift_left(I32(-1), shB) if k < 4 else 0
                    m_hi = (lax.shift_right_logical(I32(-1), inv)
                            & hi_or) if k > 0 else 0
                    return lo_p | hi_p, m_lo | m_hi

                def commit(u, shB, okp, win):
                    for k in range(5):
                        v, mmask = word_val_mask(k, shB)
                        w = jnp.minimum(u + k, W - 1)

                        @pl.when(okp & (mmask != 0))
                        def _(v=v, mmask=mmask, w=w):
                            if mem_hbm:
                                cur = win_read_row(win, w)
                                win_write_row(
                                    win, w, (cur & ~mmask) | (v & mmask))
                            else:
                                cur = srow(memr, w)
                                wrow(memr, w,
                                     (cur & ~mmask) | (v & mmask))

                if optimistic:
                    _ea0, oob0, u, shB = opt_addr_prolog(
                        ea, off, 16, c[6])
                    if mem_hbm:
                        rhi = jnp.minimum(u + 4, W - 1)
                        dirty, way, wfs2, ls2 = _opt_window(c, u, rhi)
                        c2 = _keep_win(c, _win_dirtied(way, wfs2),
                                       ls=ls2)

                        def go():
                            commit(u, shB, True, win_at(way, wfs2))
                            return keep(c2, pc=pc + 1, sp=sp - 2)

                        return _opt_leave(
                            dirty, oob0,
                            lambda: keep(c2, status=I32(ST_DIVERGED)),
                            go)
                    commit(u, shB, ~oob0, None)
                    return lax.cond(
                        oob0,
                        lambda: keep(c, status=I32(ST_DIVERGED)),
                        lambda: keep(c, pc=pc + 1, sp=sp - 2))
                carry_ = u_lt(ea, addr) | u_lt(ea, full(off))
                end = ea + 16
                mem_bytes = c[6] * I32(65536)
                oob = carry_ | u_lt(end, ea) | u_lt(mem_bytes, end)
                widx = jnp.clip(lax.shift_right_logical(ea, 2),
                                0, W - 1)
                shBv = (ea & 3) * 8
                u0 = scal(widx)
                ok = allsame(widx, u0) & allsame(shBv, scal(shBv)) & \
                    ~jnp.any(oob)
                shB = scal(shBv)
                if mem_hbm:
                    rhi = jnp.minimum(u0 + 4, W - 1)
                    way, wfs = _win_select(_wfs_of(c), u0, rhi, ok)
                    commit(u0, shB, ok, win_at(way, wfs))
                    c2 = _keep_win(c, _win_dirtied(way, wfs, ok))
                else:
                    commit(u0, shB, ok, None)
                    c2 = c
                return lax.cond(
                    ok,
                    lambda: keep(c2, pc=pc + 1, sp=sp - 2),
                    lambda: keep(c2, status=I32(ST_DIVERGED)))

        base_handlers = {
            H_NOP: h_nop, H_CONST: h_const, H_LOCAL_GET: h_local_get,
            H_LOCAL_SET: h_local_set, H_LOCAL_TEE: h_local_tee,
            H_GLOBAL_GET: h_global_get, H_GLOBAL_SET: h_global_set,
            H_DROP: h_drop, H_SELECT: h_select, H_BR: h_br, H_BRZ: h_brz,
            H_BRNZ: h_brnz, H_BR_TABLE: h_br_table, H_RETURN: h_return,
            H_CALL: h_call, H_CALL_INDIRECT: h_call_indirect,
            H_MEMSIZE: h_memsize, H_MEMGROW: h_memgrow, H_TRAP: h_trap,
            H_LOAD: h_load, H_STORE: h_store, H_HOSTCALL: h_hostcall,
            H_MEMFILL: h_memfill, H_MEMCOPY: h_memcopy,
        }

        def simd_handler_for(hid):
            if hid >= H_VREPLACE_BASE:
                return mk_vreplace(hid - H_VREPLACE_BASE)
            if hid >= H_VEXTRACT_BASE:
                return mk_vextract(hid - H_VEXTRACT_BASE)
            if hid >= H_VSPLAT_BASE:
                return mk_vsplat(hid - H_VSPLAT_BASE)
            if hid >= H_VSHIFT_BASE:
                return mk_vshift(hid - H_VSHIFT_BASE)
            if hid >= H_VTEST_BASE:
                return mk_vtest(hid - H_VTEST_BASE)
            if hid >= H_V1_BASE:
                return mk_v1(hid - H_V1_BASE)
            if hid >= H_V2_BASE:
                return mk_v2(hid - H_V2_BASE)
            return {H_VCONST: h_vconst, H_VSHUFFLE: h_vshuffle,
                    H_VBITSEL: h_vbitsel, H_VLOAD: h_vload,
                    H_VSTORE: h_vstore}[hid]

        def handler_for(hid):
            if hid >= H_BLOCK_BASE:
                return mk_block(block_shapes[hid - H_BLOCK_BASE])
            if simd and hid >= H_VCONST:
                h = simd_handler_for(hid)

                def counted(c):
                    count_simd()
                    return h(c)
                return counted
            if hid in (H_LOAD_W, H_LOAD_D, H_STORE_W, H_STORE_D):
                # width-specialized paths exist for the hbm+optimistic
                # kernel; everywhere else they alias the generic ops
                if mem_hbm and optimistic:
                    return {H_LOAD_W: h_load_w, H_LOAD_D: h_load_d,
                            H_STORE_W: h_store_w,
                            H_STORE_D: h_store_d}[hid]
                return h_load if hid in (H_LOAD_W, H_LOAD_D) else h_store
            if hid >= H_ALU1_BASE:
                return mk_alu1(hid - H_ALU1_BASE)
            if hid >= H_ALU2_BASE:
                return mk_alu2(hid - H_ALU2_BASE)
            return base_handlers[hid]

        handlers = [handler_for(h) for h in used_hids]

        # ---- the periodic commit (optimistic mode) --------------------
        # One canary validation + snapshot per snap_steps steps is the
        # whole point of the mode: per-step cross-lane reductions become
        # per-interval.  The commit point is a function of `steps`
        # alone, so a dispatch pays a scalar compare for it and nothing
        # else: when it falls due, the loop's next iteration dispatches
        # the commit in place of an instruction, as one more (cold) leaf
        # of the dispatch tree.  It is not an outer loop around the
        # dispatch loop because Mosaic's layout inference recurses over
        # nested regions on a small stack: the hbm kernels already sit
        # at the depth it survives, and one more level crashes the
        # compiler (tests/test_chip_compile.py is the guard).
        def commit_due(c):
            """The FIRST interval after launch is short: genuinely
            divergent blocks (mixed entries the scheduler could not
            group) diverge within a few hundred steps, and a short
            first window bounds the optimistic run-up their rollback
            discards."""
            ls = c[IDX["ls"]]
            interval = jnp.where(ls == 0,
                                 jnp.minimum(I32(min(512, snap_steps)),
                                             snap_dyn),
                                 snap_dyn)
            return (c[0] - ls) >= interval

        def commit(c):
            """A dirty validation rolls back to the last snapshot and
            leaves with ST_RECHECK; a clean one records the current
            state as the next rollback point."""
            flag[0] = jnp.any(srow(canr, 0) != 0).astype(jnp.int32)
            flag[1] = flag[1] + 1

            def rolled():
                do_restore()
                return rolled_carry()

            def clean():
                kw = {"ls": c[0]}
                if mem_hbm:
                    # publish dirty windows before the snapshot so the
                    # HBM plane IS the snapshot's memory state
                    @pl.when(c[IDX["wd0"]] != 0)
                    def _():
                        _wb_way(0, c[IDX["wb0"]])

                    @pl.when(c[IDX["wd1"]] != 0)
                    def _():
                        _wb_way(1, c[IDX["wb1"]])

                    kw.update(wd0=I32(0), wd1=I32(0))
                do_snapshot(c)
                return keep(c, **kw)

            return lax.cond(flag[0] != 0, rolled, clean)

        if optimistic:
            H_COMMIT = len(handlers)     # dense id of the commit leaf
            handlers.append(commit)
        plan, _depths = kernel_dispatch_plan(
            hid_weights if hid_weights else (1,) * len(used_hids),
            optimistic)

        def dispatch(hid, c):
            """Binary tree of lax.cond over the dense handler ids,
            following plan_dispatch_tree.  Mosaic lowers lax.switch to
            a LINEAR if-chain, and every scf.if region a dispatch walks
            costs the scalar core 6-9 ns on a v5e (PR 27, PERF.md
            section 5; the r4/r5 estimate was ~15 ns), so the plan puts
            the handlers a converged dispatch can start at near the
            root and the resume-only ones, with the commit, in one cold
            subtree: fib's three dispatched handlers (superblocks;
            four before them, at 2, 2, 2 and 3) sit at depth 2 where
            the slot-count weights had them at 4.
            Bit-exact vs lax.switch; the midpoint tree when no weights
            are known."""
            def tree(node):
                if isinstance(node, int):
                    return handlers[node](c)
                mid, left, right = node
                return lax.cond(hid < mid,
                                lambda: tree(left),
                                lambda: tree(right))
            return tree(plan)

        def cond(c):
            return (c[0] < chunk_eff) & (c[7] == ST_RUNNING)

        def body(c):
            pc = jnp.clip(c[1], 0, code_len - 1)
            hid = hid_r[pc]
            if optimistic:
                due = commit_due(c)
                hid = jnp.where(due, I32(H_COMMIT), hid)
            nc = dispatch(hid, c)
            # the dispatch count: one vreg in VMEM goes up by one every
            # turn, commits included (the commit leaf counts those in
            # flag[1]).  No scalar: one more in the carry, which every
            # region of a dispatch yields, cost the hbm-window kernel
            # 2 % on a v5e, and an SMEM cell as much (PR 29)
            turns[...] = turns[...] + 1
            # un-advanced stops rewind the step count (the next engine
            # re-executes the instruction): divergence, regrow, and
            # optimistic rollbacks (whose steps were already rewound);
            # a commit retires nothing
            uncounted = (nc[7] == I32(ST_DIVERGED)) | \
                (nc[7] == I32(ST_REGROW)) | (nc[7] == I32(ST_RECHECK))
            if optimistic:
                uncounted = uncounted | due
            return (nc[0] + jnp.where(uncounted, I32(0), I32(1)),) + nc[1:]

        init = (I32(0), ctrl_r[blk, _C_PC], ctrl_r[blk, _C_SP],
                ctrl_r[blk, _C_FP], ctrl_r[blk, _C_OB], ctrl_r[blk, _C_CD],
                ctrl_r[blk, _C_PAGES], ctrl_r[blk, _C_STATUS])
        if mem_hbm:
            # window cache starts invalid each launch (host serving and
            # SIMT handoffs mutate the HBM plane between launches)
            init = init + (I32(-(1 << 30)), I32(0),
                           I32(-(1 << 30)), I32(0), I32(0))
        turns[...] = jnp.zeros_like(turns)
        if softfloat:
            sfc[...] = jnp.zeros_like(sfc)
        if simd:
            sdc[...] = jnp.zeros_like(sdc)
        if indirect:
            idc[...] = jnp.zeros_like(idc)
        if optimistic:
            init = init + (I32(0),)  # ls: last-snapshot step count
            # entry state was validated at the previous exit: it IS the
            # first rollback point
            wrow(canr, 0, full(0))
            do_snapshot(init)
            flag[1] = I32(0)         # commits taken as a loop turn
        fin = lax.while_loop(cond, body, init)
        dispatched = turns[0, 0] - (flag[1] if optimistic else I32(0))
        if optimistic:
            # a commit that fell due on the launch's last dispatch
            fin = lax.cond(commit_due(fin) & (fin[7] == I32(ST_RUNNING)),
                           commit, lambda c: c, fin)
            # exit validation: every path out of the loop (chunk/fuel
            # exhaustion, DONE, trap, park, diverge) must not publish
            # state built on an unvalidated lane-0 decision
            flag[0] = jnp.any(srow(canr, 0) != 0).astype(jnp.int32)
            pdirty = flag[0] != 0

            @pl.when(pdirty)
            def _():
                do_restore()

            rolledf = rolled_carry()
            fin = tuple(jnp.where(pdirty, r, v)
                        for r, v in zip(rolledf, fin))
        steps, pc, sp, fp, ob, cd, pages, status = fin[:8]
        if mem_hbm:
            # commit dirty windows so the HBM plane is coherent for the
            # host/SIMT on every exit path (done, parked, diverged)
            wb0f, wd0f, wb1f, wd1f = fin[8], fin[9], fin[10], fin[11]

            @pl.when(wd0f != 0)
            def _():
                _wb_way(0, wb0f)

            @pl.when(wd1f != 0)
            def _():
                _wb_way(1, wb1f)
        exhausted = (status == I32(ST_RUNNING)) & (steps >= fuel_in)
        status = jnp.where(
            exhausted,
            I32(ST_TRAPPED_BASE) + I32(int(ErrCode.CostLimitExceeded)),
            status)

        @pl.when(exhausted)
        def _():
            tr_ = srow(trapr, 0)
            wrow(trapr, 0, jnp.where(tr_ == 0,
                                     I32(int(ErrCode.CostLimitExceeded)),
                                     tr_))

        # the disabled-fuel sentinel must not drift down across launches
        # (a >2^31-step run would spuriously exhaust it)
        ctrl_out[blk, _C_FUEL] = jnp.where(fuel_in == I32(_FUEL_OFF),
                                           fuel_in, fuel_in - steps)
        ctrl_out[blk, _C_PC] = pc
        ctrl_out[blk, _C_SP] = sp
        ctrl_out[blk, _C_FP] = fp
        ctrl_out[blk, _C_OB] = ob
        ctrl_out[blk, _C_CD] = cd
        ctrl_out[blk, _C_STATUS] = status
        ctrl_out[blk, _C_PAGES] = pages
        ctrl_out[blk, _C_CHUNK] = chunk
        ctrl_out[blk, _C_STEPS] = steps
        ctrl_out[blk, _C_SNAP] = snap_in
        ctrl_out[blk, _C_DISPATCHES] = dispatched
        if mem_hbm:
            ctrl_out[blk, _C_WFILLS] = wcnt[0]
            ctrl_out[blk, _C_WWBS] = wcnt[1]
            ctrl_out[blk, _C_WACCESSES] = wacc[0, 0]
        if softfloat:
            ctrl_out[blk, _C_SOFTFLOAT] = sfc[0, 0]
        if simd:
            ctrl_out[blk, _C_SIMD] = sdc[0, 0]
        if indirect:
            ctrl_out[blk, indirect_column(simd)] = idc[0, 0]

        outs = [dma(0, slo, lslice(s_lo_out)),
                dma(1, shi, lslice(s_hi_out)),
                dma(2, glo, lslice(g_lo_out)),
                dma(3, ghi, lslice(g_hi_out)),
                dma(5, trapr, lslice(trap_out))]
        if not mem_hbm:
            outs.append(dma(4, memr, lslice(mem_out)))
        if simd:
            outs += [dma(6, se2s, lslice(se2_out)),
                     dma(7, se3s, lslice(se3_out))]
        for c in outs:
            c.start()
        for c in outs:
            c.wait()

    def aspec():
        return pl.BlockSpec(memory_space=pl.ANY)

    # shadow (rollback) plane geometry: full-size whenever the ENGINE
    # is optimistic (its careful recheck kernel shares the same state
    # list, so both kernels must declare the same shadow shapes); a
    # careful-only engine degenerates them to placeholders (no HBM
    # doubling).
    if shadow_full is None:
        shadow_full = optimistic
    SH_D = D if shadow_full else 1
    SH_NG = NGp if shadow_full else 1
    SH_L = L if shadow_full else 1
    WSH = (W if (not mem_hbm and W > 1) else 1) if shadow_full else 1
    n_planes = 12 + (4 if simd else 0)  # aliased plane inputs/outputs

    def vmem_rows(n):
        """VMEM scratch holding n state rows in the active row layout."""
        return pltpu.VMEM((n,) + ROW if three_d else (n, Lblk), jnp.int32)

    def p3(shape):
        """Out-shape for an HBM plane: `plane_shape` iff it is a full
        lane plane (shape[-1] == L) and the remap is active."""
        if three_d and shape[-1] == L:
            return plane_shape(shape[0], L, Lpb)
        return shape
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=15,
        grid=(nblk,),
        in_specs=(
            [pl.BlockSpec(memory_space=pltpu.SMEM)]     # frames_in
            + [aspec()] * n_planes),                    # planes (HBM)
        out_specs=(
            [pl.BlockSpec(memory_space=pltpu.SMEM),     # ctrl_out
             pl.BlockSpec(memory_space=pltpu.SMEM)]     # frames_out
            + [aspec()] * n_planes),
        scratch_shapes=(
            [vmem_rows(D),                              # slo
             vmem_rows(D)]                              # shi
            + ([vmem_rows(D),                           # se2 (v128)
                vmem_rows(D)]                           # se3 (v128)
               if simd else [])
            + [vmem_rows(NGp),                          # glo
               vmem_rows(NGp)]                          # ghi
            + ([vmem_rows(2 * CW),                      # mwin (two ways)
                pltpu.SMEM((2,), jnp.int32),            # wcnt (DMA counts)
                pltpu.VMEM((8, 128), jnp.int32)]        # wacc (accesses)
               if mem_hbm else
               [vmem_rows(W)])                          # memr (resident)
            + [vmem_rows(1),                            # trapr
               pltpu.SemaphoreType.DMA((8,))]           # sems
            + ([vmem_rows(1),                           # canr (canary)
                pltpu.SMEM((2,), jnp.int32),            # flag
                pltpu.SMEM((3, CD), jnp.int32),         # snapf (frames)
                pltpu.SMEM((16,), jnp.int32)]           # snapc (carry)
               if optimistic else [])
            + [pltpu.VMEM((8, 128), jnp.int32)]         # turns
            + ([pltpu.VMEM((8, 128), jnp.int32)]        # sfc (softfloat)
               if softfloat else [])
            + ([pltpu.VMEM((8, 128), jnp.int32)]        # sdc (v128 ops)
               if simd else [])
            + ([pltpu.VMEM((8, 128), jnp.int32)]        # idc (indirect)
               if indirect else [])
        ),
    )
    out_shape = [
        jax.ShapeDtypeStruct((nblk, ctrl_width(simd, indirect)),
                             jnp.int32),                      # ctrl
        jax.ShapeDtypeStruct((nblk, 3, CD), jnp.int32),  # frames
        jax.ShapeDtypeStruct(p3((D, L)), jnp.int32),    # stack_lo
        jax.ShapeDtypeStruct(p3((D, L)), jnp.int32),    # stack_hi
        jax.ShapeDtypeStruct(p3((NGp, L)), jnp.int32),  # glob_lo
        jax.ShapeDtypeStruct(p3((NGp, L)), jnp.int32),  # glob_hi
        jax.ShapeDtypeStruct(p3((W, L)), jnp.int32),    # mem
        jax.ShapeDtypeStruct(p3((1, L)), jnp.int32),    # trap
        jax.ShapeDtypeStruct(p3((SH_D, SH_L)), jnp.int32),   # sh_slo
        jax.ShapeDtypeStruct(p3((SH_D, SH_L)), jnp.int32),   # sh_shi
        jax.ShapeDtypeStruct(p3((SH_NG, SH_L)), jnp.int32),  # sh_glo
        jax.ShapeDtypeStruct(p3((SH_NG, SH_L)), jnp.int32),  # sh_ghi
        jax.ShapeDtypeStruct(p3((1, SH_L)), jnp.int32),      # sh_trap
        jax.ShapeDtypeStruct(p3((WSH, SH_L)), jnp.int32),    # sh_mem
    ]
    if simd:
        out_shape += [
            jax.ShapeDtypeStruct(p3((D, L)), jnp.int32),     # stack_e2
            jax.ShapeDtypeStruct(p3((D, L)), jnp.int32),     # stack_e3
            jax.ShapeDtypeStruct(p3((SH_D, SH_L)), jnp.int32),  # sh_se2
            jax.ShapeDtypeStruct(p3((SH_D, SH_L)), jnp.int32),  # sh_se3
        ]
    # plane inputs alias the plane outputs (after ctrl/frames)
    aliases = {_PLANE_ARG0 + k: 2 + k for k in range(n_planes)}
    # a stable name per kernel kind: it names the Mosaic kernel, the
    # jitted program and the device event of a profiler trace, so the
    # optimistic and the careful kernel can be told apart there
    kname = "wasm_kernel_optimistic" if optimistic else "wasm_kernel_careful"
    fn = pl.pallas_call(
        kernel,
        grid_spec=spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=kname,
    )
    fn.__name__ = kname
    return jax.jit(fn, donate_argnums=_DONATED_PLANES)


def pallas_enabled(cfg) -> bool:
    """One policy for whether the Pallas fast path is on: the explicit
    `use_pallas` knob wins; unset means TPU-backend auto-detect; and
    `interpret=True` opts in on CPU (tests).  Shared by the uniform and
    multi-tenant engines so they can never disagree."""
    use = cfg.use_pallas
    if use is None:
        from wasmedge_tpu.batch import ensure_jax_backend

        ensure_jax_backend()
        import jax

        use = jax.default_backend() == "tpu"
    return bool(use or cfg.interpret)


class HostLink:
    """What crosses between the host and the device on the block
    scheduler's path, each crossing under a leaf span and counted: a
    download (`batch/d2h`), an upload (`batch/h2d`), a call of a compiled
    program (`batch/enqueue`).  A profiler trace then says for every
    millisecond the device idles whether the host was moving data,
    enqueueing, or doing its own work in the phase span around them.

    `timed` opens the spans (the recorder's `obs.timed` with its
    category and track bound, and no object that owns device planes: the
    link must not keep them alive); a link lives as long as the run it
    counts.  A numpy argument handed straight to a compiled program
    rides its enqueue."""

    def __init__(self, timed):
        self._timed = timed
        self.d2h_transfers = 0
        self.h2d_transfers = 0
        self.programs_enqueued = 0

    def d2h(self, what: str, arr, index=None) -> np.ndarray:
        """The device array `arr` (its `index`, cut on the device inside
        the span) on the host: blocks until the device has produced it.
        Read-only where the backend hands out its own buffer."""
        self.d2h_transfers += 1
        with self._timed("batch/d2h", what=what) as span:
            out = np.asarray(arr if index is None else arr[index])
            span.set(bytes=out.nbytes)
        return out

    def h2d(self, what: str, arr):
        """The host value `arr` as a device array."""
        import jax.numpy as jnp

        self.h2d_transfers += 1
        with self._timed("batch/h2d", what=what,
                         bytes=int(np.asarray(arr).nbytes)):
            return jnp.asarray(arr)

    def enqueue(self, program: str, fn, *args):
        """`fn(*args)` for a compiled `fn`: returns once the program is
        enqueued, not when it has run."""
        self.programs_enqueued += 1
        with self._timed("batch/enqueue", program=program):
            return fn(*args)


class PassRecord(NamedTuple):
    """What the host reads to decide a pass, as it left the device in
    one download (`_pass_record_fn`).  `ctrl` and `frames` are the
    host's own copies (the scheduler writes its mirrors); the rest are
    read-only views of the downloaded buffer."""

    ctrl: np.ndarray      # [nblk, ctrl_width]
    frames: np.ndarray    # [nblk, 3, CD]
    trap: np.ndarray      # [L]: the trap plane's one row
    res_lo: np.ndarray    # [nres, L]: rows [:nres] of stack_lo
    res_hi: np.ndarray    # [nres, L]: and of stack_hi


def _pass_record_fn():
    """-> pack(ctrl, frames, trap, stack_lo, stack_hi, nres), the
    compiled program that lays `PassRecord`'s five parts end to end in
    one flat int32 array (`nres` static).  Enqueued behind the kernel
    whose outputs it reads, it turns the two to five blocking downloads
    of a pass into one: a download costs its round trip, not its bytes.
    Its output is a buffer of its own; it holds no plane alive."""
    import jax
    import jax.numpy as jnp

    def pack(ctrl, frames, trap, stack_lo, stack_hi, nres):
        return jnp.concatenate([
            ctrl.ravel(), frames.ravel(), trap.ravel(),
            stack_lo[:nres].ravel(), stack_hi[:nres].ravel()])

    return jax.jit(pack, static_argnums=5)


def _split_pass_record(flat: np.ndarray, nblk: int, cd: int, lanes: int,
                       nres: int, ctrl_w: int = _CTRL_W) -> PassRecord:
    """`pack`'s layout read back: it follows from the shapes alone."""
    sizes = (nblk * ctrl_w, nblk * 3 * cd, lanes, nres * lanes,
             nres * lanes)
    if flat.shape != (sum(sizes),):
        raise ValueError(f"pass record of {flat.shape}, not {sum(sizes)}")
    ctrl, frames, trap, res_lo, res_hi = np.split(
        flat, np.cumsum(sizes)[:-1])
    return PassRecord(ctrl.reshape(nblk, ctrl_w).copy(),
                      frames.reshape(nblk, 3, cd).copy(), trap,
                      res_lo.reshape(nres, lanes),
                      res_hi.reshape(nres, lanes))


def donated_planes(fn, specs) -> int:
    """How many of the kernel's plane arguments (from `_PLANE_ARG0`)
    the launch `fn` donates (`_DONATED_PLANES`, or none under
    `jit_in_place`'s carve-out), read from its trace over `specs`: jit
    keeps that trace for the first call of those shapes."""
    args = fn.trace(*specs).args_info[0]
    return sum(a.donated for a in args[_PLANE_ARG0:])


def _hostcall_fns():
    """-> {name: program}, the compiled programs of a hostcall serve
    (`_serve_hostcalls_begin`, `_finish`), one set an engine: jit keys
    their variants by the planes' shapes and the static counts.

    gather(plane, idx)                 the columns `idx` of the memory
                                       plane, [W, len(idx)], a buffer of
                                       its own (every plane in the
                                       kernel's layout, `plane_shape`)
    rows(plane, w0, k, lane_major, pieces)
                                       rows [w0, w0 + k) of every lane,
                                       [k, L], or transposed on the
                                       device to [L, k]: a lane's bytes
                                       end to end; as a tuple of
                                       `pieces` arrays, the first axis
                                       cut into near-equal runs (`k`,
                                       `lane_major`, `pieces` static)
    set_rows(plane, rows, r0, c0)      the plane with `rows` set at
                                       (r0, c0), in place
    results(lo, hi, res_lo, res_hi, ob, c0)
                                       both stacks with the result rows
                                       set at (ob, c0), in place
    trap(plane, codes, c0)             the trap row's columns from c0
                                       raised to `codes`, in place

    The three that write take their planes donated (`jit_in_place`:
    the caller rebinds them)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from wasmedge_tpu.batch import jit_in_place

    def gather(plane, idx):
        return take_cols(plane, idx)

    def rows(plane, w0, k, lane_major, pieces):
        out = lanes_of(lax.dynamic_slice_in_dim(plane, w0, k))
        out = out.T if lane_major else out
        step = -(-out.shape[0] // pieces)
        return tuple(out[a:a + step]
                     for a in range(0, out.shape[0], step))

    def set_rows(plane, new, r0, c0):
        return put_cols(plane, new, r0, c0)

    def results(lo, hi, res_lo, res_hi, ob, c0):
        return (put_cols(lo, res_lo, ob, c0), put_cols(hi, res_hi, ob, c0))

    def trap(plane, codes, c0):
        zero = c0 * 0
        old = cut_cols(plane, zero, 1, c0, codes.shape[0])
        return put_cols(plane, jnp.maximum(old, codes[None, :]), zero, c0)

    return {"gather": jax.jit(gather),
            "rows": jax.jit(rows, static_argnums=(2, 3, 4)),
            "set_rows": jit_in_place(set_rows, 0),
            "results": jit_in_place(results, 0, 1),
            "trap": jit_in_place(trap, 0)}


class PallasUniformEngine:
    """Block-converged engine running the dispatch loop on-device.

    Wraps the SIMT engine for divergence fallback exactly like
    UniformBatchEngine; the difference is the converged fast path runs as a
    Pallas kernel (one launch per `steps_per_launch` instructions) instead
    of per-step XLA, and convergence is only required within a lane block."""

    # geometry knobs (state must fit VMEM; ~16 MiB/core on v5e)
    MAX_CODE_LEN = MAX_CODE_LEN  # module-level constant, shared with aot
    # Per-block VMEM scratch budget (1x state size: state planes stay in
    # HBM and are DMA'd into scratch per lane block; ~2 MiB headroom is
    # left for gather-chunk temporaries and compiler spill).
    VMEM_BUDGET_BYTES = 9 * 1024 * 1024
    # Divergent-address loads/stores scan the whole [W, Lblk] memory block
    # (compare-reduce); cap that scan's size, not W alone — one wasm page
    # is already 16384 words.
    MAX_GATHER_ELEMS = 4 * 1024 * 1024
    # Window-cache rows per way in mem_hbm mode (2 ways).  128 rows =
    # 512 B of guest memory per lane per way; misses move CW×Lblk words
    # over DMA, so sequential access amortizes one miss over ~CW rows.
    HBM_WINDOW_ROWS = 128
    # Optimistic-convergence commit interval: dispatches between canary
    # validations/snapshots.  Bounds both the validation amortization
    # and the worst-case replay a rollback hands the careful kernel.
    # Snapshot cadence of the optimistic kernel.  Measured r05 (one
    # v5e chip, 4096 lanes): raising 8192 -> 131072 moved flagship
    # fib(30) 56 -> ~70-74G instr/s and the memory-heavy mix 29 -> 49G
    # (snapshot DMA was ~25% of wall), with the divergent mix flat.
    # Worst case a block that ran clean past its FIRST short window
    # (512 steps — genuinely divergent blocks diverge inside it) and
    # diverges late discards + carefully re-executes up to this many
    # steps ONCE (~0.2 s at 4096 lanes); its per-block interval then
    # halves adaptively (batch/scheduler.py `_SnapPolicy`), so
    # repeated rollbacks are geometrically cheaper.
    SNAP_STEPS = 131072

    def __init__(self, inst, store=None, conf=None, lanes=None, mesh=None,
                 interpret=None, simt=None, blk_cap=None):
        from wasmedge_tpu.batch.engine import BatchEngine

        self.simt = simt if simt is not None else BatchEngine(
            inst, store=store, conf=conf, lanes=lanes, mesh=mesh)
        self.inst = inst
        self.cfg = self.simt.cfg
        self.lanes = self.simt.lanes
        self.img = self.simt.img
        self.obs = self.simt.obs  # shared flight recorder (obs/)
        self.interpret = interpret
        opt = getattr(self.cfg, "optimistic", None)
        self.optimistic = True if opt is None else bool(opt)
        self._fn = None
        self._fn_careful_cache = None
        self._pack_cache = None
        self._hostcall_fns_cache = None
        self._rows_buffers = {}     # `_read_plane_rows`' own, by shape
        self._tables = None
        self._blk_cap = blk_cap   # the largest lane block (None: lanes)
        self.fell_back_to_simt = False
        self.splits = 0  # block-scheduler split count from the last run()
        self.recheck_rounds = 0  # careful-kernel rounds (optimistic mode)
        # the last run()'s launches of the optimistic kernel, rounds of
        # the careful one, the block-steps those rounds retired, and the
        # compiled programs of block surgery (an extract and an install
        # for every child of a split)
        self.launches = 0
        self.rechecks = 0
        self.careful_steps = 0
        self.surgery_programs = 0
        # the last run()'s crossings of the host link (HostLink):
        # blocking downloads, uploads, calls of a compiled program
        self.d2h_transfers = 0
        self.h2d_transfers = 0
        self.programs_enqueued = 0
        # the last run()'s hostcall serves: rounds of park, drain and
        # re-arm, lanes drained, those a vectorised implementation
        # served, bytes the calls handed to an fd
        self.hostcall_rounds = 0
        self.hostcall_calls = 0
        self.hostcall_vectorized = 0
        self.hostcall_out_bytes = 0
        # (expected, max) branches a dispatch walks in the kernel's
        # tree (plan_dispatch_tree), known once a kernel was built
        self.dispatch_depth = None
        # how the newest kernel holds linear memory: {"mem_mode": none |
        # resident | hbm_window} and, with a memory, "lane_block" and
        # (hbm_window) "window" = rows x ways
        self.mem_static = None
        # the hbm_window kernel's DMA counts over the last run(): window
        # fills and dirty write-backs, HBM_WINDOW_ROWS rows x the lane
        # block each (the careful recheck kernel's included); the loads
        # and stores it resolved against the window, and the share of
        # them that found their rows resident (1 - fills / accesses)
        self.window_fills = 0
        self.window_writebacks = 0
        self.window_accesses = 0
        self.window_hit_share = None
        # handlers the kernels dispatched over the last run(), summed
        # over blocks and launches, and the block-steps a dispatch
        # retired (3.5 in fib before superblocks, 5.25 with them)
        self.dispatches = 0
        self.instr_per_dispatch = None
        # the binary64 routines of batch/softfloat.py the kernels ran
        # over the last run(), a lane-block step each, and their share
        # of the block-steps retired; None for an image without one
        self.counts_softfloat = holds_softfloat(self.img)
        self.softfloat_ops = None
        self.softfloat_share = None
        # likewise the instructions of a v128 class (CLS_VCONST ..
        # CLS_VSTORE), which a kernel whose image has v128 counts in the
        # one ctrl column that only its rows have, and the br_table and
        # call_indirect a kernel whose image holds one ran (one more
        # column, `indirect_column`)
        self.counts_indirect = holds_indirect(self.img)
        self.ctrl_width = ctrl_width(bool(self.img.has_simd),
                                     self.counts_indirect)
        self.simd_ops = None
        self.simd_share = None
        self.indirect_ops = None
        # forward edges the newest kernel's blocks run through, by kind
        self.superblock_edges = None
        # its block shapes ({"kept", "wanted"}: fuse_blocks_counted)
        self.block_shapes = None
        # the image's i8x16.shuffle slots by lowering ({"word",
        # "dynamic"}); None without one
        self.shuffle_sites = None
        # the newest kernel's plane arguments its launch donates
        self.donated_planes = None
        # None = no tpu.aot fused section attached; set by _build when a
        # loaded artifact carries one (True = matched regeneration)
        self.aot_fused_verified = None
        # per-lane page counts recorded when a host outcall grows memory
        # (block ctrl keeps one uniform count; growth diverges the block)
        self._pages_override = {}
        self.ineligible_reason = self._eligibility()

    # -- geometry / eligibility -------------------------------------------
    def _interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        from wasmedge_tpu.batch import ensure_jax_backend

        ensure_jax_backend()
        import jax

        return jax.default_backend() == "cpu"

    def _depths(self):
        # The configured depths are honored exactly — same trap thresholds
        # as the XLA engines' _do_call; _lane_block gates whether they fit
        # VMEM (ineligible -> XLA fallback), never silently shrinks them.
        return self.cfg.value_stack_depth, self.cfg.call_stack_depth

    def _mem_words(self):
        # Watermark sizing (SURVEY §5.7): the VMEM plane covers *current*
        # pages, not the declared max — a module declaring max=16 pages
        # but touching one keeps a small state and a big lane block.
        # memory.grow beyond this capacity (but within the declared max)
        # raises ST_REGROW and the host re-executes on a bigger plane.
        img = self.img
        if not img.has_memory:
            return 1
        return max(img.mem_pages_init, 1) * _PAGE_WORDS

    def _state_bytes_per_lane(self, mem_hbm: bool) -> int:
        D, CD = self._depths()
        NGp = max(self.img.globals_lo.shape[0], 1)
        memw = 2 * self.HBM_WINDOW_ROWS if mem_hbm else self._mem_words()
        # v128 modules carry 4 stack planes (lo/hi/e2/e3) in scratch
        nstack = 4 if self.img.has_simd else 2
        return 4 * (nstack * D + 2 * NGp + memw + 1)

    def _blk_for(self, per_lane: int) -> Optional[int]:
        """Largest power-of-two lane block whose state fits the budget."""
        # Mosaic requires lane-dim slices aligned to the 128-lane tiling;
        # interpret mode (CPU tests) has no such constraint.
        align = 1 if self._interpret() else 128
        cap = self._blk_cap or self.lanes
        # start at the cap: the scheduler's lane totals need not be a
        # power of two (nblk * Lblk with arbitrary nblk), so halving from
        # self.lanes would walk past the intended block size
        blk = min(self.lanes, cap)

        def bad(k):
            return (k * per_lane > self.VMEM_BUDGET_BYTES
                    or self.lanes % k != 0 or k > cap or k % align != 0)

        while blk > align and bad(blk):
            blk //= 2
        if bad(blk):
            return None
        return blk

    def _mem_mode(self) -> bool:
        """True when the kernel should keep the memory plane HBM-resident
        behind the window cache (bigger lane blocks, DMA on window miss)
        instead of staging the whole [W, Lblk] slab into VMEM scratch
        (zero-latency access, 128-ish lane blocks).  Auto rule: pick HBM
        whenever it strictly enlarges the lane block; cfg.mem_hbm forces
        either way (tests, experiments)."""
        if not self.img.has_memory:
            return False
        if self._mem_words() < self.HBM_WINDOW_ROWS:
            return False
        blk_hbm = self._blk_for(self._state_bytes_per_lane(True))
        forced = getattr(self.cfg, "mem_hbm", None)
        if forced is not None:
            return bool(forced) and blk_hbm is not None
        if blk_hbm is None:
            return False
        blk_res = self._blk_for(self._state_bytes_per_lane(False))
        return blk_res is None or blk_hbm > blk_res

    def _lane_block(self) -> Optional[int]:
        return self._blk_for(self._state_bytes_per_lane(self._mem_mode()))

    def _eligibility(self) -> Optional[str]:
        img = self.img
        reason = pallas_image_eligibility(img, self.MAX_CODE_LEN)
        if reason is not None:
            return reason
        if self.simt.mesh is not None:
            return "mesh sharding handled by SIMT engine"
        if self.cfg.fuel_per_launch is not None and \
                self.cfg.cost_table is not None and \
                any(c != 1 for c in self.cfg.cost_table):
            return "per-opcode cost-table gas handled by SIMT engine"
        if self._lane_block() is None:
            return (f"state too large for VMEM "
                    f"({self._mem_words()} mem words/lane)")
        return None

    @property
    def eligible(self) -> bool:
        return self.ineligible_reason is None

    # -- build ------------------------------------------------------------
    def _build(self):
        from wasmedge_tpu.batch import ensure_jax_backend

        ensure_jax_backend()
        import jax
        import jax.numpy as jnp

        img = self.img
        interpret = self._interpret()
        hid, block_shapes, self.block_shapes = fuse_blocks_counted(
            hid_plane(img), img)
        # block fusion rewrites block-head hids only: the operand planes
        # are the image's own
        a_p, b_p, c_p = img.a, img.b, img.c
        ilo_p, ihi_p = img.imm_lo, img.imm_hi
        # tpu.aot artifacts carry the fused encoding.  Verification IS
        # regeneration (cheap next to XLA compilation); once verified,
        # the attached planes are the ones executed — a stale or
        # tampered section is detected here and never runs.
        attached = getattr(self.inst.lowered, "fused", None)
        if attached is not None:
            self.aot_fused_verified = all(
                getattr(attached[k], "dtype", None) == v.dtype
                and np.array_equal(attached[k], v)
                for k, v in (("hid", hid), ("a", a_p), ("b", b_p),
                             ("c", c_p), ("ilo", ilo_p), ("ihi", ihi_p)))
            if self.aot_fused_verified:
                hid, a_p, b_p, c_p, ilo_p, ihi_p = (
                    attached["hid"], attached["a"], attached["b"],
                    attached["c"], attached["ilo"], attached["ihi"])
        # weight of a handler = the number of slots at which a
        # converged dispatch can START with its hid: the entry slots
        # (absorbed slots keep their hids for resumes only and weigh
        # nothing).  Dense ids are numbered hot-first (then by flat id,
        # for determinism) so the contiguous-range tree of
        # plan_dispatch_tree can put the heavy handlers at the top.
        live = entry_slots(hid, block_shapes, img)
        count = collections.Counter(int(h) for h in hid[live])
        # a block whose every way in was absorbed stays among the hot
        # handlers all the same: it nests as deep as they do, and the
        # cold subtree is where the tree is deepest
        for h in set(int(h) for h in hid[hid >= H_BLOCK_BASE]):
            count[h] = max(count[h], 1)
        used = tuple(sorted(set(int(h) for h in hid),
                            key=lambda h: (-count[h], h)))
        dense = {h: i for i, h in enumerate(used)}
        hid_dense = np.asarray([dense[int(h)] for h in hid], np.int32)
        self._hid_weights = tuple(count[h] for h in used)
        self.dispatch_depth = expected_and_max_depth(
            self._hid_weights,
            kernel_dispatch_plan(self._hid_weights, self.optimistic)[1])
        self.obs.set_dispatch_static(*self.dispatch_depth)
        self.superblock_edges = superblock_edges(hid, block_shapes, img)
        self.obs.set_superblock_static(self.superblock_edges)
        self.obs.set_block_shapes_static(self.block_shapes)
        self.shuffle_sites = shuffle_sites(hid, block_shapes, img)
        if self.shuffle_sites:
            self.obs.set_shuffle_static(self.shuffle_sites)
        # host-side view of the fused encoding: the block scheduler's
        # divergence splitter evaluates the stopped instruction from
        # these.  _np_hid_orig is the UNfused plane: a block whose
        # first op bails leaves pc at the head (hid = block id), but
        # its operand fields are the original op's, so the splitter
        # resolves it via the original opcode.
        self._np_fused = {"hid": hid, "a": a_p, "b": b_p, "c": c_p,
                          "ilo": ilo_p, "ihi": ihi_p}
        self._np_hid_orig = hid_plane(img)
        D, CD = self._depths()
        W = self._mem_words()
        NG = img.globals_lo.shape[0]
        Lblk = self._lane_block()
        pages_cap = W // _PAGE_WORDS if img.has_memory else 0
        pages_hard = max(img.mem_pages_max, img.mem_pages_init) \
            if img.has_memory else 0
        mem_hbm = self._mem_mode()
        self._geom = (D, CD, W, Lblk)
        stripe = lane_stripe(Lblk, interpret)
        self._stripe = stripe if stripe < Lblk else None   # the remap
        self.mem_static = {"mem_mode": "none"}
        if img.has_memory:
            self.mem_static = {
                "mem_mode": "hbm_window" if mem_hbm else "resident",
                "lane_block": Lblk}
            if mem_hbm:
                self.mem_static["window"] = f"{self.HBM_WINDOW_ROWS}x2"
        self.obs.set_memory_static(self.mem_static)
        v128_t = np.asarray(img.v128, np.int32)
        self._kargs = (
            used, D, CD, W, self.lanes, Lblk, NG, img.code_len,
            len(img.f_entry), img.table0.shape[0],
            img.max_local_zeros, pages_cap, pages_hard,
            (not mem_hbm) and W * Lblk <= self.MAX_GATHER_ELEMS,
            interpret, mem_hbm,
            self.HBM_WINDOW_ROWS if mem_hbm else 0,
            block_shapes, bool(img.has_simd), v128_t.shape[0])
        self._tables = tuple(jnp.asarray(t) for t in (
            hid_dense, a_p, b_p, c_p, ilo_p, ihi_p,
            img.f_entry, img.f_nparams, img.f_nlocals, img.f_frame_top,
            img.f_type, img.br_table.reshape(-1), img.table0, v128_t))
        self._fn = self._with_export_cache(
            lambda: _build_kernel(*self._kargs,
                                  optimistic=self.optimistic,
                                  snap_steps=self.SNAP_STEPS,
                                  shadow_full=self.optimistic,
                                  hid_weights=self._hid_weights,
                                  softfloat=self.counts_softfloat,
                                  indirect=self.counts_indirect))
        self._fn_careful_cache = None if self.optimistic else self._fn
        self.donated_planes = donated_planes(self._fn, self._arg_specs())
        self.obs.set_donation_static(self.donated_planes)

    def _export_cache_key(self):
        """Content key for the serialized compiled kernel: geometry +
        fused-plane hash + backend + jax version (the reference keys its
        AOT cache on the wasm bytes, lib/aot/cache.cpp:36-61; here the
        kernel is a function of the fused encoding and geometry)."""
        import hashlib

        import jax

        import inspect

        h = hashlib.sha256()
        # the kernel SOURCE is part of the key: any edit to the kernel
        # body must invalidate previously exported artifacts.  The
        # traced kernel also inlines helpers from sibling modules
        # (laneops alu/shift/mul emulation, image opcode encodings,
        # softfloat, simdops) — a semantic change there must invalidate
        # too, so hash the whole modules, not just this file.
        h.update(inspect.getsource(_build_kernel).encode())
        import wasmedge_tpu.batch.image as _image_mod
        import wasmedge_tpu.batch.laneops as _laneops_mod
        import wasmedge_tpu.batch.simdops as _simdops_mod
        import wasmedge_tpu.batch.softfloat as _softfloat_mod
        for _m in (_laneops_mod, _softfloat_mod, _simdops_mod, _image_mod):
            h.update(inspect.getsource(_m).encode())
        h.update(inspect.getsource(plan_dispatch_tree).encode())
        h.update(repr(self._kargs).encode())
        h.update(repr((self.optimistic, self.SNAP_STEPS,
                       self._hid_weights)).encode())
        for k in ("hid", "a", "b", "c", "ilo", "ihi"):
            h.update(np.ascontiguousarray(self._np_fused[k]).tobytes())
        h.update(jax.__version__.encode())
        h.update(jax.default_backend().encode())
        return h.hexdigest()

    def _with_export_cache(self, build):
        """Warm-start path: persist the traced+lowered kernel via
        jax.export so a fresh process skips Python/Pallas tracing; XLA's
        persistent compilation cache already covers the compile itself, and the
        exports live in its directory (`kexport/`).  A failure is
        reported on stderr (once per distinct failure) and falls back
        to a plain build — the cache is an optimization, never a
        correctness dependency.

        A launch of what comes back donates the memory planes, as the
        build's own jit does (`_DONATED_PLANES`): `exp.call` alone runs
        the kernel's in-place aliasing inside a program whose arguments
        are not donated, and XLA then copies every aliased plane around
        the launch, the memory plane among them."""
        import os

        if self._interpret():
            return build()  # interpret mode: nothing worth persisting
        try:
            import jax
            import jax.export as jexport

            d = os.path.join(jax.config.jax_compilation_cache_dir,
                             "kexport")
            path = os.path.join(d, self._export_cache_key() + ".bin")
            if os.path.exists(path):
                with open(path, "rb") as f:
                    exp = jexport.deserialize(bytearray(f.read()))
            else:
                exp = jexport.export(build())(*self._arg_specs())
                os.makedirs(d, exist_ok=True)
                from wasmedge_tpu.utils.fsio import atomic_write_bytes

                atomic_write_bytes(path, exp.serialize())
            from wasmedge_tpu.batch import jit_in_place

            return jit_in_place(exp.call, *_DONATED_PLANES)
        except Exception as e:
            import warnings

            warnings.warn(f"kernel export cache failed, rebuilding the "
                          f"kernel in-process: {e!r}", RuntimeWarning)
            return build()

    def _arg_specs(self):
        """ShapeDtypeStructs matching (tables..., ctrl, frames, state)."""
        import jax

        D, CD, W, Lblk = self._geom
        L = self.lanes
        nblk = L // Lblk
        NGp = max(self.img.globals_lo.shape[0], 1)
        mem_hbm = self._mem_mode()
        wsh = (W if (not mem_hbm and W > 1) else 1) if self.optimistic \
            else 1
        i32 = jax.ShapeDtypeStruct
        import numpy as _np

        specs = [i32(t.shape, t.dtype) for t in self._tables]
        specs += [i32((nblk, self.ctrl_width), _np.int32),
                  i32((nblk, 3, CD), _np.int32)]
        shapes = [(D, L), (D, L), (NGp, L), (NGp, L), (W, L), (1, L)]
        sh_l = L if self.optimistic else 1
        sh_d = D if self.optimistic else 1
        sh_ng = NGp if self.optimistic else 1
        shapes += [(sh_d, sh_l), (sh_d, sh_l), (sh_ng, sh_l),
                   (sh_ng, sh_l), (1, sh_l), (wsh, sh_l)]
        if self.img.has_simd:
            shapes += [(D, L), (D, L), (sh_d, sh_l), (sh_d, sh_l)]
        return specs + [i32(self._plane(*s), _np.int32) for s in shapes]

    def _plane(self, rows, lanes=None):
        """The shape of a plane of `rows` (`plane_shape`): a full-lane
        one in the kernel's layout, a placeholder ([1, 1]) as it is."""
        lanes = self.lanes if lanes is None else lanes
        if lanes != self.lanes:
            return (rows, lanes)
        return plane_shape(rows, lanes, self._stripe)

    def _fn_careful(self):
        """The non-optimistic kernel, compiled lazily on the first
        ST_RECHECK (most runs never diverge and never pay the compile)."""
        if self._fn_careful_cache is None:
            self._fn_careful_cache = _build_kernel(
                *self._kargs, optimistic=False,
                snap_steps=self.SNAP_STEPS, shadow_full=self.optimistic,
                hid_weights=self._hid_weights,
                softfloat=self.counts_softfloat,
                indirect=self.counts_indirect)
        return self._fn_careful_cache

    def enqueue_pass_record(self, state, nres, link):
        """Enqueue the pack of `state`'s pass record behind whatever
        program produced `state`; -> the record, still on the device.
        The program is one an engine (so one a geometry and `nres`: it
        compiles in a warm-up job, like the surgery pair)."""
        if self._pack_cache is None:
            self._pack_cache = _pass_record_fn()
        return link.enqueue("pack", self._pack_cache, state[0], state[1],
                            state[7], state[2], state[3], nres)

    def read_pass_record(self, record, nres, link) -> PassRecord:
        """The one download of a pass: blocks until the program the
        record was enqueued behind has ended."""
        return _split_pass_record(
            link.d2h("pass", record), self.lanes // self._geom[3],
            self._geom[1], self.lanes, nres, self.ctrl_width)

    def shadow_planes(self):
        """Fresh rollback-shadow planes matching this geometry (appended
        to the kernel state list; contents only matter intra-launch)."""
        import jax.numpy as jnp

        D, CD, W, Lblk = self._geom
        z = jnp.zeros
        if not self.optimistic:
            # careful-only kernel: placeholder shadows
            return [z((1, 1), jnp.int32) for _ in range(5)] + \
                [z((1, 1), jnp.int32)]
        NGp = max(self.img.globals_lo.shape[0], 1)
        wsh = W if (not self._mem_mode() and W > 1) else 1
        return [z(self._plane(n), jnp.int32)
                for n in (D, D, NGp, NGp, 1, wsh)]

    def _shadow_simd_planes(self):
        """Rollback shadows for the v128 e2/e3 planes (appended after
        them at the end of the state list)."""
        import jax.numpy as jnp

        D = self._geom[0]
        if not self.optimistic:
            return [jnp.zeros((1, 1), jnp.int32),
                    jnp.zeros((1, 1), jnp.int32)]
        return [jnp.zeros(self._plane(D), jnp.int32),
                jnp.zeros(self._plane(D), jnp.int32)]

    def careful_recheck(self, state, ctrl_np, chunk, snap, link, nres=0):
        """The block scheduler's recheck round: re-run the blocks whose
        `chunk` is not 0 (those a rollback left ST_RECHECK) on the
        careful kernel for that many steps.  An optimistic rollback
        rewound them to their last validated snapshot; exact per-step
        checking reaches the divergent instruction and stops there with
        the precise status (DIVERGED/trap/...), after which normal
        handling proceeds.  The other blocks run zero steps, their state
        untouched.  `snap` is every block's snapshot interval from here
        on (the scheduler's `_SnapPolicy`), written into the ctrl the
        round uploads.  Returns (state, record): the round's pass record
        (`PassRecord`, with rows [:nres] of the stacks), packed behind
        the careful kernel and downloaded once, its `ctrl` with the
        saved chunk restored and non-recheck step counts zeroed so
        callers' accounting is exact; that `ctrl` is uploaded again,
        the record's other parts are the planes as they stand.  Its two
        uploads, its download and its two enqueues go through the
        caller's `link` (HostLink)."""
        self.recheck_rounds += 1
        recheck_mask = chunk > 0
        ctrl = ctrl_np.copy()
        saved_chunk = ctrl[:, _C_CHUNK].copy()
        ctrl[:, _C_SNAP] = snap
        ctrl[:, _C_CHUNK] = chunk
        ctrl[:, _C_STATUS] = np.where(recheck_mask, ST_RUNNING,
                                      ctrl[:, _C_STATUS])
        state[0] = link.h2d("ctrl", ctrl)
        out = link.enqueue("careful", self._fn_careful(), *self._tables,
                           state[0], state[1], *state[2:])
        state = list(out)
        rec = self.read_pass_record(
            self.enqueue_pass_record(state, nres, link), nres, link)
        ctrl = rec.ctrl
        ctrl[:, _C_CHUNK] = saved_chunk
        # blocks that ran clean past the divergence window resume
        # optimistic on the next launch
        ctrl[:, _C_STEPS] = np.where(recheck_mask, ctrl[:, _C_STEPS], 0)
        state[0] = link.h2d("ctrl", ctrl)
        return state, rec

    # -- run --------------------------------------------------------------
    def run(self, func_name: str, args_lanes: List,
            max_steps: int = 10_000_000):
        """Run through the block scheduler (batch/scheduler.py): entry
        grouping packs same-args lanes into the same blocks, data
        divergence splits blocks instead of abandoning the kernel, and
        only the genuinely per-lane residue finishes on SIMT.
        `splits`, `launches`, `rechecks` (`recheck_rounds` is the same
        number), `careful_steps`, `surgery_programs`, `d2h_transfers`,
        `h2d_transfers` and `programs_enqueued` are this run's, and so
        are `hostcall_rounds`, `hostcall_calls`, `hostcall_vectorized`
        and `hostcall_out_bytes` (park, drain and re-arm cycles, lanes
        drained, those a vectorised implementation served, bytes the
        calls handed to an fd); the cached per-geometry engines keep
        their own growing `recheck_rounds`."""
        ex = self.inst.exports.get(func_name)
        if ex is None or ex[0] != 0:
            raise KeyError(f"no exported function {func_name}")
        if not self.eligible:
            return self.simt.run(func_name, args_lanes, max_steps)
        from wasmedge_tpu.batch.engine import new_hostcall_stats
        from wasmedge_tpu.batch.scheduler import BlockScheduler

        self.simt.hostcall_stats = new_hostcall_stats()   # this run's
        sched = BlockScheduler(self, func_name, args_lanes, max_steps)
        sched.run()
        self.fell_back_to_simt = sched.fell_back_to_simt
        self.splits = sched.splits
        self.quarantined = sched.quarantined
        self.launches = sched.launches
        self.recheck_rounds = self.rechecks = sched.rechecks
        self.careful_steps = sched.careful_steps
        self.surgery_programs = sched.surgery_programs
        link = sched.link
        self.d2h_transfers = link.d2h_transfers
        self.h2d_transfers = link.h2d_transfers
        self.programs_enqueued = link.programs_enqueued
        self.obs.add_split_counts(sched.splits, sched.launches,
                                  sched.rechecks, sched.careful_steps,
                                  sched.surgery_programs,
                                  d2h_transfers=link.d2h_transfers,
                                  h2d_transfers=link.h2d_transfers,
                                  programs_enqueued=link.programs_enqueued)
        # what the run's hostcall serves counted (the scheduler binds
        # its engine's `hostcall_stats` to this engine's for the run)
        hc = getattr(self.simt, "hostcall_stats", None) or {}
        self.hostcall_rounds = hc.get("serve_rounds", 0)
        self.hostcall_calls = hc.get("tier1_calls", 0)
        self.hostcall_vectorized = hc.get("tier1_vectorized", 0)
        self.hostcall_out_bytes = hc.get("out_bytes", 0)
        if self.hostcall_rounds:
            self.obs.add_hostcall_counts(
                self.hostcall_rounds, self.hostcall_calls,
                self.hostcall_vectorized, self.hostcall_out_bytes)
        self.aot_fused_verified = sched.eng.aot_fused_verified
        self.dispatch_depth = sched.eng.dispatch_depth
        self.mem_static = sched.eng.mem_static
        self.window_fills = sched.window_fills
        self.window_writebacks = sched.window_writebacks
        self.window_accesses = sched.window_accesses
        self.window_hit_share = \
            1 - sched.window_fills / sched.window_accesses \
            if sched.window_accesses else None
        self.obs.add_window_counts(sched.window_fills,
                                   sched.window_writebacks,
                                   sched.window_accesses)
        self.superblock_edges = sched.eng.superblock_edges
        self.block_shapes = sched.eng.block_shapes
        self.shuffle_sites = sched.eng.shuffle_sites
        self.donated_planes = sched.eng.donated_planes
        self.dispatches = sched.dispatches
        self.instr_per_dispatch = sched.kernel_steps / sched.dispatches \
            if sched.dispatches else None
        self.obs.add_dispatch_counts(sched.dispatches)
        if self.counts_softfloat:
            self.softfloat_ops = sched.softfloat_ops
            self.softfloat_share = \
                sched.softfloat_ops / sched.kernel_steps \
                if sched.kernel_steps else None
            self.obs.add_softfloat_counts(sched.softfloat_ops)
        if self.img.has_simd:
            self.simd_ops = sched.simd_ops
            self.simd_share = sched.simd_ops / sched.kernel_steps \
                if sched.kernel_steps else None
            self.obs.add_simd_counts(sched.simd_ops)
        if self.counts_indirect:
            self.indirect_ops = sched.indirect_ops
            self.obs.add_indirect_counts(sched.indirect_ops)
        return sched.result()

    def _hostcall_programs(self):
        if self._hostcall_fns_cache is None:
            self._hostcall_fns_cache = _hostcall_fns()
        return self._hostcall_fns_cache

    # A download of more than this comes down in pieces (below)
    ROWS_PIECE_BYTES = 8 << 20

    def _read_plane_rows(self, link, plane, w0, k, lane_major):
        """Rows [w0, w0 + k) of every lane of `plane` on the host: [k, L],
        or [L, k] with `lane_major`, cut (and transposed) on the device.

        A payload (a `fd_write` of 8 KiB a lane is 32 MiB at 4096
        lanes) comes down in pieces of at most `ROWS_PIECE_BYTES`, cut
        by the one program, their copies started at once, each laid into
        a buffer the engine keeps from round to round.  One download of
        the whole would land in 32 MiB the allocator maps anew every
        round (over its mmap threshold, whatever it is set to), and the
        page faults of that cost more than the link does: 17 ms against
        4.4 for the pieces on a v5e host, where a fresh 32 MiB touched
        once a page costs 34 (PR 40).  The buffer is the caller's until
        the next read of that shape: a serve has written it out by
        then."""
        lanes = int(np.prod(plane.shape[1:]))
        shape = (lanes, k) if lane_major else (k, lanes)
        pieces = min(-(-4 * k * lanes // self.ROWS_PIECE_BYTES), shape[0])
        parts = link.enqueue(
            "hc_rows", self._hostcall_programs()["rows"], plane,
            np.int32(w0), k, lane_major, max(pieces, 1))
        if len(parts) == 1:
            return link.d2h("mem_rows", parts[0])
        for part in parts:
            part.copy_to_host_async()
        out = self._rows_buffers.get(shape)
        if out is None:
            out = self._rows_buffers[shape] = np.empty(shape, np.int32)
        a = 0
        for part in parts:
            out[a:a + part.shape[0]] = link.d2h("mem_rows", part)
            a += part.shape[0]
        return out

    def _serve_hostcalls_begin(self, state, ctrl_np, valid_blocks, link):
        """Phase 1 of the outcall serve: capture every device-side read
        the serve needs — parked blocks' metas and ctrl rows, the two
        stack slabs covering all argument rows, and the parked blocks'
        memory columns as an array the next launch's donation cannot
        invalidate.  After this returns, the caller may launch the next
        kernel round; phase 2 never touches the launched planes for
        reads.

        Where every column of the plane is parked, no block is left to
        launch before phase 2, so the live plane itself is that array
        and nothing is copied; otherwise the columns are gathered on
        the device into a buffer of their own.

        Transfer discipline (each host-link transfer pays a fixed
        latency, and each is a leaf span of `link`, the caller's
        HostLink): guest memory goes through a PlaneMemoryCache over
        those columns, which cuts (and transposes) the rows of an access
        at one address in every lane on the device, fetches 4 KiB row
        chunks for ALL lanes at once for any other, and writes back the
        written rows only — per-lane data never rides the link alone
        (the "vectorized memory views" serve, SURVEY §5.8/§7(d))."""
        img = self.img
        D, CD, W, Lblk = self._geom
        t_begin = self.obs.now()
        blocks = [int(b) for b in
                  np.nonzero(ctrl_np[:, _C_STATUS] == ST_HOSTCALL)[0]]
        metas = []
        max_row = 0
        for b in blocks:
            pc = int(ctrl_np[b, _C_PC])
            k = int(img.a[pc])
            fi = self.simt.resolve_func(k)
            nargs = len(fi.functype.params)
            metas.append((b, pc, k, fi, nargs,
                          int(ctrl_np[b, _C_FP]), int(ctrl_np[b, _C_OB]),
                          int(ctrl_np[b, _C_PAGES]),
                          ctrl_np[b].copy()))
            max_row = max(max_row, int(ctrl_np[b, _C_FP]) + nargs)
        has_mem = img.has_memory and bool(blocks)
        cols = np.concatenate(
            [np.arange(b * Lblk, (b + 1) * Lblk, dtype=np.int32)
             for b in blocks]) if blocks else np.zeros(0, np.int32)
        mem_cols = None
        if has_mem:
            mem_cols = state[6] if cols.size == self.lanes else \
                link.enqueue("hc_gather",
                             self._hostcall_programs()["gather"],
                             state[6], cols)
        slab_lo = lanes_of(link.d2h("slab_lo", state[2], np.s_[:max_row])) \
            if max_row else None
        slab_hi = lanes_of(link.d2h("slab_hi", state[3], np.s_[:max_row])) \
            if max_row else None
        obs = self.obs
        if obs.enabled and blocks:
            obs.span("serve_begin", t_begin, cat="scheduler",
                     track="serve", blocks=len(blocks))
            # queue depth counts REAL parked lanes: pad (clone) lanes
            # are never served, so a near-empty block must not inflate
            # the counter track by Lblk
            obs.counter("hostcall_queue_depth", sum(
                int(valid_blocks[b].sum())
                if valid_blocks.get(b) is not None else Lblk
                for b in blocks))
        return {"blocks": blocks, "metas": metas,
                "mem_cols": mem_cols, "slab_lo": slab_lo,
                "slab_hi": slab_hi, "Lblk": Lblk, "link": link,
                "valid_blocks": valid_blocks}

    def _serve_hostcalls_finish(self, state, pending):
        """Phase 2: run the host functions (vectorized per block where
        a tier-1 SoA WASI implementation exists, per-lane otherwise)
        and apply the results — result rows, trap columns, and written
        memory rows go back through compiled programs that set them in
        place; re-armed ctrl rows are RETURNED for the caller to fold
        into its ctrl mirror (the kernel may be mid-flight on the other
        blocks).  Every crossing goes through the link of phase 1.

        valid_blocks: {block: bool[Lblk]} from the scheduler — pad
        (clone) lanes are NOT served (a host function's side effects
        must fire once per real instance, never for padding); their
        result columns and memory writes are replayed from the block's
        first valid lane (their clone source), keeping them converged.

        The run's counts (`hostcall_stats` of the engine's SIMT twin,
        which the block scheduler binds to the outer engine's):
        `serve_rounds`, `tier1_calls`, `tier1_vectorized`, and
        `out_bytes`, what the calls handed to an fd (the environ's own
        count, whichever path served them)."""
        from wasmedge_tpu.batch.hostcall import (
            PlaneMemoryCache,
            _CachedLaneMemory,
            make_cached_view,
            serve_one,
            vec_impl_for,
        )
        from wasmedge_tpu.host.wasi.vectorized import NotVectorizable

        img = self.img
        D, CD, W, Lblk = self._geom
        metas = pending["metas"]
        link = pending["link"]
        fns = self._hostcall_programs()
        valid_blocks = pending["valid_blocks"]
        slab_lo = pending["slab_lo"]
        slab_hi = pending["slab_hi"]
        has_mem = img.has_memory and pending["mem_cols"] is not None
        cache = None
        if has_mem:
            mem_cols = pending["mem_cols"]

            def read_rows(w0, k, lane_major):
                return self._read_plane_rows(link, mem_cols, w0, k,
                                             lane_major)

            cache = PlaneMemoryCache(mem_cols, d2h=link.d2h,
                                     read_rows=read_rows)
        plane_cap = (W // _PAGE_WORDS) if has_mem else 0
        if img.mem_pages_max > 0:
            max_pages = min(img.mem_pages_max, plane_cap)
        else:
            max_pages = plane_cap or None
        use_vec = bool(getattr(self.cfg, "vectorized_hostcalls", True))
        stats = getattr(self.simt, "hostcall_stats", None)
        rearms = {}
        obs = self.obs
        t_finish = obs.now()
        from wasmedge_tpu.host.wasi.vectorized import set_drain_recorder

        prev_rec = set_drain_recorder(obs)

        def set_results(lo_col, ob, nres, res_lo, res_hi):
            if nres:
                state[2], state[3] = link.enqueue(
                    "hc_results", fns["results"], state[2], state[3],
                    res_lo[:nres], res_hi[:nres], np.int32(ob),
                    np.int32(lo_col))

        try:
            for bi, (b, pc, k, fi, nargs, fp, ob, pages, cc) in \
                    enumerate(metas):
                lo_col = b * Lblk      # absolute columns (slab / state)
                loc = bi * Lblk        # local columns (gathered mem cache)
                vmask = valid_blocks.get(b)
                nres = int(img.f_nresults[k])
                res_lo = np.zeros((max(nres, 1), Lblk), np.int32)
                res_hi = np.zeros((max(nres, 1), Lblk), np.int32)
                trap_codes = np.zeros(Lblk, np.int32)
                new_pages = np.full(Lblk, pages, np.int32)
                env = getattr(getattr(fi, "host", None), "_env", None)
                out0 = getattr(env, "bytes_written", 0)
                if stats is not None:
                    n_real = int(vmask.sum()) if vmask is not None else Lblk
                    stats["serve_rounds"] += 1 if bi == 0 else 0
                    stats["tier1_calls"] += n_real
                served_vec = False
                if use_vec and has_mem and getattr(fi, "kind", None) == "host":
                    vecfn, venv = vec_impl_for(fi)
                    if vecfn is not None:
                        from wasmedge_tpu.batch.hostcall import \
                            gather_arg_cells

                        vsel = np.arange(Lblk, dtype=np.int64) \
                            if vmask is None else \
                            np.nonzero(vmask)[0].astype(np.int64)
                        fp_vec = np.full(slab_lo.shape[1], fp, np.int64)
                        args = gather_arg_cells(slab_lo, slab_hi, fp_vec,
                                                lo_col + vsel, nargs)
                        view = make_cached_view(cache, loc + vsel,
                                                np.full(vsel.size, pages))
                        try:
                            cells, codes = vecfn(venv, view, args)
                            served_vec = True
                        except NotVectorizable:
                            served_vec = False
                        if served_vec:
                            if stats is not None:
                                stats["tier1_vectorized"] += int(vsel.size)
                            cu = cells.astype(np.uint64)
                            for r in range(cells.shape[0]):
                                res_lo[r, vsel] = (
                                    cu[r] & np.uint64(0xFFFFFFFF)).astype(
                                        np.uint32).view(np.int32)
                                res_hi[r, vsel] = (
                                    cu[r] >> np.uint64(32)).astype(
                                        np.uint32).view(np.int32)
                            trap_codes[vsel] = codes
                if not served_vec:
                    t_drain = obs.now()
                    for li in range(Lblk):
                        if vmask is not None and not vmask[li]:
                            continue  # pad lane: replayed from clone below
                        args = []
                        for i in range(nargs):
                            a_lo = int(np.uint32(slab_lo[fp + i, lo_col + li]))
                            a_hi = int(np.uint32(slab_hi[fp + i, lo_col + li]))
                            args.append(a_lo | (a_hi << 32))
                        lane_mem = None
                        if has_mem:
                            lane_mem = _CachedLaneMemory(
                                cache, loc + li, pages, max_pages, plane_cap)
                        out, code = serve_one(fi, args, lane_mem)
                        if code:
                            trap_codes[li] = code
                            continue
                        for i, cell in enumerate(out):
                            res_lo[i, li] = np.int32(
                                np.uint32(cell & 0xFFFFFFFF))
                            res_hi[i, li] = np.int32(
                                np.uint32((cell >> 32) & 0xFFFFFFFF))
                        if has_mem:
                            new_pages[li] = lane_mem.pages
                    if obs.enabled:
                        from wasmedge_tpu.batch.hostcall import hostcall_kind

                        n_real = int(vmask.sum()) if vmask is not None else Lblk
                        obs.hostcall(hostcall_kind(fi), obs.now() - t_drain,
                                     lanes=n_real, vectorized=False)
                if stats is not None:
                    stats["out_bytes"] += \
                        getattr(env, "bytes_written", 0) - out0
                if vmask is not None and not vmask.all():
                    src = int(np.argmax(vmask))  # first valid = clone source
                    pads = np.nonzero(~vmask)[0]
                    for li in pads:
                        res_lo[:, li] = res_lo[:, src]
                        res_hi[:, li] = res_hi[:, src]
                        trap_codes[li] = trap_codes[src]
                        new_pages[li] = new_pages[src]
                    if has_mem:
                        # replay the clone source's memory writes onto pads
                        for (off, n) in cache.writes_of(loc + src):
                            data = cache.read_bytes(loc + src, off, n)
                            for li in pads:
                                cache.write_bytes(loc + int(li), off, data)
                grew = (new_pages != pages) & (trap_codes == 0)
                if trap_codes.any() or grew.any():
                    # Per-lane outcomes: record them, re-arm at pc+1 with the
                    # served lanes' results applied (their host calls MUST
                    # NOT re-run), then leave the block DIVERGED for the
                    # scheduler to partition per lane.
                    state[7] = link.enqueue(
                        "hc_trap", fns["trap"], state[7], trap_codes,
                        np.int32(lo_col))
                    if grew.any():
                        self._pages_override[b] = new_pages.copy()
                    if (trap_codes != 0).all() and \
                            len(set(trap_codes.tolist())) == 1:
                        cc[_C_STATUS] = ST_TRAPPED_BASE + int(trap_codes[0])
                        rearms[b] = cc
                        continue
                    set_results(lo_col, ob, nres, res_lo, res_hi)
                    cc[_C_PC] = pc + 1
                    cc[_C_SP] = ob + nres
                    cc[_C_STATUS] = ST_DIVERGED
                    rearms[b] = cc
                    continue
                set_results(lo_col, ob, nres, res_lo, res_hi)
                cc[_C_PC] = pc + 1
                cc[_C_SP] = ob + nres
                cc[_C_STATUS] = ST_RUNNING
                rearms[b] = cc
        finally:
            set_drain_recorder(prev_rec)
        if has_mem:
            # the rows written this round go back to the live plane,
            # each block's columns in place
            for row0, rows in cache.dirty_rows():
                for bi, meta in enumerate(metas):
                    part = rows if len(metas) == 1 else \
                        np.ascontiguousarray(
                            rows[:, bi * Lblk:(bi + 1) * Lblk])
                    state[6] = link.enqueue(
                        "hc_scatter", fns["set_rows"], state[6], part,
                        np.int32(row0), np.int32(meta[0] * Lblk))
        if obs.enabled and metas:
            obs.span("serve_finish", t_finish, cat="scheduler",
                     track="serve", blocks=len(metas))
        return state, rearms
