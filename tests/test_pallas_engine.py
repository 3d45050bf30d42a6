"""Pallas warp-interpreter parity suite (interpret mode on CPU).

The Pallas engine must agree lane-by-lane with the scalar oracle through
the same staging — the engine-swap discipline of the reference's SpecTest
seam (/root/reference/test/spec/spectest.h:62-90).  On CPU the kernel runs
in pallas interpret mode, which executes the identical kernel program the
TPU runs (minus Mosaic lowering), so the dispatch-loop logic, the
divergence bail-outs, and the SIMT handoff are all exercised by pytest.
"""

import numpy as np
import pytest

from wasmedge_tpu.batch import pallas_engine as pe
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import ErrCode, TrapError
from wasmedge_tpu.models import (
    build_coremark_kernel,
    build_fac,
    build_fib,
    build_loop_sum,
    build_memory_workload,
)
from wasmedge_tpu.utils.builder import ModuleBuilder
from tests.helpers import instantiate

LANES = 8


def make_engine(data: bytes, lanes=LANES, chunk=50_000, conf=None):
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine

    conf = conf or Configure()
    conf.batch.steps_per_launch = chunk
    ex, store, inst = instantiate(data, conf)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=lanes,
                              interpret=True)
    return ex, store, inst, eng


def scalar_call(ex, store, inst, func, args):
    fi = inst.find_func(func)
    return ex.invoke(store, fi, [int(a) for a in args])


def check_parity(data, func, per_lane_args, max_steps=2_000_000,
                 conf=None):
    """Run batch vs scalar; compare per-lane values and trap codes.

    Each lane gets a *fresh* scalar instance: batch lanes are independent
    instances, so scalar state (globals/memory) must not leak across the
    per-lane oracle calls."""
    ex, store, inst, eng = make_engine(data, conf=conf)
    args = [np.asarray(a, np.int64) for a in per_lane_args]
    res = eng.run(func, args, max_steps=max_steps)
    for lane in range(LANES):
        lane_args = [int(a[lane]) for a in args]
        s_ex, s_store, s_inst = instantiate(data, conf or Configure())
        try:
            expect = scalar_call(s_ex, s_store, s_inst, func, lane_args)
            assert res.trap[lane] == -1, \
                f"lane {lane}: batch trapped {res.trap[lane]}, scalar ok"
            from wasmedge_tpu.common.types import typed_to_bits

            rtypes = s_inst.find_func(func).functype.results
            for ri, val in enumerate(expect):
                got = int(res.results[ri][lane]) & ((1 << 64) - 1)
                want = typed_to_bits(rtypes[ri], val)
                assert got == want, \
                    f"lane {lane}: got {got:#x}, scalar {want:#x} ({val})"
        except TrapError as te:
            assert res.trap[lane] == int(te.code), \
                f"lane {lane}: batch trap {res.trap[lane]} != scalar {te.code}"
    return eng, res


def test_fib_uniform_stays_on_pallas():
    eng, res = check_parity(build_fib(), "fib",
                            [np.full(LANES, 10, np.int64)])
    assert not eng.fell_back_to_simt
    assert res.results[0][0] == 55


def test_fib_divergent_args_split_on_kernel():
    # different n per lane -> control divergence -> the block scheduler
    # splits blocks at the divergent branch and keeps everything on the
    # Pallas kernel (no whole-batch SIMT abandonment)
    ns = np.array([3, 5, 8, 2, 9, 4, 7, 6], np.int64)
    eng, res = check_parity(build_fib(), "fib", [ns])
    assert not eng.fell_back_to_simt
    assert eng.splits > 0


def test_fac_i64_uniform():
    eng, res = check_parity(build_fac(), "fac",
                            [np.full(LANES, 12, np.int64)])
    assert res.results[0][0] == 479001600


def test_loop_sum():
    check_parity(build_loop_sum(), "loop_sum",
                 [np.full(LANES, 1000, np.int64)])


def test_memory_workload_uniform():
    # loads/stores with lane-uniform addresses stay on the pallas path
    eng, res = check_parity(build_memory_workload(), "mem_checksum",
                            [np.full(LANES, 64, np.int64)])
    assert not eng.fell_back_to_simt


def test_coremark_kernel():
    check_parity(build_coremark_kernel(), "coremark",
                 [np.full(LANES, 8, np.int64)])


def test_div_by_zero_all_lanes():
    b = ModuleBuilder()
    b.add_function(("i32",), ("i32",), (),
                   [("local.get", 0), ("i32.const", 0), ("i32.div_s",)],
                   export="f")
    check_parity(b.build(), "f", [np.full(LANES, 7, np.int64)])


def test_div_by_zero_some_lanes_diverges():
    # lane-dependent divisor: lanes 0,4 trap, others don't
    b = ModuleBuilder()
    b.add_function(("i32", "i32"), ("i32",), (),
                   [("local.get", 0), ("local.get", 1), ("i32.div_s",)],
                   export="f")
    divisors = np.array([0, 1, 2, 3, 0, 5, 6, 7], np.int64)
    eng, res = check_parity(b.build(), "f",
                            [np.full(LANES, 42, np.int64), divisors])
    # the scheduler peels the trapped lanes off; no SIMT pass needed
    assert not eng.fell_back_to_simt
    assert res.trap[0] == int(ErrCode.DivideByZero)
    assert res.trap[1] == -1


def test_unreachable_traps():
    b = ModuleBuilder()
    b.add_function((), ("i32",), (), [("unreachable",)], export="f")
    check_parity(b.build(), "f", [])


def test_call_indirect_parity():
    b = ModuleBuilder()
    b.add_function(("i32",), ("i32",), (),
                   [("local.get", 0), ("i32.const", 10), ("i32.add",)])
    b.add_function(("i32",), ("i32",), (),
                   [("local.get", 0), ("i32.const", 3), ("i32.mul",)])
    ti = b.add_type(("i32",), ("i32",))
    b.add_table("funcref", 2)
    b.add_active_elem(0, [("i32.const", 0)], [0, 1])
    b.add_function(("i32", "i32"), ("i32",), (),
                   [("local.get", 0), ("local.get", 1),
                    ("call_indirect", ti, 0)], export="dispatch")
    check_parity(b.build(), "dispatch",
                 [np.full(LANES, 5, np.int64), np.full(LANES, 1, np.int64)])


def test_br_table_uniform():
    b = ModuleBuilder()
    b.add_function(
        ("i32",), ("i32",), (),
        [("block",), ("block",), ("block",),
         ("local.get", 0), ("br_table", [0, 1], 2),
         ("end",), ("i32.const", 100), ("return",),
         ("end",), ("i32.const", 200), ("return",),
         ("end",), ("i32.const", 300)],
        export="f")
    for sel in (0, 1, 7):
        check_parity(b.build(), "f", [np.full(LANES, sel, np.int64)])


def test_globals_and_memory_grow():
    b = ModuleBuilder()
    b.add_memory(1, 3)
    b.add_global("i32", True, [("i32.const", 5)])
    b.add_function(
        ("i32",), ("i32",), (),
        [("global.get", 0), ("local.get", 0), ("i32.add",),
         ("global.set", 0),
         ("i32.const", 1), ("memory.grow",), ("drop",),
         ("memory.size",), ("global.get", 0), ("i32.add",)],
        export="f")
    conf = Configure()
    # static batch memory: the knob must cover the workload's peak pages
    # for grow parity (documented knob-dependent semantics, engine.py)
    conf.batch.memory_pages_per_lane = 3
    check_parity(b.build(), "f", [np.full(LANES, 3, np.int64)], conf=conf)


def test_unaligned_and_subword_memory():
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(
        ("i32", "i32"), ("i32",), (),
        [("local.get", 0), ("local.get", 1), ("i32.store", 0, 1),
         ("local.get", 0), ("i32.load", 0, 1),
         ("local.get", 0), ("i32.load8_u", 0, 3), ("i32.add",),
         ("local.get", 0), ("i32.load16_s", 0, 1), ("i32.add",)],
        export="f")
    # odd base address -> unaligned store/load spanning words
    check_parity(b.build(), "f",
                 [np.full(LANES, 13, np.int64),
                  np.full(LANES, 0x7F61_43A5, np.int64)])


def test_divergent_addresses_gathered():
    """Per-lane addresses differ: compare-reduce gather path (W small)."""
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(
        ("i32", "i32"), ("i32",), (),
        [("local.get", 0), ("local.get", 1), ("i32.store", 0, 2),
         ("local.get", 0), ("i32.load", 0, 2)],
        export="f")
    addrs = np.array([0, 8, 16, 24, 4, 12, 20, 28], np.int64)
    vals = np.arange(LANES, dtype=np.int64) * 1000 + 7
    eng, res = check_parity(b.build(), "f", [addrs, vals])
    # divergent addresses are data divergence, not control divergence:
    # the gather path keeps the block on-device
    assert not eng.fell_back_to_simt


def test_memory_oob_some_lanes():
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(
        ("i32",), ("i32",), (),
        [("local.get", 0), ("i32.load", 0, 2)],
        export="f")
    addrs = np.array([0, 4, 8, 0x10000, 12, 16, 0xFFFFF0, 20], np.int64)
    eng, res = check_parity(b.build(), "f", [addrs])
    assert res.trap[3] == int(ErrCode.MemoryOutOfBounds)
    assert res.trap[0] == -1


def test_deep_recursion_call_stack_exhausted():
    conf = Configure()
    conf.batch.call_stack_depth = 16
    b = ModuleBuilder()
    b.add_function(("i32",), ("i32",), (),
                   [("local.get", 0), ("i32.const", 1), ("i32.add",),
                    ("call", 0)], export="f")
    ex, store, inst, eng = make_engine(b.build(), conf=conf)
    res = eng.run("f", [np.zeros(LANES, np.int64)], max_steps=100_000)
    assert (res.trap == int(ErrCode.CallStackExhausted)).all()


def test_steps_match_xla_uniform_engine():
    """Retired-step parity with the XLA uniform engine on the same run."""
    from wasmedge_tpu.batch.uniform import UniformBatchEngine

    data = build_fib()
    conf = Configure()
    conf.batch.steps_per_launch = 50_000
    conf.batch.use_pallas = False   # reference engine must stay XLA
    ex, store, inst = instantiate(data, conf)
    xla = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    r1 = xla.run("fib", [np.full(LANES, 9, np.int64)], max_steps=200_000)
    ex2, store2, inst2, eng = make_engine(data)
    r2 = eng.run("fib", [np.full(LANES, 9, np.int64)], max_steps=200_000)
    assert r1.steps == r2.steps
    assert (np.asarray(r1.results[0]) == np.asarray(r2.results[0])).all()


def test_bulk_memory_fill_and_copy():
    """memory.fill/copy on the batch engines vs the scalar oracle,
    including overlapping copies (memmove semantics) and per-lane args."""
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(
        ("i32", "i32", "i32"), ("i32",), (),
        [("local.get", 0), ("local.get", 1), ("local.get", 2),
         ("memory.fill",),
         # copy [dst+2, dst+2+n) <- [dst, dst+n) (overlap forward)
         ("local.get", 0), ("i32.const", 2), ("i32.add",),
         ("local.get", 0), ("local.get", 2), ("memory.copy",),
         # checksum a window
         ("local.get", 0), ("i32.load", 0, 2),
         ("local.get", 0), ("i32.load", 0, 6), ("i32.add",),
         ("local.get", 0), ("i32.load8_u", 0, 11), ("i32.add",)],
        export="f")
    dsts = np.array([0, 8, 13, 100, 255, 1000, 4093, 64], np.int64)
    vals = np.arange(LANES, dtype=np.int64) + 0xA0
    ns = np.array([4, 9, 16, 3, 8, 32, 1, 64], np.int64)
    eng, res = check_parity(b.build(), "f", [dsts, vals, ns])


def test_bulk_memory_oob_lanes():
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32", "i32"), (), (),
                   [("local.get", 0), ("i32.const", 0x5A),
                    ("local.get", 1), ("memory.fill",)], export="f")
    dsts = np.array([0, 0xFFF0, 0, 4, 8, 12, 16, 20], np.int64)
    ns = np.array([4, 0x20, 0, 4, 4, 4, 4, 4], np.int64)  # lane 1 OOB
    eng, res = check_parity(b.build(), "f", [dsts, ns])
    assert res.trap[1] == int(ErrCode.MemoryOutOfBounds)


def test_fill_and_copy_stay_on_pallas():
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32",), ("i32",), (),
                   [("i32.const", 16), ("local.get", 0), ("i32.const", 8),
                    ("memory.fill",),
                    ("i32.const", 16), ("i32.load", 0, 2)], export="fill")
    eng, res = check_parity(b.build(), "fill",
                            [np.full(LANES, 0x7F, np.int64)])
    assert not eng.fell_back_to_simt

    b2 = ModuleBuilder()
    b2.add_memory(1, 1)
    b2.add_function(("i32",), ("i32",), (),
                    [("i32.const", 0), ("local.get", 0), ("i32.store", 2, 0),
                     ("i32.const", 32), ("i32.const", 0), ("i32.const", 4),
                     ("memory.copy",),
                     ("i32.const", 32), ("i32.load", 0, 2)], export="cp")
    eng2, res2 = check_parity(b2.build(), "cp",
                              [np.full(LANES, 0xBEEF, np.int64)])
    assert not eng2.fell_back_to_simt  # uniform-delta copy runs in-kernel


def test_memcopy_unaligned_overlap_in_kernel():
    # per-lane dst with a uniform (src - dst) delta, including overlapping
    # forward and backward moves and sub-word byte shifts
    for delta in (5, -5, 3, -3, 64, -64, 1, 0):
        dsts = np.array([100 + k for k in range(LANES)], np.int64)
        srcs = dsts + delta
        ns = np.array([1, 2, 3, 4, 7, 9, 16, 31], np.int64)
        b3 = ModuleBuilder()
        b3.add_memory(1, 1)
        body = []
        for i in range(0, 128, 4):
            body += [("i32.const", i),
                     ("i32.const", (i * 0x01010101 + 0x0F1E2D3C) & 0x7FFFFFFF),
                     ("i32.store", 2, 0)]
        body += [("local.get", 0), ("local.get", 1), ("local.get", 2),
                 ("memory.copy",),
                 ("local.get", 0), ("i32.load", 0, 0)]
        b3.add_function(("i32", "i32", "i32"), ("i32",), (), body,
                        export="cp")
        eng, res = check_parity(b3.build(), "cp", [dsts, srcs, ns])
        assert not eng.fell_back_to_simt, f"delta {delta} fell back"


def test_memcopy_divergent_delta_falls_back():
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32", "i32"), ("i32",), (),
                   [("i32.const", 0), ("i32.const", 0x11223344),
                    ("i32.store", 2, 0),
                    ("i32.const", 64), ("i32.const", 0x55667788),
                    ("i32.store", 2, 0),
                    ("local.get", 0), ("local.get", 1), ("i32.const", 4),
                    ("memory.copy",),
                    ("local.get", 0), ("i32.load", 0, 2)], export="cp")
    dsts = np.array([128, 128, 132, 132, 136, 140, 144, 148], np.int64)
    srcs = np.array([0, 64, 0, 64, 0, 64, 0, 64], np.int64)  # mixed deltas
    eng, res = check_parity(b.build(), "cp", [dsts, srcs])
    assert eng.fell_back_to_simt


def test_fuel_on_pallas_path():
    # fuel metering now runs in the kernel carry: the block trap is
    # CostLimitExceeded and the engine stays on the fast path
    conf = Configure()
    conf.batch.fuel_per_launch = 1000
    ex, store, inst, eng = make_engine(build_fib(), conf=conf)
    assert eng.eligible, eng.ineligible_reason
    res = eng.run("fib", [np.full(LANES, 25, np.int64)], max_steps=500_000)
    assert (res.trap == int(ErrCode.CostLimitExceeded)).all()

    conf2 = Configure()
    conf2.batch.fuel_per_launch = 10_000_000
    ex, store, inst, eng2 = make_engine(build_fib(), conf=conf2)
    res2 = eng2.run("fib", [np.full(LANES, 10, np.int64)],
                    max_steps=500_000)
    assert (res2.trap == -1).all()
    s_ex, s_store, s_inst = instantiate(build_fib(), Configure())
    expect = scalar_call(s_ex, s_store, s_inst, "fib", [10])
    assert int(res2.results[0][0]) == expect[0]


def test_memgrow_regrow_beyond_watermark():
    # init 1 page, declared max 3: the watermark plane holds 1 page, so a
    # legal grow to 2 pages must leave the kernel (ST_REGROW) and finish
    # on the SIMT engine with the right result
    conf = Configure()
    conf.batch.memory_pages_per_lane = 3
    b = ModuleBuilder()
    b.add_memory(1, 3)
    b.add_function((), ("i32",), (),
                   [("i32.const", 1), ("memory.grow",), "drop",
                    ("i32.const", 70000), ("i32.const", 0xCAFE),
                    ("i32.store", 2, 0),
                    ("i32.const", 70000), ("i32.load", 0, 2),
                    "drop",
                    ("memory.size",)], export="g")
    eng, res = check_parity(b.build(), "g", [], conf=conf)
    assert eng.fell_back_to_simt  # regrow handled by the big-plane engine


def _simd_wat_module():
    from wasmedge_tpu.utils.wat import parse_wat

    return parse_wat("""
(module
  (memory 1)
  (func (export "vmix") (param i32) (result i32)
    (local $acc v128)
    (local $i i32)
    (local.set $acc (v128.const i32x4 1 2 3 4))
    (block (loop
      (br_if 1 (i32.ge_u (local.get $i) (local.get 0)))
      (local.set $acc
        (i32x4.add (local.get $acc) (i32x4.splat (local.get $i))))
      (local.set $acc
        (v128.xor (local.get $acc)
                  (i8x16.shuffle 4 5 6 7 0 1 2 3 12 13 14 15 8 9 10 11
                                 (local.get $acc) (local.get $acc))))
      (local.set $i (i32.add (local.get $i) (i32.const 1)))
      (br 0)))
    ;; unaligned v128 store + load round-trip
    (v128.store offset=3 (i32.const 64) (local.get $acc))
    (local.set $acc (v128.load offset=3 (i32.const 64)))
    (i32.add
      (i32x4.extract_lane 1 (local.get $acc))
      (i32.add
        (i32x4.extract_lane 2
          (v128.bitselect (local.get $acc)
                          (v128.const i32x4 -1 -1 -1 -1)
                          (v128.const i32x4 0xFF00FF00 0x00FF00FF
                                            0xF0F0F0F0 0x0F0F0F0F)))
        (i32x4.extract_lane 3 (local.get $acc))))))
""")


def test_v128_through_pallas_kernel():
    # the v128 page runs IN the pallas kernel (handlers + 4-plane cells
    # + unaligned v128 load/store through the memory machinery)
    eng, res = check_parity(_simd_wat_module(), "vmix",
                            [np.full(LANES, 9, np.int64)])
    assert eng.eligible, eng.ineligible_reason
    assert not eng.fell_back_to_simt


def test_v128_divergent_lanes_recheck():
    # divergent per-lane loop counts force optimistic rollback + careful
    # recheck with v128 state riding the rollback shadow planes
    args = np.array([3, 3, 9, 9, 15, 15, 21, 21], np.int64)[:LANES]
    eng, res = check_parity(_simd_wat_module(), "vmix", [args])
    assert eng.eligible, eng.ineligible_reason


def test_v128_select_and_global_in_fused_block():
    # regression: fused-block select over v128 cells and global.get
    # feeding local.set must push full-width cells in simd modules
    from wasmedge_tpu.utils.wat import parse_wat

    wasm = parse_wat("""
(module
  (global $g (mut i32) (i32.const 7))
  (func (export "f") (param i32) (result i32)
    (local $v v128)
    (local $x i32)
    (local.set $v (v128.const i32x4 9 8 7 6))
    (local.set $v (select (local.get $v)
                          (v128.const i32x4 1 1 1 1)
                          (local.get 0)))
    (local.set $x (global.get $g))
    (i32.add (local.get $x)
             (i32x4.extract_lane 2 (local.get $v)))))
""")
    for arg in (0, 1):
        eng, res = check_parity(wasm, "f", [np.full(LANES, arg, np.int64)])
        assert eng.eligible, eng.ineligible_reason


# ---------------------------------------------------------------------------
# superblocks (PR 29): a fused block runs through forward `br`s (jumps)
# and taken forward guards (tails) into their targets
# ---------------------------------------------------------------------------
def scalar_retired(data, func, args, conf=None):
    """(results or TrapError code, instructions retired) of one scalar
    instance, counted by Statistics.instr_counting."""
    from wasmedge_tpu.common.statistics import Statistics
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf = conf or Configure()
    conf.statistics.instr_counting = True
    stat = Statistics(conf)
    ex = Executor(conf, stat)
    store = StoreManager()
    inst = ex.instantiate(
        store, Validator(conf).validate(Loader(conf).parse_module(data)))
    try:
        out = ex.invoke(store, inst.find_func(func), [int(a) for a in args])
    except TrapError as te:
        out = int(te.code)
    return out, stat.instr_count


def block_shapes_of(eng):
    inner = next(iter(eng.simt._sched_cache.values()), eng)
    return inner._kargs[17]


def has_op(shape, kind):
    """Does `shape` (or a tail in it) hold an op of `kind`?"""
    return any(op[0] == kind or
               (op[0] in ("guardz", "guardnz") and has_op(op[1], kind))
               for op in shape)


def tails_of(shapes):
    return [op[1] for shape in shapes for op in shape
            if op[0] in ("guardz", "guardnz") and op[1]]


def test_fib15_dispatch_count_and_scalar_parity():
    """fib(15) is 987 leaves and 986 inner calls: one dispatch a leaf
    and three an inner call, where plain blocks took two and four
    (5,918)."""
    eng, res = check_parity(build_fib(), "fib",
                            [np.full(LANES, 15, np.int64)])
    assert not eng.fell_back_to_simt and eng.splits == 0
    assert eng.dispatches == 987 + 3 * 986 == 3945
    out, retired = scalar_retired(build_fib(), "fib", [15])
    assert out == [610] and retired == 7 * 987 + 14 * 986
    assert np.asarray(res.retired).tolist() == [retired] * LANES
    assert eng.instr_per_dispatch == retired / 3945


def if_else_guest(result: bool) -> bytes:
    """f(x, y): an if/else whose arms leave to `end` by a forward `br`
    (nkeep 1 with a result, 0 with none), then more code at the `end`
    that the then-arm's jump runs into."""
    b = ModuleBuilder()
    if result:
        body = [
            ("local.get", 0),
            ("if", "i32"),
            ("local.get", 1), ("i32.const", 3), "i32.mul",
            "else",
            ("local.get", 1), ("i32.const", 7), "i32.add",
            "end",
            ("i32.const", 1), "i32.add",
        ]
    else:
        body = [
            ("local.get", 0),
            ("if", None),
            ("local.get", 1), ("i32.const", 3), "i32.mul", ("local.set", 2),
            "else",
            ("local.get", 1), ("i32.const", 7), "i32.add", ("local.set", 2),
            "end",
            ("local.get", 2), ("i32.const", 1), "i32.add",
        ]
    b.add_function(["i32", "i32"], ["i32"], ["i32"], body, export="f")
    return b.build()


@pytest.mark.parametrize("result", [True, False],
                         ids=["nkeep1", "nkeep0"])
@pytest.mark.parametrize("x", [0, 1], ids=["else-arm", "then-arm"])
def test_if_else_superblock_parity(result, x):
    data = if_else_guest(result)
    ys = np.arange(LANES, dtype=np.int64) + 5
    eng, res = check_parity(data, "f", [np.full(LANES, x, np.int64), ys])
    assert not eng.fell_back_to_simt
    shapes = block_shapes_of(eng)
    # the then-arm's `br end` is a jump, so its path is one superblock
    # down to the `return`; the else-arm is the guard's tail and falls
    # off at the `end`, a join that starts its own block
    assert ("jump", 1 if result else 0) in shapes[0]
    assert shapes[0][-1] == ("term", pe.H_RETURN)
    (tail,) = tails_of(shapes[:1])
    assert tail[-1][0] != "term" and not has_op(tail, "jump")
    assert eng.dispatches == (1 if x else 2)
    _out, retired = scalar_retired(data, "f", [x, 5])
    assert np.asarray(res.retired).tolist() == [retired] * LANES


def test_if_else_superblock_divergent_condition():
    data = if_else_guest(True)
    xs = np.array([0, 1, 0, 0, 1, 1, 0, 1], np.int64)
    ys = np.arange(LANES, dtype=np.int64) + 5
    eng, _res = check_parity(data, "f", [xs, ys])
    assert not eng.fell_back_to_simt


def test_trap_in_a_tail_call_stack_exhausted():
    """fib's inner call leaves block 0 through the guard's tail, whose
    terminal is the `call`: with five frames it traps there, at the
    call's own slot 9, after what the scalar engine retires when it is
    held to the same six frames."""
    from wasmedge_tpu.batch.scheduler import BlockScheduler

    conf = Configure()
    conf.batch.call_stack_depth = 6
    _ex, _store, _inst, eng = make_engine(build_fib(), conf=conf)
    sched = BlockScheduler(eng, "fib", [np.full(LANES, 12, np.int64)],
                           1_000_000)
    sched.launch()
    row = sched._ctrl()[0]
    res = eng.run("fib", [np.full(LANES, 12, np.int64)],
                  max_steps=1_000_000)
    sconf = Configure()
    sconf.runtime.max_call_depth = 6
    code, retired = scalar_retired(build_fib(), "fib", [12], conf=sconf)
    assert code == int(ErrCode.CallStackExhausted)
    assert (res.trap == code).all()
    assert np.asarray(res.retired).tolist() == [retired] * LANES
    assert tuple(int(row[c]) for c in (
        pe._C_STATUS, pe._C_STEPS, pe._C_PC)) == (
            pe.ST_TRAPPED_BASE + code, retired, 9)
    assert retired == 48


def oob_after_jump_guest() -> bytes:
    """f(x): x != 0 loads from 0x10000 (out of bounds), x == 0 from 4.
    The then-arm's `br end` is a jump, so the load at the `end` runs in
    the superblock after it; the loop head after it bounds the block."""
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(["i32"], ["i32"], ["i32"], [
        ("local.get", 0),
        ("if", "i32"), ("i32.const", 0x10000), "else", ("i32.const", 4),
        "end",
        ("i32.load", 2, 0),
        ("local.set", 1),
        ("loop", None),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.tee", 1),
        ("i32.const", 3), "i32.lt_u", ("br_if", 0),
        "end",
        ("local.get", 1),
    ], export="f")
    return b.build()


@pytest.mark.parametrize("xs", [[1] * LANES, [0] * LANES,
                                [0, 1, 0, 0, 1, 1, 0, 1]],
                         ids=["all-oob", "none-oob", "some-oob"])
def test_trap_after_a_jump_out_of_bounds_load(xs):
    from wasmedge_tpu.batch.scheduler import BlockScheduler

    data = oob_after_jump_guest()
    eng, res = check_parity(data, "f", [np.asarray(xs, np.int64)])
    shapes = block_shapes_of(eng)
    at = shapes[0].index(("jump", 1))
    assert shapes[0][at + 1][0] == "loadi"
    for lane, x in enumerate(xs):
        out, retired = scalar_retired(data, "f", [x])
        if x:
            assert out == int(ErrCode.MemoryOutOfBounds) == res.trap[lane]
        else:
            assert res.trap[lane] == -1
            # (a trapped lane's `retired` counts the trapping op or not
            # by engine; a finished one's is exact)
            assert res.retired[lane] == retired or len(set(xs)) > 1
    if len(set(xs)) == 1 and xs[0]:
        # the kernel stops un-advanced at the load's own slot (5), with
        # the four ops before it retired
        _ex, _store, _inst, eng2 = make_engine(data)
        sched = BlockScheduler(eng2, "f", [np.asarray(xs, np.int64)],
                               1_000_000)
        sched.launch()
        row = sched._ctrl()[0]
        assert (int(row[pe._C_STATUS]), int(row[pe._C_STEPS]),
                int(row[pe._C_PC])) == (pe.ST_DIVERGED, 4, 5)


def test_fuel_runs_out_inside_a_superblock():
    """Fuel 1000 on fib(12): the scalar engine runs 1000 instructions
    and traps on the next, the superblock kernel at the end of the
    dispatch that crossed the 1000th, less than MAX_BLOCK_LEN later;
    both kill."""
    from wasmedge_tpu.batch.scheduler import BlockScheduler

    conf = Configure()
    conf.batch.fuel_per_launch = 1000
    _ex, _store, _inst, eng = make_engine(build_fib(), conf=conf)
    sched = BlockScheduler(eng, "fib", [np.full(LANES, 12, np.int64)],
                           1_000_000)
    sched.launch()
    row = sched._ctrl()[0]
    sconf = Configure()
    sconf.statistics.cost_measuring = True
    sconf.statistics.cost_limit = 1000
    code, counted = scalar_retired(build_fib(), "fib", [12], conf=sconf)
    assert code == int(ErrCode.CostLimitExceeded)
    assert int(row[pe._C_STATUS]) == pe.ST_TRAPPED_BASE + code
    # the scalar engine counts the instruction that found the meter empty
    assert counted - 1 == 1000
    assert 1000 <= int(row[pe._C_STEPS]) < 1000 + pe.MAX_BLOCK_LEN
