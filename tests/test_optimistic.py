"""Optimistic-convergence mode: rollback/recheck correctness.

The optimistic kernel takes every block-level decision from lane 0 and
accumulates a divergence canary instead of reducing across lanes per
instruction (see _build_kernel's docstring).  These tests force each
rollback trigger — divergent branch conds, semantically-equal-but-
bitwise-different conds, partial-lane traps, divergent load addresses —
and check the recovered results stay lane-exact against the scalar
oracle, with the careful-kernel recheck path actually exercised.
"""

import numpy as np
import pytest

from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import ErrCode
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.utils.builder import ModuleBuilder
from tests.helpers import instantiate

LANES = 8


def make_engine(data, conf=None, lanes=LANES, hbm=None):
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine

    conf = conf or Configure()
    conf.batch.steps_per_launch = 50_000
    conf.batch.mem_hbm = hbm
    conf.batch.value_stack_depth = 64
    conf.batch.call_stack_depth = 32
    ex, store, inst = instantiate(data, conf)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=lanes,
                              interpret=True)
    return ex, store, inst, eng


def test_grouped_divergent_args_never_roll_back():
    """Entry grouping packs same-arg lanes into uniform blocks, so mixed
    args with repeats run divergence-free even optimistically."""
    ex, store, inst, eng = make_engine(build_fib())
    assert eng.optimistic
    args = np.array([3, 3, 9, 9, 11, 3, 9, 11], np.int64)
    res = eng.run("fib", [args], max_steps=500_000)
    assert np.asarray(res.results[0]).tolist() == \
        [2, 2, 34, 34, 89, 2, 34, 89]


def test_divergent_branch_recovers_via_recheck():
    """All-distinct args defeat entry grouping (groups of one lane): the
    block genuinely diverges mid-run, triggering a canary rollback and a
    careful-kernel recheck round."""
    ex, store, inst, eng = make_engine(build_fib())
    assert eng.optimistic
    args = np.arange(3, 11, dtype=np.int64)
    res = eng.run("fib", [args], max_steps=500_000)
    assert np.asarray(res.results[0]).tolist() == \
        [2, 3, 5, 8, 13, 21, 34, 55]
    assert eng.recheck_rounds >= 1


def test_semantic_agreement_bitwise_differs_no_false_divergence():
    """br_if conds that are nonzero-but-different agree semantically;
    the zeroness canary must not flag them."""
    b = ModuleBuilder()
    # loop n times where the continue-cond is the (varying) counter
    b.add_function(["i32"], ["i32"], ["i32"], [
        ("block", None),
        ("loop", None),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("local.get", 0), ("local.get", 1), "i32.sub",
        ("br_if", 0),   # cond = n - i: nonzero differs per iteration
        "end",
        "end",
        ("local.get", 1),
    ], export="f")
    ex, store, inst, eng = make_engine(b.build())
    res = eng.run("f", [np.full(LANES, 50, np.int64)], max_steps=100_000)
    assert (np.asarray(res.results[0]) == 50).all()
    assert eng.recheck_rounds == 0


def test_partial_lane_div_by_zero_rolls_back():
    b = ModuleBuilder()
    b.add_function(["i32", "i32"], ["i32"], [], [
        ("local.get", 0), ("local.get", 1), "i32.div_u",
    ], export="f")
    ex, store, inst, eng = make_engine(b.build())
    num = np.full(LANES, 100, np.int64)
    den = np.array([5, 5, 0, 5, 0, 5, 5, 5], np.int64)
    res = eng.run("f", [num, den], max_steps=10_000)
    for lane in range(LANES):
        if den[lane] == 0:
            assert res.trap[lane] == int(ErrCode.DivideByZero), lane
        else:
            assert res.trap[lane] == -1
            assert int(res.results[0][lane]) == 20


def test_partial_lane_oob_load_rolls_back():
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.load", 2, 0),
    ], export="f")
    for hbm in (False, True):
        ex, store, inst, eng = make_engine(b.build(), hbm=hbm)
        addr = np.array([0, 4, 8, 70000, 0, 4, 70000, 8], np.int64)
        res = eng.run("f", [addr], max_steps=10_000)
        for lane in range(LANES):
            if addr[lane] >= 65536:
                assert res.trap[lane] == int(ErrCode.MemoryOutOfBounds), \
                    (hbm, lane)
            else:
                assert res.trap[lane] == -1, (hbm, lane)


def test_divergent_load_addresses_lane_exact():
    """Per-lane different addresses: the optimistic kernel rolls back
    and the careful/SIMT path computes each lane exactly."""
    b = ModuleBuilder()
    b.add_memory(1, 1)
    # store lane-arg at its own address, read it back
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("local.get", 0), ("i32.store", 2, 0),
        ("local.get", 0), ("i32.load", 2, 0),
    ], export="f")
    for hbm in (False, True):
        ex, store, inst, eng = make_engine(b.build(), hbm=hbm)
        addr = (np.arange(LANES, dtype=np.int64) * 512) % 65000
        res = eng.run("f", [addr], max_steps=10_000)
        got = np.asarray(res.results[0], np.int64)
        assert (got == addr).all(), (hbm, got.tolist())


def test_careful_mode_forced_off():
    """cfg.optimistic=False runs the per-step-checked kernel only."""
    conf = Configure()
    conf.batch.optimistic = False
    ex, store, inst, eng = make_engine(build_fib(), conf=conf)
    assert not eng.optimistic
    res = eng.run("fib", [np.full(LANES, 10, np.int64)],
                  max_steps=100_000)
    assert (np.asarray(res.results[0]) == 55).all()
    assert eng.recheck_rounds == 0


def test_retired_counts_match_careful():
    """Rollbacks must not inflate or lose retired-instruction counts on
    a clean run (uniform args: canary never fires)."""
    conf_o = Configure()
    ex, store, inst, eng_o = make_engine(build_fib(), conf=conf_o)
    conf_c = Configure()
    conf_c.batch.optimistic = False
    ex, store, inst, eng_c = make_engine(build_fib(), conf=conf_c)
    a = np.full(LANES, 14, np.int64)
    r_o = eng_o.run("fib", [a], max_steps=500_000)
    r_c = eng_c.run("fib", [a], max_steps=500_000)
    assert np.asarray(r_o.retired).sum() == np.asarray(r_c.retired).sum()


def test_snapshot_interval_commits():
    """A run far longer than SNAP_STEPS crosses many periodic commits;
    results stay exact (exercises snapshot/flush cadence)."""
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine

    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(["i32"], ["i32"], ["i32", "i32"], [
        ("block", None),
        ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        ("local.get", 1), ("i32.const", 4), "i32.mul",
        ("local.get", 1), ("i32.const", 0x55AA55), "i32.xor",
        ("i32.store", 2, 0),
        ("local.get", 2),
        ("local.get", 1), ("i32.const", 4), "i32.mul", ("i32.load", 2, 0),
        "i32.add", ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0),
        "end", "end",
        ("local.get", 2),
    ], export="f")
    data = b.build()
    for hbm in (False, True):
        ex, store, inst, eng = make_engine(data, hbm=hbm)
        # force frequent commits so pytest-scale runs cross many
        eng.SNAP_STEPS = 64
        n = 500
        res = eng.run("f", [np.full(LANES, n, np.int64)],
                      max_steps=2_000_000)
        s_ex, s_store, s_inst = instantiate(data, Configure())
        expect = s_ex.invoke(s_store, s_inst.find_func("f"), [n])[0]
        got = np.asarray(res.results[0], np.int64) & 0xFFFFFFFF
        assert (got == (int(expect) & 0xFFFFFFFF)).all(), (hbm, got[0])


@pytest.mark.parametrize("hbm", [False, True])
def test_rollbacks_across_commits_stay_lane_exact(hbm, monkeypatch):
    """The whole path with a short build-time interval: lanes leave the
    loop at different iterations, every divergence rolls back across
    one or more periodic commits, the careful kernel and the splitter
    take over, and each lane still returns its own count with its own
    memory."""
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine
    from tests.test_dispatch_tree import counting_loop

    monkeypatch.setattr(PallasUniformEngine, "SNAP_STEPS", 64)
    ex, store, inst, eng = make_engine(counting_loop(True), hbm=hbm)
    ns = np.array([90, 90, 17, 90, 55, 55, 90, 3], np.int64)
    res = eng.run("f", [ns], max_steps=2_000_000)
    assert np.asarray(res.results[0]).tolist() == ns.tolist()
    assert (np.asarray(res.trap) == -1).all()
    assert eng.recheck_rounds >= 1


V128_ROLLBACK_SPAN = 120 * 16      # bytes the longest lane's stream writes


def v128_rollback_guest() -> bytes:
    """f(n: i32) -> i64: n turns of a loop whose exit test runs with two
    v128 operands on the operand stack above two v128 locals, and whose
    body moves all four 32-bit lanes of both locals and streams one of
    them out 16 bytes a turn; then a fold of every byte any lane wrote
    and of both locals.  A rollback that loses an `e2` / `e3` row of a
    local or an operand, or a store the snapshot held, changes the
    result."""
    def v(*lanes):
        return ("v128.const", b"".join(
            (x & 0xFFFFFFFF).to_bytes(4, "little") for x in lanes))

    N, I, P, ACC, A, B = range(6)
    body = [
        ("local.get", N), "i32x4.splat",
        v(0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F), "i32x4.mul",
        ("local.set", A),
        v(0x01000193, 0x811C9DC5, 0xDEADBEEF, 0x7F4A7C15), ("local.set", B),
        ("block", None),
        ("loop", None),
        # the two operands are live across the exit test, which is
        # where the lanes disagree
        ("local.get", A), ("local.get", B),
        ("local.get", I), ("local.get", N), "i32.ge_u", ("br_if", 1),
        "i32x4.add", ("local.set", A),
        ("local.get", B), ("local.get", B),
        ("i8x16.shuffle", [4, 5, 6, 7, 8, 9, 10, 11,
                           12, 13, 14, 15, 0, 1, 2, 3]),
        ("local.get", A), "v128.xor",
        v(0x00010001, 0x00030003, 0x00050005, 0x00070007), "i32x4.add",
        ("local.set", B),
        ("local.get", I), ("i32.const", 16), "i32.mul",
        ("local.get", A), ("v128.store", 4, 0),
        ("local.get", I), ("i32.const", 1), "i32.add", ("local.set", I),
        ("br", 0),
        "end", "end",
        ("loop", None),
        ("local.get", ACC), ("i64.const", 1), "i64.rotl",
        ("local.get", P), ("i64.load", 3, 0), "i64.xor", ("local.set", ACC),
        ("local.get", P), ("i32.const", 8), "i32.add", ("local.tee", P),
        ("i32.const", V128_ROLLBACK_SPAN), "i32.ne", ("br_if", 0),
        "end",
    ]
    for row in (A, B):
        for half in (0, 1):
            body += [("local.get", ACC), ("i64.const", 7), "i64.rotl",
                     ("local.get", row), ("i64x2.extract_lane", half),
                     "i64.xor", ("local.set", ACC)]
    body += [("local.get", ACC)]
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(["i32"], ["i64"], ["i32", "i32", "i64", "v128", "v128"],
                   body, export="f")
    return b.build()


@pytest.mark.parametrize("hbm", [False, True])
def test_v128_rollbacks_across_commits_stay_lane_exact(hbm, monkeypatch):
    """The same path with v128 values on the stack: lanes leave the loop
    at different turns while two v128 locals and two v128 operands are
    live and differ in all four 32-bit lanes from commit to commit, and
    (behind the window) the `v128.store` stream evicts dirty windows,
    each a commit point of its own.  Every lane's result, which folds
    its memory and both locals, is the scalar engine's bit for bit."""
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine

    monkeypatch.setattr(PallasUniformEngine, "SNAP_STEPS", 64)
    data = v128_rollback_guest()
    ex, store, inst, eng = make_engine(data, hbm=hbm)
    assert eng.img.has_simd and eng.optimistic
    ns = np.array([120, 120, 37, 120, 75, 75, 120, 9], np.int64)
    res = eng.run("f", [ns], max_steps=2_000_000)
    assert not eng.fell_back_to_simt
    assert (np.asarray(res.trap) == -1).all()
    want = {}
    for n in sorted(set(ns.tolist())):
        s_ex, s_store, s_inst = instantiate(data, Configure())
        want[n] = int(s_ex.invoke(s_store, s_inst.find_func("f"),
                                  [n])[0]) & (2**64 - 1)
    got = [int(x) & (2**64 - 1) for x in np.asarray(res.results[0])]
    assert got == [want[n] for n in ns.tolist()]
    assert len(set(want.values())) == len(want)
    assert eng.recheck_rounds >= 1
    if hbm:
        assert eng.mem_static["mem_mode"] == "hbm_window"
        assert eng.window_writebacks >= 8
