"""The Pallas kernel's dispatch tree: the planner as a pure function,
the entry-slot weights it is fed, and the cold subtree's reachability.

A dispatch walks one scf.if region per tree level, so the plan puts the
handlers a converged dispatch can START at near the root; handlers that
only a resume can reach (slots a fused block absorbed) hang in one cold
subtree and must stay dispatchable.
"""

import numpy as np
import pytest

from wasmedge_tpu.batch import pallas_engine as pe
from wasmedge_tpu.batch.pallas_engine import (
    H_BLOCK_BASE,
    H_RETURN,
    PallasUniformEngine,
    expected_and_max_depth,
    entry_slots,
    fuse_blocks,
    hid_plane,
    kernel_dispatch_plan,
    plan_dispatch_tree,
)
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.models import build_fib, build_memory_workload
from wasmedge_tpu.utils.builder import ModuleBuilder
from tests.helpers import instantiate

LANES = 8

# build_fib's entry weights, hot-first: blocks 0, 1 and 2 with one entry
# slot each (0, 10, 14), then the eight handlers that only a resume
# reaches: the seven whose slots were all absorbed into blocks, and
# `return`, whose slot 15 every way in now runs through (PR 29)
FIB_WEIGHTS = (1, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0)
# before superblocks (PR 27): block 1 with two call sites, then return,
# block 0 and block 2; kept for the planner's own tests
OLD_FIB_WEIGHTS = (2, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0)


def leaves(tree):
    if isinstance(tree, int):
        return [tree]
    return leaves(tree[1]) + leaves(tree[2])


def midpoint_tree(lo, hi):
    if hi - lo == 1:
        return lo
    mid = lo + (hi - lo) // 2
    return (mid, midpoint_tree(lo, mid), midpoint_tree(mid, hi))


def log2_ceil(n):
    return max(n - 1, 0).bit_length()


def fib_engine():
    conf = Configure()
    conf.batch.steps_per_launch = 50_000
    _ex, store, inst = instantiate(build_fib(), conf)
    return PallasUniformEngine(inst, store=store, conf=conf, lanes=LANES,
                               interpret=True)


# -- (a) the planner, without a kernel --------------------------------------
def test_fib_weights_put_the_dispatched_handlers_on_top():
    tree, depths = plan_dispatch_tree(OLD_FIB_WEIGHTS)
    assert leaves(tree) == list(range(len(OLD_FIB_WEIGHTS)))
    assert depths[:4] == (2, 2, 2, 3)
    expected, deepest = expected_and_max_depth(OLD_FIB_WEIGHTS, depths)
    assert expected <= 2.5
    # fib's dynamic mix of block 0 : block 1 : block 2 : return
    dyn = {2: 2, 0: 2, 3: 1, 1: 1}
    assert sum(depths[i] * k for i, k in dyn.items()) / 6 <= 2.5
    assert deepest == max(depths) <= log2_ceil(len(OLD_FIB_WEIGHTS)) + 2


def test_superblock_fib_weights_plan_three_hot_handlers():
    tree, depths = plan_dispatch_tree(FIB_WEIGHTS)
    assert leaves(tree) == list(range(len(FIB_WEIGHTS)))
    # every dispatch of a converged fib walks two branches
    assert depths[:3] == (2, 2, 2)
    assert expected_and_max_depth(FIB_WEIGHTS, depths) == (2.0, 5)


@pytest.mark.parametrize("n", [1, 2, 3, 7, 8, 11, 33])
def test_equal_weights_give_the_midpoint_tree(n):
    tree, depths = plan_dispatch_tree((3,) * n)
    assert tree == midpoint_tree(0, n)
    assert max(depths) == log2_ceil(n)


@pytest.mark.parametrize("n", [1, 2, 5, 11, 16, 37])
def test_zero_weights_do_not_chain(n):
    tree, depths = plan_dispatch_tree((0,) * n)
    assert leaves(tree) == list(range(n))
    assert max(depths) <= log2_ceil(n) + 1


@pytest.mark.parametrize("weights", [
    tuple(2 ** k for k in range(12, -1, -1)) + (0, 0, 0),
    (1000,) + (1,) * 40,
    (5, 5, 5, 1) + (0,) * 30,
    (7,),
    (4, 0),
])
def test_every_handler_is_a_leaf_once_and_depth_is_capped(weights):
    tree, depths = plan_dispatch_tree(weights)
    n = len(weights)
    assert leaves(tree) == list(range(n))
    assert max(depths) <= log2_ceil(n) + 2
    # a heavier handler never sits deeper than a lighter hot one by
    # more than the cap's fallback allows: the heaviest is on top
    assert depths[0] == min(depths)


def test_planner_refuses_weights_that_are_not_hot_first():
    with pytest.raises(ValueError):
        plan_dispatch_tree((1, 2))
    with pytest.raises(ValueError):
        plan_dispatch_tree((0, 1))
    with pytest.raises(ValueError):
        plan_dispatch_tree(())


def test_the_commit_is_one_more_cold_leaf_of_the_optimistic_tree():
    tree_c, depths_c = kernel_dispatch_plan(OLD_FIB_WEIGHTS, False)
    tree_o, depths_o = kernel_dispatch_plan(OLD_FIB_WEIGHTS, True)
    assert leaves(tree_c) == list(range(11))
    assert leaves(tree_o) == list(range(12))
    # the hot handlers do not move for it
    assert depths_o[:4] == depths_c[:4] == (2, 2, 2, 3)
    assert kernel_dispatch_plan(FIB_WEIGHTS, True)[1][:3] == \
        kernel_dispatch_plan(FIB_WEIGHTS, False)[1][:3] == (2, 2, 2)


# -- (b) the weights the engine feeds it ------------------------------------
def test_fib_entry_slot_weights():
    eng = fib_engine()
    img = eng.img
    hid, shapes = fuse_blocks(hid_plane(img), img)
    entry = entry_slots(hid, shapes, img)
    # the heads are where they were, 0, 6, 10, 14 (a superblock's first
    # segment is the plain block's), but block 0 now runs through both
    # of its forward edges (PR 29): the `brz` at 3 into slot 6's ops
    # (its tail) and the `br` at 5 into the `return` at 15 (a jump).
    # No other edge leads to 6 or 15, so only a resume starts there:
    # 6 keeps its block id for one, 15 its bare `return`.
    assert np.flatnonzero(hid >= H_BLOCK_BASE).tolist() == [0, 6, 10, 14]
    assert shapes[0] == (
        ("lget", 0), ("const",), ("alu2", 17),
        ("guardz", (("lget", 0), ("const",), ("alu2", 1),
                    ("term", pe.H_CALL))),
        ("lget", 0), ("jump", 1), ("term", H_RETURN))
    assert pe.superblock_edges(hid, shapes, img) == {
        "jump": 1, "guard_tail": 1}
    assert np.flatnonzero(entry).tolist() == [0, 10, 14]
    counts = {}
    for h in hid[entry]:
        counts[int(h)] = counts.get(int(h), 0) + 1
    assert counts == {H_BLOCK_BASE + 0: 1, H_BLOCK_BASE + 1: 1,
                      H_BLOCK_BASE + 2: 1}

    eng._build()
    used = eng._kargs[0]
    assert used[:3] == (H_BLOCK_BASE + 0, H_BLOCK_BASE + 1,
                        H_BLOCK_BASE + 2)
    assert used[3:] == tuple(sorted(used[3:])) and H_RETURN in used[3:]
    assert eng._hid_weights == FIB_WEIGHTS
    assert sorted(used) == sorted(set(int(h) for h in hid))
    # three hot handlers at depth 2 each: 2.00 where four gave 2.20
    assert eng.dispatch_depth == (2.0, 6)
    assert eng.superblock_edges == {"jump": 1, "guard_tail": 1}
    # the flat plane and the splitter's views are what they were
    assert np.array_equal(eng._np_fused["hid"], hid)
    assert np.array_equal(eng._np_hid_orig, hid_plane(img))


def test_memory_workload_blocks_are_hot_and_absorbed_ops_cold():
    conf = Configure()
    _ex, store, inst = instantiate(build_memory_workload(), conf)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=LANES,
                              interpret=True)
    eng._build()
    used, weights = eng._kargs[0], eng._hid_weights
    blocks = [i for i, h in enumerate(used) if h >= H_BLOCK_BASE]
    assert blocks and all(weights[i] >= 1 for i in blocks)
    assert 0 in weights      # something was absorbed everywhere
    _tree, depths = kernel_dispatch_plan(weights, True)
    assert max(depths) <= log2_ceil(len(weights) + 1) + 2


# -- (c) a resume on an absorbed slot reaches the cold subtree --------------
def test_resume_on_an_absorbed_slot_is_bit_exact(monkeypatch):
    """Divergent fib arguments: the careful kernel bails at block 0's
    guard, the scheduler splits, and the fall-through children resume
    at slot 4, a `local.get` that block 0 absorbed, whose handler has
    weight zero; the taken children start at slot 6, which since PR 29
    only a resume reaches (block 0 runs its ops as the guard's tail):
    its block id is hot through slot 10.  Slot 15's bare `return` is
    cold.  Results are the parent commit's; retired is the scalar
    engine's 21 F(n+1) - 14, the `brz` the host resolved at each of a
    lane's splits counted (PR 32)."""
    from wasmedge_tpu.batch.scheduler import BlockScheduler

    eng = fib_engine()
    started = set()
    launch = BlockScheduler.launch

    def spy(self):
        ctrl = self._ctrl()
        running = ctrl[:, pe._C_STATUS] == pe.ST_RUNNING
        started.update(int(p) for p in ctrl[running, pe._C_PC])
        return launch(self)

    monkeypatch.setattr(BlockScheduler, "launch", spy)
    ns = np.array([3, 5, 8, 2, 9, 4, 7, 6], np.int64)
    res = eng.run("fib", [ns], max_steps=2_000_000)
    assert np.asarray(res.results[0]).tolist() == \
        [2, 5, 21, 1, 34, 3, 13, 8]
    assert np.asarray(res.retired).tolist() == \
        [49, 154, 700, 28, 1141, 91, 427, 259]
    assert not eng.fell_back_to_simt and eng.splits == 7

    inner = next(iter(eng.simt._sched_cache.values()))
    hid = inner._np_fused["hid"]
    entry = entry_slots(hid, inner._kargs[17], inner.img)
    cold = {pc for pc in started if not entry[pc]}
    assert cold == {4, 6}
    used = inner._kargs[0]
    assert inner._hid_weights[used.index(int(hid[4]))] == 0
    assert hid[6] == hid[10] and entry[10]
    assert inner._hid_weights[used.index(int(hid[6]))] == 1
    assert hid[15] == H_RETURN and not entry[15]
    assert inner._hid_weights[used.index(H_RETURN)] == 0


# -- the counter ------------------------------------------------------------
def test_dispatch_depth_reaches_metrics_and_the_run_span():
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.obs import parse_prometheus, render_prometheus

    conf = Configure()
    conf.batch.steps_per_launch = 50_000
    conf.batch.interpret = True
    conf.obs.enabled = True
    _ex, store, inst = instantiate(build_fib(), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    assert eng.pallas is not None and eng.pallas.dispatch_depth is None
    res = eng.run("fib", [np.full(LANES, 10, np.int64)],
                  max_steps=500_000)
    assert (np.asarray(res.results[0]) == 55).all()
    # three hot handlers (PR 29: superblocks), each two branches down
    assert eng.pallas.dispatch_depth == (2.0, 6)
    # the first run builds the kernel and learns the depth at its end;
    # a later one carries it from the start (a profiler trace has it)
    assert eng._kernel_args() == {"dispatch_depth": "2.00/6",
                                  "mem_mode": "none", "donated_planes": 2,
                                  "block_shapes": "3/3"}
    eng.run("fib", [np.full(LANES, 5, np.int64)], max_steps=500_000)
    runs = [e for e in eng.obs.events if e["name"] == "batch/run"]
    assert [e["args"]["dispatch_depth"] for e in runs] == ["2.00/6"] * 2
    parsed = parse_prometheus(render_prometheus(recorder=eng.obs))
    got = {dict(labels)["stat"]: v for (name, labels), v in parsed.items()
           if name == "wasmedge_dispatch_depth"}
    assert got == {"expected": 2.0, "max": 6.0}
    assert eng.pallas.block_shapes == {"kept": 3, "wanted": 3}
    assert {dict(labels)["kind"]: v for (name, labels), v in parsed.items()
            if name == "wasmedge_block_shapes"} == {"kept": 3, "wanted": 3}


def test_dispatches_reach_metrics_and_the_run_span():
    """fib(10) is 89 leaves and 88 inner calls: 89 + 3 x 88 dispatches
    of 7 x 89 + 14 x 88 instructions; fib(5) 8 + 3 x 7 of 154."""
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.obs import parse_prometheus, render_prometheus

    conf = Configure()
    conf.batch.steps_per_launch = 50_000
    conf.batch.interpret = True
    conf.obs.enabled = True
    _ex, store, inst = instantiate(build_fib(), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    eng.run("fib", [np.full(LANES, 10, np.int64)], max_steps=500_000)
    assert eng.pallas.dispatches == 353
    eng.run("fib", [np.full(LANES, 5, np.int64)], max_steps=500_000)
    assert eng.pallas.dispatches == 29
    runs = [e for e in eng.obs.events if e["name"] == "batch/run"]
    assert [e["args"]["instr_per_dispatch"] for e in runs] == [
        round(1855 / 353, 4), round(154 / 29, 4)]
    parsed = parse_prometheus(render_prometheus(recorder=eng.obs))
    flat = {(name, tuple(sorted(dict(labels).items()))): v
            for (name, labels), v in parsed.items()}
    assert flat[("wasmedge_pallas_dispatches_total", ())] == 353 + 29
    assert flat[("wasmedge_superblock_edges", (("kind", "jump"),))] == 1
    assert flat[("wasmedge_superblock_edges",
                 (("kind", "guard_tail"),))] == 1


# ---------------------------------------------------------------------------
# commit points: the `steps` at which the optimistic kernel validates,
# snapshots and rolls back, pinned against the values of the commit
# before the commit moved out of the per-dispatch path (PR 27)
# ---------------------------------------------------------------------------
def counting_loop(mem: bool) -> bytes:
    """for (i = 0; i < n; i++) [mem[4 * i] = i]; return i.  The loop
    body is one fused block (9 instructions, 14 with the store), so a
    dispatch boundary falls on every multiple of that."""
    b = ModuleBuilder()
    if mem:
        b.add_memory(1, 1)
    store = [("local.get", 1), ("i32.const", 4), "i32.mul",
             ("local.get", 1), ("i32.store", 2, 0)] if mem else []
    b.add_function(["i32"], ["i32"], ["i32"], [
        ("block", None),
        ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        *store,
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0),
        "end", "end",
        ("local.get", 1),
    ], export="f")
    return b.build()


# (module has memory, mem_hbm, snap, chunk, iterations of the odd lane)
#   -> (status, steps, pc, sp) of the block after ONE optimistic launch
#   (status 0 running, 1 done, 5 ST_RECHECK: rolled back).
# Seven lanes run 400 iterations and lane 7 runs k, so the canary goes
# dirty in iteration k; the launch then rolls back to the newest commit
# point at or before it, which is what `steps` reads: the staircase over
# k is the list of commit points.  snap 40 commits at the first dispatch
# boundary >= 40 steps after the last commit (45, 90, ... without
# memory; 42, 84, ... with); snap 700 and snap 0 (the build-time
# interval) have the short first interval of 512 steps (513, 518).
_COMMIT_CASES = {
    "entry-snapshot": (False, None, 40, 50_000, 4, (5, 0, 0, 2)),
    "first-commit": (False, None, 40, 50_000, 5, (5, 45, 0, 2)),
    "before-second": (False, None, 40, 50_000, 9, (5, 45, 0, 2)),
    "second-commit": (False, None, 40, 50_000, 10, (5, 90, 0, 2)),
    "eighth-commit": (False, None, 40, 50_000, 39, (5, 315, 0, 2)),
    "short-first-interval-not-reached": (False, None, 700, 50_000, 56,
                                         (5, 0, 0, 2)),
    "short-first-interval": (False, None, 700, 50_000, 57, (5, 513, 0, 2)),
    "long-second-interval-not-reached": (False, None, 700, 50_000, 134,
                                         (5, 513, 0, 2)),
    "long-second-interval": (False, None, 700, 50_000, 135,
                             (5, 1215, 0, 2)),
    "fifth-long-interval": (False, None, 700, 50_000, 399,
                            (5, 3321, 0, 2)),
    "build-time-interval": (False, None, 0, 50_000, 399, (5, 513, 0, 2)),
    "clean-run-to-the-end": (False, None, 700, 50_000, 400,
                             (1, 3606, 10, 1)),
    # the chunk ends the launch between commit points, on one, and on
    # one with the canary dirty (the commit that falls due on the
    # launch's last dispatch still runs)
    "chunk-between-commits": (False, None, 40, 81, 400, (0, 81, 0, 2)),
    "chunk-on-a-commit": (False, None, 40, 90, 400, (0, 90, 0, 2)),
    "chunk-overshoot": (False, None, 40, 30, 400, (0, 36, 0, 2)),
    "chunk-on-a-commit-dirty": (False, None, 40, 90, 7, (5, 45, 0, 2)),
    "chunk-between-commits-dirty": (False, None, 40, 81, 7,
                                    (5, 45, 0, 2)),
    # with a memory plane, resident and behind the HBM window (whose
    # dirty windows the commit writes back before it snapshots)
    "resident-first": (True, False, 40, 50_000, 4, (5, 42, 0, 2)),
    "resident-fourth": (True, False, 40, 50_000, 12, (5, 168, 0, 2)),
    "resident-short-first": (True, False, 700, 50_000, 37,
                             (5, 518, 0, 2)),
    "resident-clean": (True, False, 700, 50_000, 400, (1, 5606, 15, 1)),
    "hbm-entry": (True, True, 40, 50_000, 0, (5, 0, 0, 2)),
    "hbm-first": (True, True, 40, 50_000, 4, (5, 42, 0, 2)),
    "hbm-third": (True, True, 40, 50_000, 11, (5, 126, 0, 2)),
    "hbm-tenth": (True, True, 40, 50_000, 30, (5, 420, 0, 2)),
    "hbm-long-second": (True, True, 700, 50_000, 88, (5, 1218, 0, 2)),
    "hbm-chunk-on-a-commit": (True, True, 40, 112, 400, (0, 112, 0, 2)),
    "hbm-chunk-dirty": (True, True, 40, 112, 7, (5, 84, 0, 2)),
    "hbm-clean": (True, True, 700, 50_000, 400, (1, 5606, 15, 1)),
}


@pytest.mark.parametrize("case", sorted(_COMMIT_CASES))
def test_commit_points_pinned(case):
    from wasmedge_tpu.batch.scheduler import BlockScheduler

    mem, hbm, snap, chunk, k, expect = _COMMIT_CASES[case]
    conf = Configure()
    conf.batch.steps_per_launch = 50_000
    conf.batch.mem_hbm = hbm
    conf.batch.value_stack_depth = 64
    conf.batch.call_stack_depth = 32
    _ex, store, inst = instantiate(counting_loop(mem), conf)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=LANES,
                              interpret=True)
    args = np.full(LANES, 400, np.int64)
    args[7] = k
    sched = BlockScheduler(eng, "f", [args], 10_000_000)
    assert sched.nblk == 1 and sched.eng.optimistic
    assert sched.eng._mem_mode() is bool(hbm)
    ctrl = sched._ctrl()
    ctrl[:, pe._C_SNAP] = snap
    ctrl[:, pe._C_CHUNK] = chunk
    sched._ctrl_dirty = True
    sched.launch()
    row = sched._ctrl()[0]
    got = tuple(int(row[c]) for c in (pe._C_STATUS, pe._C_STEPS,
                                      pe._C_PC, pe._C_SP))
    assert got == expect


# ---------------------------------------------------------------------------
# superblock formation (PR 29): the rules fuse_blocks reads off the image
# ---------------------------------------------------------------------------
def device_image(wasm):
    conf = Configure()
    _ex, store, inst = instantiate(wasm, conf)
    return PallasUniformEngine(inst, store=store, conf=conf, lanes=LANES,
                               interpret=True).img


def paths_of(shape):
    """Every path through a shape, as flat op lists: the fall-through
    side of every guard, and each taken side up to its tail's end."""
    paths, main = [], []
    for op in shape:
        if op[0] in ("guardz", "guardnz"):
            paths.append(main + [(op[0], ())] + list(op[1]))
            main = main + [(op[0], ())]
        else:
            main = main + [op]
    return paths + [main]


def _guests():
    from wasmedge_tpu.models import (build_coremark_kernel, build_fac,
                                     build_loop_sum, build_memory_batch)

    return {"fib": build_fib, "fac": build_fac, "loop_sum": build_loop_sum,
            "memory": build_memory_batch, "coremark": build_coremark_kernel,
            "counting_loop": lambda: counting_loop(True)}


@pytest.mark.parametrize("guest", sorted(_guests()))
def test_superblock_rules_hold(guest):
    img = device_image(_guests()[guest]())
    hid, shapes = fuse_blocks(hid_plane(img), img)
    assert len(shapes) <= pe.MAX_BLOCK_SHAPES
    call_nest = 2 if int(img.max_local_zeros) > 0 else 1
    for shape in shapes:
        # first segment: what the plain block was, the limit on nesting
        cut = next((i for i, op in enumerate(shape) if op[0] == "jump"),
                   None)
        plain = tuple((op[0], ()) if op[0] in ("guardz", "guardnz") else op
                      for op in (shape if cut is None else shape[:cut]))
        if cut is not None:
            plain += (("term", pe.H_BR),)
        limit = pe._path_nesting(plain, call_nest)
        assert pe._path_nesting(shape, call_nest) <= limit
        for path in paths_of(shape):
            assert 2 <= len(path) <= pe.MAX_BLOCK_LEN
            assert all(op[0] != "term" for op in path[:-1])
        for op in shape:
            if op[0] in ("guardz", "guardnz"):
                tail = op[1]
                # one level of duplication, no memory op, a plain term
                assert all(t[0] not in ("loadi", "storei") for t in tail)
                assert all(t[1] == () for t in tail
                           if t[0] in ("guardz", "guardnz"))
                assert all(t[1] in pe._DUP_TERMS for t in tail
                           if t[0] == "term")
    # every op keeps its own slot: a walk never leaves the image, and a
    # head's first segment is contiguous from the head
    for head in np.flatnonzero(hid >= H_BLOCK_BASE):
        shape = shapes[int(hid[head]) - H_BLOCK_BASE]
        slots = [slot for _op, slot, in_tail in
                 pe.walk_shape(shape, int(head), img) if not in_tail]
        assert all(0 <= s <= img.code_len for s in slots)
        first = next((i for i, op in enumerate(shape)
                      if op[0] == "jump"), len(shape) - 1)
        assert slots[:first + 1] == list(range(head, head + first + 1))


def test_loop_exits_keep_the_empty_tail():
    """build_memory_batch: its three forward `br_if`s leave a loop (the
    block they sit in ends in the loop's backward `br`), so each is
    taken once a loop against once an iteration for the fall-through
    side, and none gets a tail: the plane, the entry slots and the
    weights are what the plain fuser gave (with tails the two loops ran
    7-9 ns a dispatch slower on the chip, PERF.md section 6, PR 29)."""
    from wasmedge_tpu.models import build_memory_batch

    conf = Configure()
    conf.batch.value_stack_depth = 128
    conf.batch.call_stack_depth = 64
    _ex, store, inst = instantiate(build_memory_batch(), conf)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=LANES,
                              interpret=True)
    eng._build()
    hid, shapes = eng._np_fused["hid"], eng._kargs[17]
    heads = [0, 2, 4, 24, 26, 42, 49]
    assert np.flatnonzero(hid >= H_BLOCK_BASE).tolist() == heads
    assert np.flatnonzero(entry_slots(hid, shapes, eng.img)).tolist() == \
        heads
    assert eng.superblock_edges == {"jump": 0, "guard_tail": 0}
    for h in (4, 26, 42):
        shape = shapes[int(hid[h]) - H_BLOCK_BASE]
        assert shape[-1] == ("term", pe.H_BR)
        assert [op for op in shape if op[0] == "guardnz"] == [
            ("guardnz", ())]
    used, weights = eng._kargs[0], eng._hid_weights
    assert weights[:5] == (3, 1, 1, 1, 1) and not any(weights[5:])
    _tree, depths = kernel_dispatch_plan(weights, True)
    assert [depths[used.index(int(hid[h]))] for h in (4, 26)] == [2, 3]


def test_a_block_reached_only_through_a_tail_stays_hot():
    """f(x) = x ? 1 : 2.  The else-arm (`const 2; return`, a block of
    its own at slot 4) is reached through the guard's tail alone, so no
    converged dispatch starts there; its handler keeps weight 1 all the
    same: a block nests as deep as the hot ones, and the cold subtree
    is where the tree is deepest."""
    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("if", "i32"), ("i32.const", 1), "else",
        ("i32.const", 2), "end"], export="f")
    conf = Configure()
    _ex, store, inst = instantiate(b.build(), conf)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=LANES,
                              interpret=True)
    eng._build()
    hid, shapes = eng._np_fused["hid"], eng._kargs[17]
    assert shapes[0] == (
        ("lget", 0), ("guardz", (("const",), ("term", H_RETURN))),
        ("const",), ("jump", 1), ("term", H_RETURN))
    assert np.flatnonzero(hid >= H_BLOCK_BASE).tolist() == [0, 4]
    assert np.flatnonzero(entry_slots(hid, shapes, eng.img)).tolist() == [0]
    used, weights = eng._kargs[0], eng._hid_weights
    assert used[:2] == (H_BLOCK_BASE, H_BLOCK_BASE + 1)
    assert weights[:2] == (1, 1) and not any(weights[2:])
    res = eng.run("f", [np.array([0, 1, 0, 0, 1, 1, 0, 5], np.int64)],
                  max_steps=10_000)
    assert np.asarray(res.results[0]).tolist() == [2, 1, 2, 2, 1, 1, 2, 1]


def test_shape_budget_falls_back_to_the_plain_block(monkeypatch):
    """Where a head's full shape is not kept it takes its plain shape if
    that one is.  fa's edges cannot be followed (both lead to a `div`,
    which keeps its own dispatch), so its head's full shape is the
    plain one; fb's head has the same plain block and edges that can.
    With two shapes kept, the rule keeps the `div` block (two heads in
    each fa, 2 x 4) and fa's (one head in each, 3 x 2) over fb's full
    shape (one head, 4): fb's head runs the plain block."""
    b = ModuleBuilder()
    div = [("i32.const", 6), ("i32.const", 3), "i32.div_u"]
    for name in ("fa", "fa2"):
        b.add_function(["i32"], ["i32"], [], [
            ("local.get", 0), ("if", "i32"), ("i32.const", 1), "else",
            *div, "end", *div, "i32.add"], export=name)
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("if", "i32"), ("i32.const", 1), "else",
        ("i32.const", 2), "end"], export="fb")
    img = device_image(b.build())
    plain = (("lget", 0), ("guardz", ()), ("const",), ("term", pe.H_BR))
    divide = (("const",), ("const",), ("term", int(hid_plane(img)[6])))
    hid, shapes = fuse_blocks(hid_plane(img), img)
    fb = int(img.f_entry[2])
    assert shapes[:2] == (plain, divide) and hid[0] == H_BLOCK_BASE
    assert hid[fb] > H_BLOCK_BASE + 1 and ("jump", 1) in \
        shapes[int(hid[fb]) - H_BLOCK_BASE]
    monkeypatch.setattr(pe, "MAX_BLOCK_SHAPES", 2)
    hid2, shapes2, counts = pe.fuse_blocks_counted(hid_plane(img), img)
    assert counts == {"kept": 2, "wanted": 5}
    assert shapes2 == (plain, divide)
    heads = np.flatnonzero(hid2 >= H_BLOCK_BASE)
    assert heads.tolist() == [0, 4, 7, 12, 16, 19, fb]
    assert (hid2[heads] - H_BLOCK_BASE).tolist() == [0, 1, 1, 0, 1, 1, 0]


# tests/test_optimistic.py is a slow suite by its file name
# (tests/conftest.py), so the rollback test every change to the v128
# planes' snapshot leans on is collected here as well, where the
# tier-1 run (-m 'not slow') counts it.
from tests.test_optimistic import (  # noqa: E402,F401
    test_v128_rollbacks_across_commits_stay_lane_exact,
)
