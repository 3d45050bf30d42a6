"""Block-scheduler suite: entry grouping, divergence splits, SIMT residue.

The scheduler (batch/scheduler.py) is what turns the block-uniform Pallas
kernel into a general engine: lanes with equal inputs share blocks, data
divergence splits blocks at the stopped instruction, and only genuinely
per-lane work lands on the SIMT engine.  Every case here checks
bit-parity against the scalar oracle per lane AND asserts the scheduling
outcome (stayed-on-kernel / split count / residue use) so regressions in
either dimension are caught.
"""

import numpy as np
import pytest

from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import ErrCode, TrapError
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.utils.builder import ModuleBuilder
from tests.helpers import instantiate

LANES = 32


def make_engine(data, lanes=LANES, chunk=50_000, conf=None):
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine

    conf = conf or Configure()
    conf.batch.steps_per_launch = chunk
    ex, store, inst = instantiate(data, conf)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=lanes,
                              interpret=True)
    assert eng.eligible, eng.ineligible_reason
    return ex, store, inst, eng


def run_and_check(data, func, per_lane_args, lanes=LANES,
                  max_steps=2_000_000, conf=None):
    ex, store, inst, eng = make_engine(data, lanes=lanes, conf=conf)
    args = [np.asarray(a, np.int64) for a in per_lane_args]
    res = eng.run(func, args, max_steps=max_steps)
    for lane in range(lanes):
        lane_args = [int(a[lane]) for a in args]
        s_ex, s_store, s_inst = instantiate(data, conf or Configure())
        try:
            expect = s_ex.invoke(s_store, s_inst.find_func(func), lane_args)
            assert res.trap[lane] == -1, \
                f"lane {lane}: trap {res.trap[lane]}, expected result"
            for r, e in zip(res.results, expect):
                got = int(r[lane]) & 0xFFFFFFFFFFFFFFFF
                want = int(e) & 0xFFFFFFFFFFFFFFFF
                assert got == want, f"lane {lane}: {got:#x} != {want:#x}"
        except TrapError as te:
            assert res.trap[lane] == int(te.code), \
                f"lane {lane}: trap {res.trap[lane]} != {te.code}"
    return eng, res


def test_entry_grouping_avoids_all_splits():
    # two arg populations, each >= MIN_GROUP_LANES: the scheduler packs
    # them into separate blocks, so no divergence ever occurs
    ns = np.concatenate([np.full(LANES // 2, 12, np.int64),
                         np.full(LANES // 2, 7, np.int64)])
    rng = np.random.default_rng(7)
    rng.shuffle(ns)
    eng, res = run_and_check(build_fib(), "fib", [ns])
    assert not eng.fell_back_to_simt
    assert eng.splits == 0


def test_many_groups_split_then_converge():
    # 7 shattered fib arg groups (median < MIN_GROUP_LANES -> identity
    # packing): the block MUST diverge mid-recursion and split, carrying
    # live call frames into the children, then run converged
    ns = (np.arange(LANES, dtype=np.int64) % 7) + 4
    eng, res = run_and_check(build_fib(), "fib", [ns])
    assert not eng.fell_back_to_simt
    assert eng.splits > 0


def test_divergent_br_table_splits():
    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], [], [
        ("block", None), ("block", None), ("block", None),
        ("local.get", 0), ("br_table", [0, 1], 2),
        "end", ("i32.const", 100), "return",
        "end", ("i32.const", 200), "return",
        "end", ("i32.const", 300),
    ], export="f")
    # 6 values -> median group size < MIN_GROUP_LANES: identity packing,
    # so the br_table itself must diverge and split in-flight
    sel = np.arange(LANES, dtype=np.int64) % 6
    eng, res = run_and_check(b.build(), "f", [sel])
    assert not eng.fell_back_to_simt
    assert eng.splits > 0


def test_divergent_call_indirect_with_traps():
    b = ModuleBuilder()
    f_add = b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.const", 10), "i32.add"])
    f_mul = b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.const", 3), "i32.mul"])
    f_other = b.add_function([], [], [], ["nop"])  # wrong signature
    b.add_table("funcref", 5)
    b.add_active_elem(0, [("i32.const", 0)], [f_add, f_mul])
    b.add_active_elem(0, [("i32.const", 3)], [f_other])
    ti = b.add_type(["i32"], ["i32"])
    b.add_function(["i32", "i32"], ["i32"], [], [
        ("local.get", 0), ("local.get", 1),
        ("call_indirect", ti, 0),
    ], export="f")
    data = b.build()
    # idx 0/1: ok; 2: uninitialized; 3: type mismatch; 9: undefined
    idx = np.asarray([0, 1, 2, 3, 9, 0, 1, 0] * (LANES // 8), np.int64)
    x = np.arange(LANES, dtype=np.int64)
    eng, res = run_and_check(data, "f", [x, idx])
    assert not eng.fell_back_to_simt
    assert eng.splits > 0


def test_divergent_memgrow_splits():
    b = ModuleBuilder()
    b.add_memory(1, 2)
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("memory.grow",), "drop",
        ("memory.size",),
    ], export="g")
    conf = Configure()
    conf.batch.memory_pages_per_lane = 2
    # 5 shattered delta groups (median < MIN_GROUP_LANES -> identity
    # packing -> in-flight split); 0 succeeds in place, the rest exceed
    # the declared max and fail with -1.  grow(1) would REGROW past the
    # 1-page watermark plane — covered by the regrow test instead.
    deltas = (np.arange(LANES, dtype=np.int64) % 5) * 100000
    eng, res = run_and_check(b.build(), "g", [deltas], conf=conf)
    assert not eng.fell_back_to_simt
    assert eng.splits > 0


def test_partial_div_by_zero_splits_traps():
    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], [], [
        ("i32.const", 100), ("local.get", 0), "i32.div_u",
    ], export="f")
    divs = np.asarray([1, 2, 0, 4] * (LANES // 4), np.int64)
    eng, res = run_and_check(b.build(), "f", [divs])
    assert not eng.fell_back_to_simt
    assert (res.trap[divs == 0] == int(ErrCode.DivideByZero)).all()
    assert (res.trap[divs != 0] == -1).all()


def test_simt_residue_isolated_to_bad_group():
    # lane-divergent memory.copy deltas force those lanes to the SIMT
    # residue; everything else must stay on the kernel and ALL lanes
    # must still be bit-correct
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(["i32", "i32"], ["i32"], [], [
        ("i32.const", 0), ("i32.const", 0x11AA22BB), ("i32.store", 2, 0),
        ("i32.const", 64), ("i32.const", 0x33CC44DD), ("i32.store", 2, 0),
        ("local.get", 0), ("local.get", 1), ("i32.const", 4),
        ("memory.copy",),
        ("local.get", 0), ("i32.load", 0, 2),
    ], export="f")
    # per-lane-unique args force identity packing; the per-lane deltas
    # then diverge inside the block and cannot be split (memory-data
    # divergence), so those lanes finish on the SIMT residue
    dst = 128 + np.arange(LANES, dtype=np.int64) * 8
    src = np.where(np.arange(LANES) % 2 == 0, dst, 0)
    eng, res = run_and_check(b.build(), "f", [dst, src])
    assert eng.fell_back_to_simt  # residue ran


def test_deep_split_cascade_recursion():
    # shattered args force identity packing; lanes at different recursion
    # depths split exactly where the depths first disagree; both sides
    # complete on the kernel with live call frames carried through
    ns = np.asarray([11, 13, 9, 12, 10, 14] * 6 or [], np.int64)[:LANES]
    ns = np.concatenate([ns, np.full(LANES - len(ns), 8, np.int64)])
    eng, res = run_and_check(build_fib(), "fib", [ns])
    assert not eng.fell_back_to_simt
    assert eng.splits > 0


def test_max_steps_reports_running_lanes():
    ns = np.full(LANES, 30, np.int64)
    ex, store, inst, eng = make_engine(build_fib())
    res = eng.run("fib", [ns], max_steps=1000)
    assert (res.trap == 0).all()  # still running
    assert not res.completed.any()


def test_partial_trap_followed_by_branch_keeps_codes():
    """Regression (r3 review): a div-by-zero stop advances control to a
    branch; the splitter must peel the trapped lanes FIRST instead of
    resolving the branch and carrying trap-coded lanes into RUNNING
    children (which harvested them as successes)."""
    b = ModuleBuilder()
    b.add_function(["i32", "i32"], ["i32"], [], [
        ("local.get", 0), ("local.get", 1), "i32.div_u",
        ("if", "i32"),
        ("i32.const", 111),
        "else",
        ("i32.const", 222),
        "end",
    ], export="f")
    xs = np.full(LANES, 100, np.int64)
    ys = np.asarray([5, 0, 200, 5, 0, 200, 5, 200] * (LANES // 8), np.int64)
    eng, res = run_and_check(b.build(), "f", [xs, ys])
    assert (res.trap[ys == 0] == int(ErrCode.DivideByZero)).all()
    assert (res.trap[ys != 0] == -1).all()
    assert (np.asarray(res.results[0])[ys == 5] == 111).all()
    assert (np.asarray(res.results[0])[ys == 200] == 222).all()


# -- block surgery: two compiled programs a child ---------------------------

SURGERY_LANES = 64
# group sizes no entry grouping takes (median under MIN_GROUP_LANES), and
# every split peels one group off: children of 1..7 and 63, 61, ... 36
_GROUP_SIZES = (1, 2, 3, 4, 5, 6, 7, 36)


def _cascade_fib():
    ns = np.repeat(np.arange(4, 4 + len(_GROUP_SIZES)), _GROUP_SIZES)
    return build_fib(), "fib", ns, None


def _cascade_memory():
    """A loop of n turns that stores its counter at 4 * counter, then
    answers the first turn's word plus the counter: the memory plane
    rides through every split."""
    b = ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(["i32"], ["i32"], ["i32"], [
        ("loop", None),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("local.get", 1), ("i32.const", 2), "i32.shl",
        ("local.get", 1), ("i32.store", 2, 0),
        ("local.get", 0), ("i32.const", 1), "i32.sub", ("local.tee", 0),
        ("br_if", 0),
        "end",
        ("i32.const", 4), ("i32.load", 2, 0), ("local.get", 1), "i32.add",
    ], export="f")
    ns = np.repeat(np.arange(1, 1 + len(_GROUP_SIZES)), _GROUP_SIZES)
    return b.build(), "f", ns, ns + 1


@pytest.fixture(scope="module", params=[_cascade_fib, _cascade_memory],
                ids=lambda g: g.__name__.strip("_"))
def cascade(request):
    """A split cascade run twice on one engine -> (engine, every child
    extracted as (lanes, {plane: shape}), the compiled variants the two
    surgery programs held after each run)."""
    from wasmedge_tpu.batch.scheduler import BlockScheduler

    data, func, ns, want = request.param()
    ns = np.random.default_rng(3).permutation(ns).astype(np.int64)
    _ex, _store, _inst, eng = make_engine(data, lanes=SURGERY_LANES)
    children = []
    extract = BlockScheduler._extract_cols

    def spy(self, b, cols, writes, sel=None):
        out = extract(self, b, cols, writes, sel)
        children.append((len(cols), {k: v.shape for k, v in out.items()}))
        return out

    variants = []
    BlockScheduler._extract_cols = spy
    try:
        for _ in range(2):
            res = eng.run(func, [ns], max_steps=2_000_000)
            (surgery,) = eng.simt._surgery_cache.values()
            variants.append(tuple(f._cache_size() for f in surgery))
    finally:
        BlockScheduler._extract_cols = extract
    assert not eng.fell_back_to_simt and (res.trap == -1).all()
    if want is not None:
        assert (np.asarray(res.results[0])[np.argsort(ns, kind="stable")]
                == np.sort(want)).all()
    return eng, children, variants


def test_surgery_compiles_a_variant_a_width_not_a_lane_count(cascade):
    eng, children, variants = cascade
    (inner,) = eng.simt._sched_cache.values()
    lblk = inner._geom[3]
    assert lblk == SURGERY_LANES
    first = children[:len(children) // 2]
    assert len({n for n, _shapes in first}) >= 6
    assert eng.splits == len(_GROUP_SIZES) - 1
    # an extract and an install for every child, this run's own
    assert eng.surgery_programs == 2 * len(first) == 4 * eng.splits
    most = int(np.log2(lblk)) + 1
    assert all(0 < v <= most for v in variants[0])
    assert variants[1] == variants[0]      # the second run compiled none


def test_a_pending_childs_columns_are_a_power_of_two_wide(cascade):
    eng, children, _variants = cascade
    (inner,) = eng.simt._sched_cache.values()
    D, _CD, W, lblk = inner._geom
    for n, shapes in children:
        widths = {shape[1] for shape in shapes.values()}
        (w,) = widths
        assert w & (w - 1) == 0 and n <= w <= lblk
        assert w < 2 * n or w == 1
        assert shapes["mem"][0] == W and shapes["slo"][0] == D
    if inner.img.has_memory:
        assert W > 1


def _partition_by_loop(keys):
    """`BlockScheduler._partition` as it was before it was vectorised."""
    out = []
    seen = {}
    for col in range(len(keys[0])):
        key = tuple(int(k[col]) for k in keys)
        if key in seen:
            out[seen[key]][1].append(col)
        else:
            seen[key] = len(out)
            out.append((key, [col]))
    return [(k, np.asarray(c, np.int64)) for k, c in out]


@pytest.mark.parametrize("nkeys,distinct,seed", [
    (1, 2, 0), (1, 7, 1), (1, 200, 2), (2, 3, 3), (2, 40, 4), (1, 1, 5)])
def test_partition_matches_the_loop_it_replaces(nkeys, distinct, seed):
    from wasmedge_tpu.batch.scheduler import BlockScheduler

    rng = np.random.default_rng(seed)
    n, real = 256, 180
    # negative and over-32-bit keys (memory.grow's delta, a u32 index);
    # the pads repeat column 0's keys, as clones of its data would
    values = rng.integers(-2 ** 33, 2 ** 33, size=(nkeys, distinct))
    pick = rng.integers(0, distinct, size=(nkeys, n))
    keys = [values[k][pick[k]] for k in range(nkeys)]
    for k in keys:
        k[real:] = k[0]
    got = BlockScheduler._partition(keys)
    want = _partition_by_loop(keys)
    assert [key for key, _cols in got] == [key for key, _cols in want]
    for (_key, a), (_key2, b) in zip(got, want):
        assert a.dtype == b.dtype and (a == b).all()
    # the pads follow their clone source
    assert set(range(real, n)) <= set(got[0][1].tolist())
