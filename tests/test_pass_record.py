"""The pass record (batch/pallas_engine.py `PassRecord`): what the block
scheduler reads to decide a pass (`ctrl`, `frames`, the trap row, the
result rows) leaves the device as one array, packed behind the kernel
and downloaded once.  Every case runs the Pallas kernels in interpret
mode, holds each lane to the scalar engine (results, trap code and
retired count) and names the downloads the run made: `pass` at a sync
or after a careful round, `rows` for a resolver's stack row, and a
plane on its own only where a program wrote it after the record.
"""

import numpy as np
import pytest

from wasmedge_tpu.batch.pallas_engine import (
    HostLink, PassRecord, _C_STATUS, _pass_record_fn, _split_pass_record)
from wasmedge_tpu.batch.scheduler import BlockScheduler
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import ErrCode, TrapError
from wasmedge_tpu.common.statistics import Statistics
from wasmedge_tpu.executor import Executor
from wasmedge_tpu.loader import Loader
from wasmedge_tpu.models import build_fib, build_simd_kernel
from wasmedge_tpu.runtime.store import StoreManager
from wasmedge_tpu.utils.builder import ModuleBuilder
from wasmedge_tpu.validator import Validator
from tests.test_scheduler import make_engine

LANES = 32
MASK64 = 0xFFFFFFFFFFFFFFFF


@pytest.fixture
def downloads(monkeypatch):
    """-> the `what` of every download through a HostLink, in order."""
    whats = []
    d2h = HostLink.d2h

    def spy(self, what, arr, index=None):
        whats.append(what)
        return d2h(self, what, arr, index)

    monkeypatch.setattr(HostLink, "d2h", spy)
    return whats


def _scalar(data, func, args):
    """-> (results, trap code or -1, instructions retired) of one lane
    on the scalar engine."""
    conf = Configure()
    conf.statistics.instr_counting = True
    stat = Statistics(conf)
    ex = Executor(conf, stat)
    store = StoreManager()
    inst = ex.instantiate(store, Validator(conf).validate(
        Loader(conf).parse_module(data)))
    try:
        out = ex.invoke(store, inst.find_func(func), args)
    except TrapError as e:
        return None, int(e.code), stat.instr_count
    return [int(v) & MASK64 for v in out], -1, stat.instr_count


def _assert_lanes_exact(data, func, per_lane, res, lanes=None):
    memo = {}
    for lane in range(len(per_lane[0])) if lanes is None else lanes:
        args = tuple(int(a[lane]) for a in per_lane)
        if args not in memo:
            memo[args] = _scalar(data, func, list(args))
        want, trap, count = memo[args]
        assert (int(res.trap[lane]), int(res.retired[lane])) == \
            (trap, count), lane
        if want is not None:
            assert [int(r[lane]) & MASK64 for r in res.results] == want, lane


def _div_then_if():
    """A div_u that traps where y is 0, then an `if` on the quotient:
    a trap-partial stop (the trap row holds the codes), then a split."""
    b = ModuleBuilder()
    b.add_function(["i32", "i32"], ["i32"], [], [
        ("local.get", 0), ("local.get", 1), "i32.div_u",
        ("if", "i32"), ("i32.const", 111), "else", ("i32.const", 222),
        "end"], export="f")
    ys = np.asarray([5, 0, 200, 5, 0, 200, 5, 200] * (LANES // 8), np.int64)
    return b.build(), "f", [100 + np.arange(LANES, dtype=np.int64), ys]


def _no_result():
    """No result: the record carries no stack row at all."""
    b = ModuleBuilder()
    b.add_global("i32", True, [("i32.const", 0)])
    b.add_function(["i32"], [], [], [
        ("loop", None),
        ("global.get", 0), ("i32.const", 3), "i32.add", ("global.set", 0),
        ("local.get", 0), ("i32.const", 1), "i32.sub", ("local.tee", 0),
        ("br_if", 0),
        "end"], export="f")
    return b.build(), "f", [(np.arange(LANES, dtype=np.int64) % 5) + 1]


def _i64_result():
    """An i64 whose upper word differs lane by lane: the `hi` rows."""
    b = ModuleBuilder()
    b.add_function(["i64"], ["i64"], [], [
        ("local.get", 0), ("i64.const", 0x1_0000_0001), "i64.mul",
        ("local.get", 0), ("i64.const", 40), "i64.shl", "i64.xor",
    ], export="f")
    return b.build(), "f", [
        (np.arange(LANES, dtype=np.int64) % 4) * 0x7FFF_FFFF + 3]


def _i64_through_splits():
    """An i64 accumulated over n turns, n from five values no grouping
    takes: the upper words ride the splits and leave in the `hi` rows."""
    b = ModuleBuilder()
    b.add_function(["i32"], ["i64"], ["i64"], [
        ("loop", None),
        ("local.get", 1), ("i64.const", 0x1_0000_0003), "i64.mul",
        ("local.get", 0), "i64.extend_i32_u", "i64.add", ("local.set", 1),
        ("local.get", 0), ("i32.const", 1), "i32.sub", ("local.tee", 0),
        ("br_if", 0),
        "end",
        ("local.get", 1)], export="f")
    return b.build(), "f", [(np.arange(LANES, dtype=np.int64) % 5) + 2]


def _fib_shattered():
    """Seven argument values no entry grouping takes: the block diverges
    mid-recursion, so every child carries live call frames, and with
    fewer slots than children each is installed in a later pass."""
    return build_fib(), "fib", [(np.arange(LANES, dtype=np.int64) % 7) + 4]


def _v128_through_splits():
    """A `has_simd` image: the ctrl row is one column wider (the v128
    instruction count), a cell is four stack planes, and the loop's
    trip count, from five values no grouping takes, splits the block
    with two v128 locals live."""
    return build_simd_kernel(), "vloop", [
        (np.arange(LANES, dtype=np.int64) % 5) + 2]


@pytest.mark.parametrize("guest,blk_cap,splits", [
    (_div_then_if, 8, True), (_div_then_if, None, True),
    (_no_result, None, True), (_i64_result, None, False),
    (_i64_through_splits, None, True), (_fib_shattered, None, True),
    (_fib_shattered, 16, True), (_v128_through_splits, None, True)],
    ids=["traps-4-blocks", "traps-1-block", "no-result", "i64-result",
         "i64-through-splits", "frames-1-slot", "frames-2-slots",
         "v128-wider-ctrl"])
def test_every_lane_is_exact_out_of_the_record(downloads, guest, blk_cap,
                                               splits):
    data, func, per_lane = guest()
    _ex, _store, _inst, eng = make_engine(data, lanes=LANES)
    assert eng.ctrl_width == (17 if eng.img.has_simd else 16)
    eng._blk_cap = blk_cap
    res = eng.run(func, per_lane, max_steps=2_000_000)
    assert not eng.fell_back_to_simt
    assert bool(eng.splits) == splits
    _assert_lanes_exact(data, func, per_lane, res)
    # no mirror missed: a record a kernel, and the resolver's own rows
    assert set(downloads) <= {"pass", "rows"}
    assert downloads.count("pass") == eng.launches + eng.rechecks
    assert eng.d2h_transfers == len(downloads)
    assert eng.programs_enqueued == \
        2 * (eng.launches + eng.rechecks) + eng.surgery_programs


def test_the_trap_codes_of_four_blocks_come_from_the_records_row(
        monkeypatch, downloads):
    """Each of the four blocks stops trap-partial: its lanes with y = 0
    take DivideByZero out of the trap row, the others split on."""
    data, func, per_lane = _div_then_if()
    _ex, _store, _inst, eng = make_engine(data, lanes=LANES)
    eng._blk_cap = 8
    seen = []
    split = BlockScheduler._split

    def spy(self, b, ctrl_np, status):
        seen.append(self._trap_full.copy())
        return split(self, b, ctrl_np, status)

    monkeypatch.setattr(BlockScheduler, "_split", spy)
    sched = BlockScheduler(eng, func, per_lane, 2_000_000)
    assert sched.nblk == 4 and downloads == []
    sched.launch()
    # the kernel wrote every plane: no mirror outlives its launch
    assert sched._ctrl_cache is None and sched._frames_cache is None
    assert sched._trap_full is None and sched._res_lo_full is None
    # the optimistic kernel rolls all four back; the careful round's
    # record holds the codes the split then reads
    assert sched.process() and sched.rechecks == 1 and sched.splits == 4
    assert downloads == ["pass", "pass"]
    ys = per_lane[1]
    assert (seen[0] == np.where(
        ys == 0, int(ErrCode.DivideByZero), 0)).all()
    sched.run()
    res = sched.result()
    assert (res.trap[ys == 0] == int(ErrCode.DivideByZero)).all()
    _assert_lanes_exact(data, func, per_lane, res)
    assert set(downloads) <= {"pass", "rows"}


def test_a_running_block_is_harvested_at_max_steps_out_of_the_record(
        downloads):
    """fib(5) ends; fib(18) is still running when `max_steps` is
    reached: its lanes read 0 out of the record's trap row and retire
    what their block ran."""
    args = np.repeat(np.asarray([5, 18], np.int64), LANES // 2)
    _ex, _store, _inst, eng = make_engine(build_fib(), lanes=LANES,
                                          chunk=2_000)
    res = eng.run("fib", [args], max_steps=3_000)
    done = args == 5
    _assert_lanes_exact(build_fib(), "fib", [args], res,
                        lanes=np.flatnonzero(done))
    assert (res.trap[~done] == 0).all() and not res.completed[~done].any()
    ran = np.unique(res.retired[~done])
    assert len(ran) == 1 and 3_000 <= ran[0] < 3_000 + 2_000
    assert eng.launches == 2 and downloads == ["pass", "pass"]


def test_an_install_drops_the_mirrors_of_the_planes_it_wrote(
        monkeypatch, downloads):
    """The pass that splits a trap-partial block holds the codes in its
    trap mirror; the install of the child that runs on writes the slot's
    columns (trap-free, in another lane order), so the mirror goes and
    the next read is the plane itself, not the record's row."""
    data, func, per_lane = _div_then_if()
    _ex, _store, _inst, eng = make_engine(data, lanes=LANES)
    stale = []
    install = BlockScheduler._install

    def spy(self, free):
        stale.append((self._trap().copy(), self._res()[0].copy()))
        return install(self, free)

    monkeypatch.setattr(BlockScheduler, "_install", spy)
    sched = BlockScheduler(eng, func, per_lane, 2_000_000)
    sched.launch()
    assert sched.process() and sched.surgery_programs == 2
    (trap_stale, lo_stale), = stale
    assert trap_stale.any() and downloads == ["pass", "pass"]
    assert sched._trap_full is None and sched._res_lo_full is None
    fresh = sched._trap()
    assert downloads == ["pass", "pass", "trap"]
    assert not fresh.any() and (fresh == np.asarray(sched.state[7])[0]).all()
    lo, _hi = sched._res()
    assert downloads == ["pass", "pass", "trap", "res_lo", "res_hi"]
    assert (lo == np.asarray(sched.state[2])[:1]).all()
    assert (lo != lo_stale).any()
    # ctrl and frames are the host's own: an install writes the mirrors
    assert sched._ctrl_cache is not None and sched._ctrl_dirty
    assert sched._frames_cache is not None and sched._frames_dirty
    sched.run()
    _assert_lanes_exact(data, func, per_lane, sched.result())


def test_a_child_harvested_running_right_after_its_install_reads_the_plane(
        downloads):
    """`max_steps` reached by the split itself: the child that would run
    on is harvested running in the pass after its install, with no
    kernel (so no record) in between.  Its lanes read 0, not the codes
    the slot's columns held for other lanes before the install."""
    data, func, per_lane = _div_then_if()
    _want, _trap, at_div = _scalar(data, func, [100, 0])
    _ex, _store, _inst, eng = make_engine(data, lanes=LANES)
    res = eng.run(func, per_lane, max_steps=at_div)
    ys = per_lane[1]
    assert (res.trap[ys == 0] == int(ErrCode.DivideByZero)).all()
    assert (res.trap[ys != 0] == 0).all()
    assert (res.retired == at_div).all()
    assert downloads == ["pass", "pass", "trap"]


@pytest.mark.parametrize("turns,want", [
    # one block: no kernel runs between the capture and the finish
    ([1] * 8, ["pass", "slab_lo", "slab_hi", "trap", "pass"]),
    # two blocks of eight: the second is still looping, launch after
    # launch, while the first one's calls are served, so a record is out
    # when the finish writes
    ([1] * 8 + [900] * 8, None)], ids=["one-block", "behind-a-launch"])
def test_a_serves_finish_drops_the_mirrors_of_the_planes_it_wrote(
        downloads, turns, want):
    """A host function that traps in one lane of a block: the serve's
    finish writes that lane's code into the trap plane after the pass's
    record was packed, so the split that follows reads the plane itself,
    peels the lane off on the kernel's path and hands nothing to the
    per-step engine."""
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine
    from wasmedge_tpu.common.errors import trap
    from wasmedge_tpu.runtime.hostfunc import ImportObject, PyHostFunction
    from tests.helpers import instantiate

    calls = []

    def picky(mem, x):
        calls.append(x)
        if x % 8 == 3:
            trap(ErrCode.ExecutionFailed)
        return x * 2

    imp = ImportObject("env")
    imp.add_func("f", PyHostFunction(picky, ["i32"], ["i32"]))
    b = ModuleBuilder()
    b.import_func("env", "f", ["i32"], ["i32"])
    b.add_function(["i32", "i32"], ["i32"], [], [
        ("loop", None),
        ("local.get", 1), ("i32.const", 1), "i32.sub", ("local.tee", 1),
        ("br_if", 0),
        "end",
        ("local.get", 0), ("call", 0), ("i32.const", 1), "i32.add"],
        export="g")
    lanes = len(turns)
    conf = Configure()
    conf.batch.steps_per_launch = 500
    _ex, store, inst = instantiate(b.build(), conf, imports=[imp])
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=lanes,
                              interpret=True)
    eng._blk_cap = 8
    xs = np.arange(lanes, dtype=np.int64)
    res = eng.run("g", [xs, np.asarray(turns, np.int64)], max_steps=100_000)
    assert sorted(calls) == list(range(lanes))
    bad = xs % 8 == 3
    assert (res.trap[bad] == int(ErrCode.ExecutionFailed)).all()
    assert (res.trap[~bad] == -1).all()
    assert (np.asarray(res.results[0])[~bad] == 2 * xs[~bad] + 1).all()
    assert not eng.fell_back_to_simt and eng.splits == lanes // 8
    if want is None:
        assert eng.launches > 4 and set(downloads) == {
            "pass", "slab_lo", "slab_hi", "trap"}
        assert downloads.count("trap") == 2
    else:
        # the first launch's record, the serve's two stack slabs (on the
        # link since PR 40), the trap plane after the serve wrote it,
        # the record of the launch that ran seven lanes on
        assert downloads == want


def test_a_one_block_job_is_one_download_and_two_programs(downloads):
    _ex, _store, _inst, eng = make_engine(build_fib(), lanes=LANES)
    args = np.full(LANES, 12, np.int64)
    res = eng.run("fib", [args], max_steps=2_000_000)
    _assert_lanes_exact(build_fib(), "fib", [args], res)
    assert (eng.launches, eng.d2h_transfers, eng.programs_enqueued) == \
        (1, 1, 2)
    assert downloads == ["pass"]


def test_a_mirror_that_misses_falls_back_to_the_plane(downloads):
    """With no record out and no mirror, each read downloads its own
    plane through the link, as before the record."""
    _ex, _store, _inst, eng = make_engine(build_fib(), lanes=LANES)
    sched = BlockScheduler(eng, "fib", [np.full(LANES, 9, np.int64)],
                           2_000_000)
    sched.launch()
    sched._record = None          # as if no record had been packed
    assert sched.process()
    assert downloads == ["ctrl", "res_lo", "res_hi"]
    assert np.asarray(sched.result().results[0]).tolist() == [34] * LANES


@pytest.mark.parametrize("nblk,cd,lanes,nres,ctrl_w", [
    (1, 256, 4096, 1, 16), (11, 256, 5632, 1, 16), (4, 16, 32, 0, 16),
    (2, 8, 16, 2, 16), (1, 16, 4096, 1, 17), (3, 16, 24, 2, 17)])
def test_the_records_layout_follows_from_the_shapes(nblk, cd, lanes, nres,
                                                    ctrl_w):
    """At both widths of a ctrl row: 16 columns, and the 17 of an image
    with v128."""
    rng = np.random.default_rng(nblk * 1000 + nres)

    def plane(*shape):
        return rng.integers(-2 ** 31, 2 ** 31, shape).astype(np.int32)

    ctrl, frames, trap = (plane(nblk, ctrl_w), plane(nblk, 3, cd),
                          plane(1, lanes))
    slo, shi = plane(5, lanes), plane(5, lanes)
    flat = np.asarray(_pass_record_fn()(ctrl, frames, trap, slo, shi, nres))
    assert flat.dtype == np.int32
    assert flat.shape == (
        nblk * (ctrl_w + 3 * cd) + lanes * (1 + 2 * nres),)
    if ctrl_w == 16:    # the width of every image without v128
        assert _split_pass_record(flat, nblk, cd, lanes, nres).ctrl.shape \
            == (nblk, 16)
    else:
        with pytest.raises(ValueError):
            _split_pass_record(flat, nblk, cd, lanes, nres)
    rec = _split_pass_record(flat, nblk, cd, lanes, nres, ctrl_w)
    assert isinstance(rec, PassRecord)
    for got, want in zip(rec, (ctrl, frames, trap[0], slo[:nres],
                               shi[:nres])):
        assert got.shape == want.shape and (got == want).all()
    # the scheduler writes its ctrl and frames mirrors
    assert rec.ctrl.flags.writeable and rec.frames.flags.writeable
    rec.ctrl[0, _C_STATUS] += 1
    with pytest.raises(ValueError):
        _split_pass_record(flat[:-1], nblk, cd, lanes, nres, ctrl_w)
