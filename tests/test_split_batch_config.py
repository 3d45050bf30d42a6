"""The deployment `fib-batch-4096-split` (cell `batch-fib-split`) at its
rehearsal size: arguments that entry grouping cannot group, so one block
splits in flight, and every lane still retires the scalar engine's
instruction count however many splits it passed.

Everything runs on the CPU with the Pallas kernels in interpret mode; the
benchmark's own driver supplies the lane arguments and the plain
reference the answers.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from wasmedge_tpu.batch.pallas_engine import (
    ST_RECHECK,
    _C_SNAP,
    _C_STATUS,
    _C_STEPS,
    PallasUniformEngine,
)
from wasmedge_tpu.batch.scheduler import BlockScheduler
from wasmedge_tpu.batch.uniform import UniformBatchEngine
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import TrapError
from wasmedge_tpu.common.statistics import Statistics
from wasmedge_tpu.executor import Executor
from wasmedge_tpu.loader import Loader
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.obs import NULL_RECORDER, parse_prometheus, \
    render_prometheus
from wasmedge_tpu.runtime.store import StoreManager
from wasmedge_tpu.utils.builder import ModuleBuilder
from wasmedge_tpu.validator import Validator
from tests.helpers import instantiate
from tests.test_obs_spans import SPLIT_SPANS, _profiled
from tests.test_scheduler import LANES as SCHED_LANES, make_engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
LANES = 64


def _bench_module(kind, name):
    """A file of benchmark/ as run.py would find it (its own directory
    on the path while it loads: the driver imports `harness`)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(BENCH)
    return mod


def _cell():
    with open(os.path.join(BENCH, "workloads", "batch-fib-split.json")) as f:
        return json.load(f)


def _split_args(seed=7):
    driver = _bench_module("drivers", "batch_split")
    return driver.lane_args(_cell()["rehearse"]["args"], LANES, seed)


def _split_engine(obs=False):
    conf = Configure()
    conf.batch.steps_per_launch = 50_000_000
    conf.batch.interpret = True
    conf.obs.enabled = obs
    _ex, store, inst = instantiate(build_fib(), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    assert eng.pallas is not None and eng.pallas.eligible
    return eng


def _scalar_count(data, func, args, conf=None):
    """-> (instructions the scalar engine retires, its trap code or -1),
    by the command the cells' `retired_by_arg_made_by` records."""
    conf = conf or Configure()
    conf.statistics.instr_counting = True
    stat = Statistics(conf)
    ex = Executor(conf, stat)
    store = StoreManager()
    inst = ex.instantiate(store, Validator(conf).validate(
        Loader(conf).parse_module(data)))
    try:
        ex.invoke_raw(store, inst.find_func(func), args)
    except TrapError as e:
        return stat.instr_count, int(e.code)
    return stat.instr_count, -1


@pytest.fixture(scope="module")
def split_job():
    """The rehearsal's job twice on one engine, with obs on, counting for
    every lane the splits it passed."""
    args = _split_args()
    passed = np.zeros(LANES, np.int64)
    install = BlockScheduler._install_children

    def spy(self, b, children, resolved=0):
        ids = self.block_lanes[b]
        passed[ids[ids >= 0]] += 1
        return install(self, b, children, resolved)

    eng = _split_engine(obs=True)
    BlockScheduler._install_children = spy
    try:
        res = eng.run("fib", [args], max_steps=1_000_000)
    finally:
        BlockScheduler._install_children = install
    first = {k: getattr(eng.pallas, k) for k in
             ("splits", "launches", "rechecks", "recheck_rounds",
              "careful_steps", "surgery_programs", "snap_restored",
              "snap_commits", "d2h_transfers", "h2d_transfers",
              "programs_enqueued")}
    again = eng.run("fib", [args], max_steps=1_000_000)
    return args, passed, eng, res, first, again


def test_the_arguments_cannot_be_grouped():
    """A quarter of the lanes one value each, all below 2; the rest the
    cell's sizes dealt evenly, the same multiset under every seed."""
    a, b = _split_args(seed=1), _split_args(seed=2 ** 31 + 11)
    lane = np.arange(LANES)
    assert (a[lane % 4 == 0] == -1 - lane[lane % 4 == 0] // 4).all()
    assert (a[lane % 4 == 0] == b[lane % 4 == 0]).all()
    rest_a, rest_b = a[lane % 4 != 0], b[lane % 4 != 0]
    assert (rest_a != rest_b).any()
    assert sorted(rest_a) == sorted(rest_b)
    values, counts = np.unique(rest_a, return_counts=True)
    assert values.tolist() == list(range(5, 13))
    assert set(counts.tolist()) == {6}
    # at the cell's own size: 1024 of their own, 279 or 280 of each size
    driver = _bench_module("drivers", "batch_split")
    full = driver.lane_args(_cell()["traffic"]["args"], 4096, 3)
    values, counts = np.unique(full[full >= 2], return_counts=True)
    assert values.tolist() == list(range(20, 31))
    assert set(counts.tolist()) == {279, 280} and counts.sum() == 3072
    assert len(np.unique(full[full < 2])) == 1024


@pytest.mark.parametrize("n,splits_passed", [(-3, 1), (5, 2), (10, 7)])
def test_a_lane_retires_the_scalar_count_through_its_splits(
        split_job, n, splits_passed):
    args, passed, eng, res, _first, _again = split_job
    reference = _bench_module("references", "fib").reference
    lanes = args == n
    assert lanes.any() and (passed[lanes] == splits_passed).all()
    count, trap = _scalar_count(build_fib(), "fib", [n])
    assert trap == -1
    if n < 2:
        assert count == _cell()["expected"]["retired_below_2"]
    assert (np.asarray(res.retired)[lanes] == count).all()
    got = np.asarray(res.results[0])[lanes].astype(np.uint64) \
        & np.uint64(0xFFFFFFFF)
    assert (got == reference("fib", [n])[0]).all()


def test_every_lane_is_exact_and_nothing_fell_back(split_job):
    args, passed, eng, res, _first, again = split_job
    reference = _bench_module("references", "fib").reference
    assert not eng.fell_back_to_simt
    assert (np.asarray(res.trap) == -1).all()
    for n in np.unique(args):
        lanes = args == n
        want = 7 if n < 2 else _scalar_count(build_fib(), "fib", [int(n)])[0]
        assert (np.asarray(res.retired)[lanes] == want).all(), n
        got = np.asarray(res.results[0])[lanes].astype(np.uint64) \
            & np.uint64(0xFFFFFFFF)
        assert (got == reference("fib", [int(n)])[0]).all(), n
    assert passed.max() == 8 and passed.min() == 1
    assert (np.asarray(again.retired) == np.asarray(res.retired)).all()


def test_the_runs_counters_are_the_runs_own(split_job):
    """One split peels the lanes below 2 off and seven peel 5..11 off the
    rest; each costs a launch that rolls back and a careful round, each
    child but the last its own launch, and every child two compiled
    programs of block surgery (its extract, its install).  A second run
    reads the same, though the cached engine's total has doubled, and
    `/metrics` sums both."""
    _args, _passed, eng, _res, first, _again = split_job
    assert first["splits"] == 8 and first["rechecks"] == 8
    assert first["launches"] == 2 * 8 + 1
    assert first["recheck_rounds"] == first["rechecks"]
    assert 0 < first["careful_steps"] < 8 * 64
    installs = [e for e in eng.obs.events if e["name"] == "batch/install"]
    assert sum(e["args"]["blocks"] for e in installs) == 2 * 16
    assert first["surgery_programs"] == 2 * 16
    second = {k: getattr(eng.pallas, k) for k in first}
    assert second == first
    inner = next(iter(eng.pallas.simt._sched_cache.values()))
    assert inner.recheck_rounds == 2 * first["rechecks"]
    samples = parse_prometheus(render_prometheus(recorder=eng.obs))

    def value(name):
        return next(v for (n, _labels), v in samples.items() if n == name)

    assert value("wasmedge_block_splits_total") == 16
    assert value("wasmedge_kernel_launches_total") == 34
    assert value("wasmedge_careful_rechecks_total") == 16
    assert value("wasmedge_careful_steps_total") == \
        2 * first["careful_steps"]
    assert value("wasmedge_block_surgery_programs_total") == 64
    # every child's parent has just rolled back, so each of the 2 x 8 is
    # given the full interval back; the second run was held to the same
    assert first["snap_restored"] == 2 * 8
    assert value("wasmedge_snapshot_intervals_restored_total") == 32
    assert value("wasmedge_snapshot_commits_total") == \
        2 * first["snap_commits"]
    runs = [e for e in eng.obs.events if e["name"] == "batch/run"]
    assert [e["args"]["snap_restored"] for e in runs] == [16, 16]
    assert [(e["args"]["splits"], e["args"]["launches"],
             e["args"]["rechecks"], e["args"]["surgery_programs"])
            for e in runs] == [(8, 17, 8, 32)] * 2
    splits = [e for e in eng.obs.events if e["name"] == "batch/split"]
    assert len(splits) == 16
    assert {e["args"]["parent"] for e in splits} == {"batch/statuses"}
    assert {e["args"]["children"] for e in splits} == {2}


def test_the_runs_crossings_of_the_host_link_are_pinned(split_job):
    """What a job of this mix moves between host and device, as counts
    that repeat: with S = 8 splits and L = 2 S + 1 = 17 launches,
    downloads: one pass record (`ctrl`, `frames`, the `trap` row and the
    result rows in one array) at each of the L syncs and after each of
    the S careful rounds, and S stack rows a `brz` is resolved from:
    nothing under `harvest`, `install` or `run`, and no plane on its own
    (a `what` of `trap`, `res_lo`, `frames` would be a mirror that
    missed); uploads: 5 at entry (two argument rows, two globals,
    `ctrl`), `ctrl` L at the launches + 2 S in the careful rounds,
    `frames` 2 S; programs: L + S kernels, a pack behind each, and 4 S
    of block surgery."""
    _args, _passed, eng, _res, first, _again = split_job
    S, L = 8, 17
    assert first["d2h_transfers"] == 33 == L + 2 * S
    assert first["h2d_transfers"] == 54 == 5 + (L + 2 * S) + 2 * S
    assert first["programs_enqueued"] == 82 == 2 * (
        first["launches"] + first["rechecks"]) + first["surgery_programs"]
    assert {k: getattr(eng.pallas, k) for k in first} == first
    samples = parse_prometheus(render_prometheus(recorder=eng.obs))
    assert samples[("wasmedge_batch_transfers_total",
                    frozenset({("dir", "d2h")}))] == 2 * 33
    assert samples[("wasmedge_batch_transfers_total",
                    frozenset({("dir", "h2d")}))] == 2 * 54
    assert samples[("wasmedge_batch_programs_enqueued_total",
                    frozenset())] == 2 * 82
    # each is a span of the ring under the phase that made it
    by_parent = {}
    for e in eng.obs.events:
        if e["name"] in ("batch/d2h", "batch/h2d", "batch/enqueue"):
            key = (e["name"][6:], e["args"].get("what")
                   or e["args"]["program"], e["args"]["parent"][6:])
            by_parent[key] = by_parent.get(key, 0) + 1
    assert by_parent == {k: 2 * n for k, n in {
        ("d2h", "pass", "sync"): L, ("d2h", "pass", "recheck"): S,
        ("d2h", "rows", "split"): S,
        ("h2d", "args_lo", "initial_state"): 1,
        ("h2d", "args_hi", "initial_state"): 1,
        ("h2d", "globals_lo", "initial_state"): 1,
        ("h2d", "globals_hi", "initial_state"): 1,
        ("h2d", "ctrl", "initial_state"): 1, ("h2d", "ctrl", "launch"): L,
        ("h2d", "ctrl", "recheck"): 2 * S,
        ("h2d", "frames", "launch"): 2 * S,
        ("enqueue", "optimistic", "launch"): L,
        ("enqueue", "pack", "launch"): L,
        ("enqueue", "careful", "recheck"): S,
        ("enqueue", "pack", "recheck"): S,
        ("enqueue", "extract", "split"): 2 * S,
        ("enqueue", "install", "install"): 2 * S}.items()}


def test_a_uniform_run_reports_no_split_counts():
    conf = Configure()
    conf.batch.steps_per_launch = 10_000
    conf.batch.interpret = True
    conf.obs.enabled = True
    _ex, store, inst = instantiate(build_fib(), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=8)
    eng.run("fib", [np.full(8, 9, np.int64)], max_steps=100_000)
    assert (eng.pallas.splits, eng.pallas.rechecks,
            eng.pallas.careful_steps, eng.pallas.surgery_programs) == \
        (0, 0, 0, 0)
    assert eng.pallas.launches == 1
    # fib(9) is 1141 steps in one launch: the short first interval's
    # commit, and no child to give an interval back to
    assert (eng.pallas.snap_restored, eng.pallas.snap_commits) == (0, 1)
    (run,) = [e for e in eng.obs.events if e["name"] == "batch/run"]
    assert not {"splits", "launches", "rechecks", "surgery_programs",
                "snap_restored"} & set(run["args"])
    samples = parse_prometheus(render_prometheus(recorder=eng.obs))
    names = {n for n, _labels in samples}
    assert "wasmedge_kernel_launches_total" in names
    assert [v for (n, _labels), v in samples.items()
            if n == "wasmedge_block_surgery_programs_total"] == [0]
    assert [v for (n, _labels), v in samples.items()
            if n == "wasmedge_snapshot_intervals_restored_total"] == [0]
    assert [v for (n, _labels), v in samples.items()
            if n == "wasmedge_snapshot_commits_total"] == [1]


@pytest.mark.parametrize("inherited", [0, 256, 4096, "full"])
def test_a_child_is_queued_with_the_full_interval(monkeypatch, inherited):
    """Whatever `_C_SNAP` the parent's row hands a child (`ctrl.copy()`
    in `_try_resolve`), the `_Pending` carries the engine's full
    interval; only a halved one counts as given back."""
    full = PallasUniformEngine.SNAP_STEPS
    handed = full if inherited == "full" else inherited
    install = BlockScheduler._install_children
    queued = []

    def spy(self, b, children, resolved=0):
        for cc, *_rest in children:
            cc[_C_SNAP] = handed
        before = len(self._pending)
        install(self, b, children, resolved)
        queued.extend(int(p.ctrl[_C_SNAP]) for p in self._pending[before:])

    monkeypatch.setattr(BlockScheduler, "_install_children", spy)
    _ex, _store, _inst, eng = make_engine(build_fib(), lanes=8)
    res = eng.run("fib", [np.arange(3, 11, dtype=np.int64)],
                  max_steps=500_000)
    assert np.asarray(res.results[0]).tolist() == \
        [2, 3, 5, 8, 13, 21, 34, 55]
    assert eng.splits == 7 and queued == [full] * 14
    assert eng.snap_restored == (14 if 0 < handed < full else 0)


def _two_blocks(monkeypatch, chunk=2_000, full=1024):
    """fib(16) and fib(15) in two blocks of eight lanes that entry
    grouping made, with a build-time interval of `full` steps, so that
    a launch of `chunk` crosses commits and a job takes many launches."""
    monkeypatch.setattr(PallasUniformEngine, "SNAP_STEPS", full)
    _ex, _store, _inst, eng = make_engine(build_fib(), lanes=16,
                                          chunk=chunk)
    args = np.repeat(np.array([16, 15], np.int64), 8)
    sched = BlockScheduler(eng, "fib", [args], 10_000_000)
    assert sched.nblk == 2 and sched.eng.optimistic
    assert sched.eng.SNAP_STEPS == full
    return sched


def _implied_commits(steps, snap, full):
    snap = snap or full
    first = min(512, full, snap)
    return 0 if steps < first else 1 + (steps - first) // snap


def test_a_block_that_rolls_back_keeps_its_own_halving(monkeypatch):
    """No split, so no child: `careful_recheck` halves the interval of
    the block that rolled back (down to 256) and of no other, each clean
    launch after it doubles it back to the full one, and the commits
    counted are those each launch's steps and interval imply."""
    full = 1024
    sched = _two_blocks(monkeypatch, full=full)
    live = np.ones(2, bool)
    sched.launch()
    assert sched.process()
    assert sched._ctrl()[:, _C_SNAP].tolist() == [0, 0]
    # a fused block may carry a launch a few steps past its 2,000
    want = sum(_implied_commits(int(s), 0, full)
               for s in sched._ctrl()[:, _C_STEPS])
    assert sched.snap_commits == want == 4
    for halved in (512, 256, 256):
        # what a dirty commit leaves: the block at its last snapshot
        # with ST_RECHECK, which the careful kernel then walks
        sched._ctrl()[0, _C_STATUS] = ST_RECHECK
        ctrl = sched._run_recheck(live)
        assert ctrl[:, _C_SNAP].tolist() == [halved, full]
    assert sched.snap_commits == want     # the careful kernel takes none
    for doubled in (512, 1024, 1024):
        snap = [int(v) for v in sched._ctrl()[:, _C_SNAP]]
        sched.launch()
        assert sched.process()
        want += sum(_implied_commits(int(s), v, full)
                    for s, v in zip(sched._ctrl()[:, _C_STEPS], snap))
        assert sched._ctrl()[:, _C_SNAP].tolist() == [doubled, full]
        assert sched.snap_commits == want
    sched.run()      # the rest of the job
    assert sched.snap_restored == 0 and sched.splits == 0
    res = sched.result()
    assert np.asarray(res.results[0]).tolist() == [987] * 8 + [610] * 8
    assert np.asarray(res.retired).tolist() == \
        [21 * 1597 - 14] * 8 + [21 * 987 - 14] * 8


def test_careful_recheck_crosses_a_link_from_the_engines_own_drive_too(
        monkeypatch):
    """`careful_recheck`'s one caller is the scheduler's drive
    (`BlockScheduler._run_recheck`): its two uploads, its two enqueues
    (the careful kernel, then the pack of its pass record behind it) and
    its one download cross the scheduler's link in that order, and
    nothing else does: the intervals `_SnapPolicy` gives the round ride
    the ctrl it uploads."""
    import contextlib

    sched = _two_blocks(monkeypatch)
    sched.launch()
    assert sched.process()
    opened = []

    class Span:
        def set(self, **args):
            pass

    def timed(name, **args):
        opened.append((name, args.get("what") or args.get("program")))
        return contextlib.nullcontext(Span())

    monkeypatch.setattr(sched.link, "_timed", timed)
    sched._ctrl()[0, _C_STATUS] = ST_RECHECK
    ctrl = sched._run_recheck(np.ones(2, bool))
    assert opened == [("batch/h2d", "ctrl"), ("batch/enqueue", "careful"),
                      ("batch/enqueue", "pack"), ("batch/d2h", "pass"),
                      ("batch/h2d", "ctrl")]
    assert ctrl[0, _C_STATUS] != ST_RECHECK
    assert ctrl[:, _C_SNAP].tolist() == [512, 1024]


def test_commits_of_one_launch_follow_the_formula(monkeypatch):
    """fib(16) is 33,523 steps; in one launch at an interval of 1024 the
    short first interval's commit and 32 whole ones (33 of fib(15)'s
    20,713: 1 + 19)."""
    sched = _two_blocks(monkeypatch, chunk=50_000)
    sched.run()
    assert sched.launches == 1
    assert sched.snap_commits == (1 + 32) + (1 + 19)
    assert sched.outer.snap_commits == sched.snap_commits
    assert sched.outer.snap_restored == 0


def test_profiler_trace_holds_the_split_spans_with_obs_off(tmp_path):
    def work():
        eng = _split_engine()
        assert eng.obs is NULL_RECORDER
        res = eng.run("fib", [_split_args()], max_steps=1_000_000)
        return res, eng.pallas

    (res, pallas), lines = _profiled(tmp_path, work)
    surgery_programs = pallas.surgery_programs
    (events,) = lines.values()      # all on the calling thread
    names = [name for name, _a, _b in events]
    assert SPLIT_SPANS <= set(names)
    # the trace holds every crossing of the host link the run counted
    assert (names.count("batch/d2h"), names.count("batch/h2d"),
            names.count("batch/enqueue")) == \
        (pallas.d2h_transfers, pallas.h2d_transfers,
         pallas.programs_enqueued) == (33, 54, 82)
    assert names.count("batch/split") == names.count("batch/recheck") == 8
    # every child is installed: two a split
    assert names.count("batch/install") == 16
    # what `wasm/batch/run` carries as `surgery_programs` where a ring
    # records it (an annotation takes its args when entered, so the end
    # args reach the ring alone): counted with obs off too, an extract
    # and an install for every install span
    assert surgery_programs == 2 * names.count("batch/install")
    for name, a, b in events:
        if name in SPLIT_SPANS:
            assert any(p == "batch/statuses" and pa <= a and b <= pb
                       for p, pa, pb in events), name
    assert (np.asarray(res.trap) == -1).all()


def _brnz_guest():
    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], ["i32"], [
        ("loop", None),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("local.get", 0), ("i32.const", 1), "i32.sub", ("local.tee", 0),
        ("br_if", 0),
        "end",
        ("local.get", 1)], export="f")
    return b.build(), "f", [(np.arange(SCHED_LANES) % 6) + 1], None


def _br_table_guest():
    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], [], [
        ("block", None), ("block", None), ("block", None),
        ("local.get", 0), ("br_table", [0, 1], 2),
        "end", ("i32.const", 100), "return",
        "end", ("i32.const", 200), "return",
        "end", ("i32.const", 300),
    ], export="f")
    return b.build(), "f", [np.arange(SCHED_LANES) % 6], None


def _call_indirect_guest():
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
    # idx 0/1: ok; 2: uninitialized; 3: type mismatch; 9: undefined
    idx = np.asarray([0, 1, 2, 3, 9, 0, 1, 0] * (SCHED_LANES // 8))
    return b.build(), "f", [np.arange(SCHED_LANES), idx], None


def _memgrow_guest():
    b = ModuleBuilder()
    b.add_memory(1, 2)
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("memory.grow",), "drop",
        ("memory.size",),
    ], export="g")

    def conf():
        c = Configure()
        c.batch.memory_pages_per_lane = 2
        return c

    return b.build(), "g", [(np.arange(SCHED_LANES) % 5) * 100000], conf


def _trap_partial_guest():
    """A div_u that traps in some lanes and an `if` after it: the kernel
    advances past the division and leaves it uncounted, the scheduler
    peels the trapped lanes off and then splits the rest at the `if`."""
    b = ModuleBuilder()
    b.add_function(["i32", "i32"], ["i32"], [], [
        ("local.get", 0), ("local.get", 1), "i32.div_u",
        ("if", "i32"), ("i32.const", 111), "else", ("i32.const", 222),
        "end"], export="f")
    ys = np.asarray([5, 0, 200, 5, 0, 200, 5, 200] * (SCHED_LANES // 8))
    return b.build(), "f", [100 + np.arange(SCHED_LANES), ys], None


@pytest.mark.parametrize("guest", [
    _brnz_guest, _br_table_guest, _call_indirect_guest, _memgrow_guest,
    _trap_partial_guest], ids=lambda g: g.__name__.strip("_"))
def test_a_split_at_this_instruction_keeps_retired_exact(guest):
    data, func, per_lane, conf = guest()
    per_lane = [np.asarray(a, np.int64) for a in per_lane]
    _ex, _store, _inst, eng = make_engine(
        data, lanes=SCHED_LANES, conf=conf() if conf else None)
    res = eng.run(func, per_lane, max_steps=2_000_000)
    assert eng.splits > 0 and not eng.fell_back_to_simt
    for lane in range(SCHED_LANES):
        count, trap = _scalar_count(
            data, func, [int(a[lane]) for a in per_lane],
            conf() if conf else None)
        assert (int(res.retired[lane]), int(res.trap[lane])) == \
            (count, trap), lane


def test_the_cells_rehearsal_prints_its_line_with_every_value_null(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "batch-fib-split", "--rehearse", "--seed", str(2 ** 31 + 5)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    warm, note, last = lines[-3:]
    assert warm["bad_lanes"] == 0 and warm["splits"] == 8
    assert note["splits_a_job"] == [8] and note["compiled_in_window"] == []
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == LANES * note["jobs"]
    assert set(last["metrics"]) == {"batch_ginstr_per_s", "setup_s"}
    assert all(m["value"] is None for m in last["metrics"].values())
