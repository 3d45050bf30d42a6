"""Supervised batch execution under deterministic fault injection.

ISSUE 2 acceptance: the suite covers four fault classes — launch-time
device error, mid-serve host exception, corrupted/truncated checkpoint,
and runaway/poison lanes — and proves the supervisor recovers or cleanly
degrades on each, with crash/resume runs BIT-IDENTICAL to uninterrupted
runs for both single-module and multi-tenant engines.

Fast by construction (tiny lane counts, short chunks): stays inside the
tier-1 `-m 'not slow'` budget.
"""

import os

import numpy as np
import pytest

from wasmedge_tpu.batch.engine import BatchEngine
from wasmedge_tpu.batch.multitenant import MultiTenantBatchEngine, Tenant
from wasmedge_tpu.batch.supervisor import BatchSupervisor, scalar_rerun
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import EngineFailure, ErrCode
from wasmedge_tpu.models import build_fib, build_loop_sum
from wasmedge_tpu.testing.faults import (
    Fault,
    FaultInjector,
    InjectedFault,
    build_selective_runaway,
    corrupt_checkpoint,
    seeded_faults,
)
from tests.helpers import instantiate

pytestmark = pytest.mark.faults

LANES = 16


def make_conf(**sup):
    conf = Configure()
    conf.batch.steps_per_launch = 100
    conf.batch.rng_seed = 7  # deterministic tier-0 streams across engines
    conf.supervisor.backoff_base_s = 0.0  # no sleeping in tests
    conf.supervisor.checkpoint_every_steps = 200
    for k, v in sup.items():
        setattr(conf.supervisor, k, v)
    return conf


def make_engine(data, conf, lanes=LANES):
    ex, store, inst = instantiate(data, conf)
    return BatchEngine(inst, store=store, conf=conf, lanes=lanes)


def fib_ref(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def assert_results_identical(a, b):
    for ra, rb in zip(a.results, b.results):
        assert (ra == rb).all()
    assert (a.trap == b.trap).all()
    assert (a.retired == b.retired).all()


# ---------------------------------------------------------------------------
# fault class 1: launch-time device error
# ---------------------------------------------------------------------------
def test_launch_fault_resume_bitmatch(tmp_path):
    args = [(np.arange(LANES) % 11).astype(np.int64)]
    ref = BatchSupervisor(make_engine(build_fib(), make_conf()),
                          checkpoint_dir=str(tmp_path / "ref"))
    rres = ref.run("fib", args, max_steps=500_000)
    assert not ref.failures

    inj = FaultInjector([Fault(point="launch", at=3)])
    sup = BatchSupervisor(make_engine(build_fib(), make_conf()),
                          faults=inj, checkpoint_dir=str(tmp_path / "a"))
    res = sup.run("fib", args, max_steps=500_000)
    assert inj.fired == 1
    assert res.completed.all()
    assert (res.results[0] == [fib_ref(n % 11) for n in range(LANES)]).all()
    assert_results_identical(res, rres)
    assert [f.fault_class for f in sup.failures] == ["launch"]
    # the restore came from the checkpoint lineage, not a fresh start
    assert sup.failures[0].retry == 1


def test_launch_fault_before_first_checkpoint(tmp_path):
    # failure before any checkpoint exists: restore = initial state
    args = [np.full(LANES, 9, np.int64)]
    inj = FaultInjector([Fault(point="launch", at=0)])
    sup = BatchSupervisor(make_engine(build_fib(), make_conf()),
                          faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", args, max_steps=500_000)
    assert res.completed.all()
    assert (res.results[0] == fib_ref(9)).all()


# ---------------------------------------------------------------------------
# fault class 2: mid-serve host exception (tier-1 hostcall drain)
# ---------------------------------------------------------------------------
def _echo_setup(conf, lanes, sink_path):
    """fd_write echo module with fd 1 routed to a file; tier 0 disabled
    so every call parks on the tier-1 serve path (the injection seam)."""
    from wasmedge_tpu.models import build_echo

    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.host.wasi import WasiModule
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf.batch.tier0_hostcalls = False
    data = build_echo()
    wasi = WasiModule()
    wasi.init_wasi(dirs=[], prog_name="echo")
    sink = os.open(sink_path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC)
    wasi.env.fds[1].os_fd = sink
    mod = Validator(conf).validate(Loader(conf).parse_module(data))
    store = StoreManager()
    ex = Executor(conf)
    ex.register_import_object(store, wasi)
    inst = ex.instantiate(store, mod)
    return BatchEngine(inst, store=store, conf=conf, lanes=lanes), sink


def test_serve_fault_resume_bitmatch(tmp_path):
    lanes, iters = 8, 2
    args = [np.full(lanes, iters, np.int64)]

    ref_eng, ref_sink = _echo_setup(make_conf(), lanes,
                                    str(tmp_path / "ref.out"))
    rres = BatchSupervisor(ref_eng,
                           checkpoint_dir=str(tmp_path / "r")).run(
        "echo", args, max_steps=200_000)
    os.close(ref_sink)
    assert rres.completed.all()

    # fault fires at the FIRST serve — before any bytes reach the fd —
    # so recovery replays the writes exactly once
    inj = FaultInjector([Fault(point="serve", at=0)])
    eng, sink = _echo_setup(make_conf(), lanes, str(tmp_path / "sup.out"))
    sup = BatchSupervisor(eng, faults=inj,
                          checkpoint_dir=str(tmp_path / "s"))
    res = sup.run("echo", args, max_steps=200_000)
    os.close(sink)
    assert inj.fired == 1
    assert res.completed.all()
    assert [f.fault_class for f in sup.failures] == ["serve"]
    assert_results_identical(res, rres)
    ref_bytes = (tmp_path / "ref.out").read_bytes()
    sup_bytes = (tmp_path / "sup.out").read_bytes()
    assert sup_bytes == ref_bytes  # stdout byte-identical, no duplicates
    assert sup_bytes.count(b"hello wasi echo\n") == lanes * iters * 2


# ---------------------------------------------------------------------------
# fault class 3: corrupted / truncated checkpoint
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["truncate", "flip"])
def test_corrupt_checkpoint_refused(tmp_path, mode):
    from wasmedge_tpu.batch.checkpoint import load, save

    conf = make_conf()
    eng = make_engine(build_fib(), conf)
    state = eng.initial_state(eng.inst.exports["fib"][1],
                              [np.full(LANES, 9, np.int64)])
    state, total = eng.run_from_state(state, 0, 300)
    p = tmp_path / "c.npz"
    save(p, eng, state, total)
    corrupt_checkpoint(p, mode=mode)
    with pytest.raises(Exception):
        load(p, make_engine(build_fib(), conf))


def test_corrupt_checkpoint_lineage_fallback(tmp_path):
    """The newest checkpoint is corrupted just before the restore; the
    supervisor must record it, fall back to the older lineage member,
    and still finish bit-identical to the uninterrupted run."""
    args = [(np.arange(LANES) % 12).astype(np.int64)]
    ref = BatchSupervisor(
        make_engine(build_fib(), make_conf(keep_checkpoints=3)),
        checkpoint_dir=str(tmp_path / "ref"))
    rres = ref.run("fib", args, max_steps=500_000)

    ckdir = tmp_path / "sup"

    def corrupt_newest():
        cks = sorted(ckdir.glob("ckpt-*.npz"))
        assert cks, "fault fired before any checkpoint existed"
        corrupt_checkpoint(cks[-1], mode="truncate")

    inj = FaultInjector([
        Fault(point="launch", at=4, before=corrupt_newest)])
    sup = BatchSupervisor(
        make_engine(build_fib(), make_conf(keep_checkpoints=3)),
        faults=inj, checkpoint_dir=str(ckdir))
    res = sup.run("fib", args, max_steps=500_000)
    assert res.completed.all()
    assert_results_identical(res, rres)
    classes = [f.fault_class for f in sup.failures]
    assert "launch" in classes and "checkpoint" in classes
    bad = [f for f in sup.failures if f.fault_class == "checkpoint"]
    assert bad[0].checkpoint  # lineage member named in the record


def test_injected_checkpoint_load_fault(tmp_path):
    # same fallback path, driven through the harness seam instead of
    # file corruption
    args = [np.full(LANES, 10, np.int64)]
    inj = FaultInjector([Fault(point="launch", at=4),
                         Fault(point="checkpoint_load", at=0)])
    sup = BatchSupervisor(
        make_engine(build_fib(), make_conf(keep_checkpoints=3)),
        faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", args, max_steps=500_000)
    assert res.completed.all()
    assert (res.results[0] == fib_ref(10)).all()
    classes = [f.fault_class for f in sup.failures]
    assert classes.count("checkpoint") == 1


def test_wall_clock_cadence_fires_with_large_step_cadence(tmp_path):
    # cadences are "whichever fires first": a huge step cadence must not
    # starve the wall-clock one of its per-chunk boundary checks
    sup = BatchSupervisor(
        make_engine(build_fib(),
                    make_conf(checkpoint_every_steps=10 ** 9,
                              checkpoint_every_s=1e-9)),
        checkpoint_dir=str(tmp_path))
    res = sup.run("fib", [np.full(LANES, 11, np.int64)],
                  max_steps=500_000)
    assert res.completed.all()
    assert list(tmp_path.glob("ckpt-*.npz"))


def test_checkpoint_save_failure_is_nonfatal(tmp_path):
    args = [np.full(LANES, 10, np.int64)]
    inj = FaultInjector([Fault(point="checkpoint_save", at=0, times=99)])
    sup = BatchSupervisor(make_engine(build_fib(), make_conf()),
                          faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", args, max_steps=500_000)
    assert res.completed.all()
    assert all(f.fault_class == "checkpoint" for f in sup.failures)
    assert not list(tmp_path.glob("ckpt-*.npz"))


# ---------------------------------------------------------------------------
# fault class 4a: poison lane (lane-attributed repeated kernel fault)
# ---------------------------------------------------------------------------
def test_poison_lane_demoted_to_scalar(tmp_path):
    args = [(np.arange(LANES) % 11).astype(np.int64)]
    # the same lane-attributed fault fires poison_lane_retries times:
    # lane 3 must be quarantined — demoted to the scalar engine (fib has
    # no host imports) — and the batch must finish correctly
    inj = FaultInjector([Fault(point="launch", at=2, times=2,
                               lanes=(3,))])
    sup = BatchSupervisor(
        make_engine(build_fib(), make_conf(poison_lane_retries=2)),
        faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", args, max_steps=500_000)
    assert inj.fired == 2
    assert res.completed.all()  # incl. lane 3, via the scalar rung
    assert (res.results[0] == [fib_ref(n % 11) for n in range(LANES)]).all()
    poisons = [f for f in sup.failures if f.fault_class == "poison_lane"]
    assert len(poisons) == 1 and poisons[0].lanes == (3,)
    assert poisons[0].tier == "scalar"


def test_poison_lane_terminated_with_host_imports(tmp_path):
    # a module WITH host imports cannot be scalar-demoted (WASI side
    # effects would double-apply): the poisoned lane is terminated
    lanes, iters = 8, 2
    inj = FaultInjector([Fault(point="launch", at=1, times=2,
                               lanes=(2,))])
    eng, sink = _echo_setup(make_conf(poison_lane_retries=2), lanes,
                            os.devnull)
    sup = BatchSupervisor(eng, faults=inj,
                          checkpoint_dir=str(tmp_path))
    res = sup.run("echo", [np.full(lanes, iters, np.int64)],
                  max_steps=200_000)
    os.close(sink)
    assert res.trap[2] == int(ErrCode.Terminated)
    done = np.ones(lanes, bool)
    done[2] = False
    assert res.completed[done].all()
    poisons = [f for f in sup.failures if f.fault_class == "poison_lane"]
    assert len(poisons) == 1 and poisons[0].lanes == (2,)
    assert poisons[0].tier == "simt"


# ---------------------------------------------------------------------------
# fault class 4b: runaway lane (lane_step_cap)
# ---------------------------------------------------------------------------
def test_runaway_lane_terminated(tmp_path):
    args = np.arange(LANES).astype(np.int64)
    args[5] = -1  # lane 5 loops forever
    sup = BatchSupervisor(
        make_engine(build_selective_runaway(),
                    make_conf(lane_step_cap=5_000)),
        checkpoint_dir=str(tmp_path))
    res = sup.run("work", [args], max_steps=10_000_000)
    assert res.trap[5] == int(ErrCode.Terminated)
    others = np.ones(LANES, bool)
    others[5] = False
    assert res.completed[others].all()
    expect = np.array([n * (n - 1) // 2 for n in range(LANES)])
    assert (res.results[0][others] == expect[others]).all()
    runaways = [f for f in sup.failures if f.fault_class == "runaway"]
    assert len(runaways) == 1 and runaways[0].lanes == (5,)
    # the batch finished well under the (huge) step budget: the runaway
    # did not pin the device loop
    assert res.steps < 10_000_000


# ---------------------------------------------------------------------------
# degradation ladder: SIMT tier exhausted -> gas-metered scalar engine
# ---------------------------------------------------------------------------
def test_ladder_demotes_to_scalar_engine(tmp_path):
    args = [(np.arange(LANES) % 9).astype(np.int64)]
    inj = FaultInjector([Fault(point="launch", at=0, times=1000)])
    sup = BatchSupervisor(
        make_engine(build_fib(), make_conf(max_retries=2)),
        faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", args, max_steps=500_000)
    assert res.completed.all()
    assert (res.results[0] == [fib_ref(n % 9) for n in range(LANES)]).all()
    classes = [f.fault_class for f in sup.failures]
    assert "demote" in classes
    # max_retries + 1 per SIMT rung; fib fuses by default, so the
    # ladder now walks fused -> unfused SIMT -> scalar (batch/fuse.py)
    assert classes.count("launch") == 6


def test_ladder_exhaustion_raises_engine_failure(tmp_path):
    # echo has host imports: no scalar rung; permanent launch failure
    # must surface as EngineFailure carrying the FailureRecord taxonomy
    eng, sink = _echo_setup(make_conf(max_retries=1), 8, os.devnull)
    inj = FaultInjector([Fault(point="launch", at=0, times=1000)])
    sup = BatchSupervisor(eng, faults=inj, checkpoint_dir=str(tmp_path))
    with pytest.raises(EngineFailure) as ei:
        sup.run("echo", [np.full(8, 1, np.int64)], max_steps=100_000)
    os.close(sink)
    assert any(f.fault_class == "demote" for f in ei.value.failures)


# ---------------------------------------------------------------------------
# multi-tenant: crash/resume bit-exactness across tenants
# ---------------------------------------------------------------------------
def _mt_engine(conf):
    exf, storef, instf = instantiate(build_fib(), conf)
    exl, storel, instl = instantiate(build_loop_sum(), conf)
    t0 = Tenant(engine=BatchEngine(instf, store=storef, conf=conf,
                                   lanes=8),
                func_name="fib",
                args_lanes=[(np.arange(8) % 10).astype(np.int64)],
                lanes=8)
    t1 = Tenant(engine=BatchEngine(instl, store=storel, conf=conf,
                                   lanes=8),
                func_name="loop_sum",
                args_lanes=[(np.arange(8) * 7).astype(np.int64)],
                lanes=8)
    return MultiTenantBatchEngine([t0, t1], conf=conf)


def test_multitenant_fault_resume_bitmatch(tmp_path):
    ref = BatchSupervisor(_mt_engine(make_conf()),
                          checkpoint_dir=str(tmp_path / "ref"))
    rres = ref.run(max_steps=500_000)
    assert not ref.failures

    inj = FaultInjector([Fault(point="launch", at=2)])
    sup = BatchSupervisor(_mt_engine(make_conf()), faults=inj,
                          checkpoint_dir=str(tmp_path / "sup"))
    res = sup.run(max_steps=500_000)
    assert inj.fired == 1
    assert len(res) == len(rres) == 2
    for a, b in zip(res, rres):
        assert a.completed.all()
        assert_results_identical(a, b)
    # spot-check semantics, not just self-consistency
    assert (res[0].results[0] == [fib_ref(n % 10) for n in range(8)]).all()
    assert (res[1].results[0]
            == [sum(range(n * 7)) for n in range(8)]).all()


# ---------------------------------------------------------------------------
# harness determinism + misc
# ---------------------------------------------------------------------------
def test_injector_is_deterministic():
    def schedule():
        inj = FaultInjector(seeded_faults(seed=42, n=3))
        seen = []
        for i in range(8):
            for point in ("launch", "serve"):
                try:
                    inj.fire(point)
                except InjectedFault as e:
                    seen.append((e.point, e.index))
        assert inj.fired == len(seen)
        return seen

    first = schedule()
    assert first  # the seeded plan actually fires
    assert schedule() == first  # same seed -> same incident schedule
    other = FaultInjector(seeded_faults(seed=43, n=3))
    assert [(f.point, f.at) for f in other.faults] \
        != [(f.point, f.at) for f in
            FaultInjector(seeded_faults(seed=42, n=3)).faults]


def test_scalar_rerun_reports_real_trap_codes():
    # a lane whose scalar re-run genuinely traps keeps its trap code
    from wasmedge_tpu.utils.builder import ModuleBuilder

    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], [], [
        ("i32.const", 1), ("local.get", 0), "i32.div_s",
    ], export="inv")
    conf = make_conf()
    ex, store, inst = instantiate(b.build(), conf)
    fidx = inst.exports["inv"][1]
    cells, trap, recs = scalar_rerun(
        inst, conf, "inv", fidx, [np.array([2, 0], np.int64)],
        np.array([0, 1], np.int64), 10_000)
    assert not recs
    from wasmedge_tpu.batch.image import TRAP_DONE

    assert trap[0] == TRAP_DONE and cells[0, 0] == 0  # 1 // 2
    assert trap[1] == int(ErrCode.DivideByZero)


def test_supervisor_records_land_in_statistics(tmp_path):
    from wasmedge_tpu.common.statistics import Statistics

    stats = Statistics()
    inj = FaultInjector([Fault(point="launch", at=1)])
    sup = BatchSupervisor(make_engine(build_fib(), make_conf()),
                          stats=stats, faults=inj,
                          checkpoint_dir=str(tmp_path))
    res = sup.run("fib", [np.full(LANES, 8, np.int64)],
                  max_steps=500_000)
    assert res.completed.all()
    assert [f.fault_class for f in stats.failures] == ["launch"]
    dumped = stats.dump()
    assert dumped["failures"][0]["fault_class"] == "launch"
