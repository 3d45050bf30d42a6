"""Mesh-level fault tolerance under deterministic fault injection.

ISSUE 5 acceptance: with one injected device failure on an 8-fake-device
run, the supervised sharded drive completes and its merged BatchResult
(results/trap/retired) is BIT-IDENTICAL to the unfaulted run; a
full-process crash + resume from a coordinated mesh checkpoint is
likewise bit-identical.  The suite also pins device ejection + lane
migration (elastic shrink), cooperative cancellation stopping sibling
devices, per-device error aggregation in the unsupervised drive
(MeshDriveError), and the lifted lanes-%-devices restriction (1000
lanes on 8 fake devices).

Runs on the conftest-forced 8-device virtual CPU mesh
(`--xla_force_host_platform_device_count=8`).  Fast by construction
(tiny lane counts, short chunks, SIMT supervision tier): stays inside
the tier-1 `-m 'not slow'` budget.
"""

import os

import numpy as np
import pytest

from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import EngineFailure
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.parallel.mesh import MeshDriveError, run_pallas_sharded
from wasmedge_tpu.parallel.supervisor import MeshSupervisor
from wasmedge_tpu.testing.faults import Fault, FaultInjector, InjectedFault
from tests.helpers import instantiate

pytestmark = pytest.mark.faults

LANES = 32


def make_conf(**sup):
    conf = Configure()
    conf.batch.steps_per_launch = 100
    conf.batch.rng_seed = 7  # deterministic tier-0 streams across engines
    # small stack planes: n_devices engines compile per supervised run
    conf.batch.value_stack_depth = 64
    conf.batch.call_stack_depth = 32
    conf.supervisor.backoff_base_s = 0.0  # no sleeping in tests
    conf.supervisor.checkpoint_every_steps = 200
    for k, v in sup.items():
        setattr(conf.supervisor, k, v)
    return conf


def make_inst(data, conf, imports=None):
    ex, store, inst = instantiate(data, conf, imports=imports)
    return store, inst


def devices(n):
    import jax

    devs = jax.devices()[:n]
    assert len(devs) == n, "virtual device mesh missing"
    return devs


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


FIB_ARGS = [(np.arange(LANES) % 11).astype(np.int64)]
FIB_EXPECT = np.array([fib_ref(n % 11) for n in range(LANES)], np.int64)


@pytest.fixture(scope="module")
def fib_ref_result(tmp_path_factory):
    """The unfaulted supervised 8-device run every bit-identity test
    compares against (computed once per module)."""
    conf = make_conf()
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(
        inst, store=store, conf=conf, devices=devices(8),
        checkpoint_dir=str(tmp_path_factory.mktemp("ref")))
    res = sup.run("fib", FIB_ARGS, max_steps=500_000)
    assert not sup.failures
    assert (res.results[0] == FIB_EXPECT).all()
    return res


# ---------------------------------------------------------------------------
# device failure detection: retry-then-recover
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("obs", [False, True])
def test_device_launch_fault_retry_recover_bitmatch(tmp_path,
                                                    fib_ref_result, obs):
    """ISSUE 5 acceptance pin: one injected device failure on an
    8-fake-device run — the supervised drive completes bit-identical to
    the unfaulted run, and with the flight recorder on the incident is
    in its event stream."""
    inj = FaultInjector([Fault(point="device_launch", at=0,
                               match={"device": 2})])
    conf = make_conf()
    conf.obs.enabled = obs
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(8),
                         faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", FIB_ARGS, max_steps=500_000)
    assert inj.fired == 1
    assert_results_identical(res, fib_ref_result)
    assert [f.fault_class for f in sup.failures] == ["device_launch"]
    assert "device 2" in sup.failures[0].error
    if obs:
        assert "failure/device_launch" in sup.obs.event_names()
    # retried, never ejected
    assert not sup._bad_devices


def test_device_serve_fault_retry_recover(tmp_path):
    """A mid-serve host exception on one device's hostcall drain is
    retried from that device's snapshot; a pure host import replays
    deterministically, so the merged result matches the unfaulted run."""
    from wasmedge_tpu.runtime.hostfunc import ImportObject, PyHostFunction
    from wasmedge_tpu.utils.builder import ModuleBuilder

    def build():
        imp = ImportObject("env")
        imp.add_func("triple", PyHostFunction(lambda mem, x: x * 3,
                                              ["i32"], ["i32"]))
        b = ModuleBuilder()
        b.import_func("env", "triple", ["i32"], ["i32"])
        b.add_function(["i32"], ["i32"], [],
                       [("local.get", 0), ("call", 0)], export="f")
        return b.build(), imp

    args = [np.arange(LANES, dtype=np.int64)]

    data, imp = build()
    conf = make_conf()
    store, inst = make_inst(data, conf, imports=[imp])
    ref = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         checkpoint_dir=str(tmp_path / "ref")).run(
        "f", args, max_steps=50_000)
    assert (ref.results[0] == args[0] * 3).all()

    data, imp = build()
    conf = make_conf()
    store, inst = make_inst(data, conf, imports=[imp])
    inj = FaultInjector([Fault(point="device_serve", at=0,
                               match={"device": 1})])
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         faults=inj, checkpoint_dir=str(tmp_path / "s"))
    res = sup.run("f", args, max_steps=50_000)
    assert inj.fired == 1
    assert_results_identical(res, ref)
    assert [f.fault_class for f in sup.failures] == ["device_serve"]


# ---------------------------------------------------------------------------
# quarantine + lane migration (elastic shrink)
# ---------------------------------------------------------------------------
def test_device_ejection_migrates_lanes_bitmatch(tmp_path,
                                                 fib_ref_result):
    """A device that keeps failing is ejected; its lanes migrate to
    surviving devices and the merged result stays bit-identical — here
    even across device counts (a 2-device elastic-shrunk run vs the
    8-device reference): per-lane outcomes are placement-independent."""
    inj = FaultInjector([Fault(point="device_launch", times=99,
                               match={"device": 1})])
    conf = make_conf(max_device_retries=1)
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", FIB_ARGS, max_steps=500_000)
    assert_results_identical(res, fib_ref_result)
    classes = {f.fault_class for f in sup.failures}
    assert "device_quarantine" in classes
    assert "lane_migrate" in classes
    assert sup._bad_devices == {1}
    # the ejected device's lanes were re-packed onto OTHER devices
    orig = next(s for s in sup.shards if s.dev_index == 1)
    moved = [s for s in sup.shards if s.di != orig.di
             and np.isin(s.lane_ids, orig.lane_ids).any()]
    assert moved and all(s.dev_index != 1 for s in moved)
    assert all(s.done for s in moved)


def test_every_device_ejected_raises(tmp_path):
    """When no healthy device remains to migrate to, the run raises
    EngineFailure instead of losing lanes silently."""
    inj = FaultInjector([Fault(point="device_launch", times=9999)])
    conf = make_conf(max_device_retries=1)
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         faults=inj, checkpoint_dir=str(tmp_path))
    with pytest.raises(EngineFailure):
        sup.run("fib", FIB_ARGS, max_steps=500_000)
    assert len(sup._bad_devices) == 2


# ---------------------------------------------------------------------------
# coordinated mesh checkpointing: crash + resume
# ---------------------------------------------------------------------------
def test_mesh_checkpoint_crash_resume_bitmatch(tmp_path, fib_ref_result):
    """ISSUE 5 acceptance pin: full-process crash after a coordinated
    mesh checkpoint, then resume=True — bit-identical to the
    uninterrupted run."""
    # SystemExit models the process dying: the supervisor re-raises it
    # (fatal, not retried), leaving the coordinated lineage on disk
    # arrival 20 lands in round 2, AFTER round 1's coordinated
    # checkpoint barrier (8 devices x 2 launches per slice per round)
    inj = FaultInjector([Fault(point="device_launch", at=20,
                               exc=lambda ctx: SystemExit("crash"))])
    conf = make_conf()
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(8),
                         faults=inj, checkpoint_dir=str(tmp_path))
    with pytest.raises(SystemExit):
        sup.run("fib", FIB_ARGS, max_steps=500_000)
    members = [m for m in os.listdir(tmp_path) if m.startswith("mesh-")]
    assert members, "crash happened before any coordinated checkpoint"
    # shards + manifest + partial merge inside one atomic member
    newest = sorted(members)[-1]
    files = os.listdir(tmp_path / newest)
    assert "manifest.json" in files and "merged.npz" in files

    conf2 = make_conf()
    store2, inst2 = make_inst(build_fib(), conf2)
    sup2 = MeshSupervisor(inst2, store=store2, conf=conf2,
                          devices=devices(8),
                          checkpoint_dir=str(tmp_path), resume=True)
    res = sup2.run("fib", FIB_ARGS, max_steps=500_000)
    assert sup2.resumed
    assert_results_identical(res, fib_ref_result)


def test_corrupt_mesh_member_skipped_on_resume(tmp_path, fib_ref_result):
    """A corrupt newest mesh member is recorded + skipped; resume walks
    to an older good member (or starts fresh) and still completes
    bit-identical."""
    # arrival 6 is in round 2 for 2 devices (2 x 2 arrivals per round)
    inj = FaultInjector([Fault(point="device_launch", at=6,
                               exc=lambda ctx: SystemExit("crash"))])
    conf = make_conf()
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         faults=inj, checkpoint_dir=str(tmp_path))
    with pytest.raises(SystemExit):
        sup.run("fib", FIB_ARGS, max_steps=500_000)
    newest = sorted(m for m in os.listdir(tmp_path)
                    if m.startswith("mesh-"))[-1]
    with open(tmp_path / newest / "manifest.json", "w") as f:
        f.write("{corrupt")

    conf2 = make_conf()
    store2, inst2 = make_inst(build_fib(), conf2)
    sup2 = MeshSupervisor(inst2, store=store2, conf=conf2,
                          devices=devices(2),
                          checkpoint_dir=str(tmp_path), resume=True)
    res = sup2.run("fib", FIB_ARGS, max_steps=500_000)
    assert_results_identical(res, fib_ref_result)
    assert any(f.fault_class == "mesh_checkpoint" for f in sup2.failures)


def test_resume_refuses_other_invocation(tmp_path):
    """A mesh lineage taken for different arguments must not be adopted
    (invocation fingerprint mismatch) — the run starts fresh instead of
    continuing someone else's answer."""
    conf = make_conf()
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         checkpoint_dir=str(tmp_path))
    sup.run("fib", FIB_ARGS, max_steps=500_000)
    assert any(m.startswith("mesh-") for m in os.listdir(tmp_path))

    other = [np.full(LANES, 9, np.int64)]
    conf2 = make_conf()
    store2, inst2 = make_inst(build_fib(), conf2)
    sup2 = MeshSupervisor(inst2, store=store2, conf=conf2,
                          devices=devices(2),
                          checkpoint_dir=str(tmp_path), resume=True)
    res = sup2.run("fib", other, max_steps=500_000)
    assert not sup2.resumed
    assert (res.results[0] == fib_ref(9)).all()
    assert any(f.fault_class == "mesh_checkpoint" for f in sup2.failures)


# ---------------------------------------------------------------------------
# cooperative cancellation
# ---------------------------------------------------------------------------
def test_cancellation_stops_siblings(tmp_path):
    """eject_devices=False: a device exhausting its retries cancels the
    whole mesh run — sibling devices stop at their next launch boundary
    with work still unfinished instead of running to completion."""
    inj = FaultInjector([Fault(point="device_launch", times=99,
                               match={"device": 0})])
    conf = make_conf(max_device_retries=1, eject_devices=False)
    # long workload + small slices: siblings need many rounds, so the
    # cancel flag must be what stops them
    conf.supervisor.checkpoint_every_steps = 100
    store, inst = make_inst(build_fib(), conf)
    args = [np.full(LANES, 14, np.int64)]
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         faults=inj, checkpoint_dir=str(tmp_path))
    with pytest.raises(EngineFailure) as ei:
        sup.run("fib", args, max_steps=5_000_000)
    assert "device 0" in str(ei.value)
    assert not sup._bad_devices  # fail-fast, not elastic shrink
    siblings = [s for s in sup.shards if s.dev_index != 0]
    assert any(not s.done for s in siblings), \
        "siblings ran to completion despite cancellation"


# ---------------------------------------------------------------------------
# uneven lane counts: lanes % n_devices lifted
# (the unsupervised pallas-drive tests — 1000 lanes on 8 fake devices,
#  uneven 30-on-8 — live with the other run_pallas_sharded coverage in
#  tests/test_mesh.py)
# ---------------------------------------------------------------------------
def test_supervised_pads_uneven_lanes(tmp_path):
    """The supervised drive takes uneven lane counts: 29 lanes on 2
    devices split 15+14 — no clone/pad lane ever executes, results
    merge in original lane order."""
    lanes = 29
    args = [(np.arange(lanes) % 11).astype(np.int64)]
    conf = make_conf()
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         checkpoint_dir=str(tmp_path))
    res = sup.run("fib", args, max_steps=500_000)
    assert res.trap.shape == (lanes,)
    assert (res.trap == -1).all()
    assert (res.results[0] ==
            np.array([fib_ref(n % 11) for n in range(lanes)])).all()


# ---------------------------------------------------------------------------
# error aggregation in the unsupervised drive
# ---------------------------------------------------------------------------
def _tiny_pallas_conf():
    conf = Configure()
    conf.batch.value_stack_depth = 64
    conf.batch.call_stack_depth = 32
    conf.batch.steps_per_launch = 1000
    conf.batch.interpret = True
    return conf


def test_mesh_drive_error_aggregates_all_devices(monkeypatch):
    """The threaded drive reports EVERY failed device, not errs[0]."""
    from wasmedge_tpu.batch import scheduler as sched_mod

    def boom(self):
        raise RuntimeError("injected drive failure")

    monkeypatch.setattr(sched_mod.BlockScheduler, "run", boom)
    conf = _tiny_pallas_conf()
    store, inst = make_inst(build_fib(), conf)
    devs = devices(2)
    with pytest.raises(MeshDriveError) as ei:
        run_pallas_sharded(inst, store, conf, "fib",
                           [np.full(8, 5, np.int64)], devices=devs,
                           max_steps=10_000, interpret=True)
    err = ei.value
    assert len(err.failures) == 2
    assert {str(d) for d, _ in err.failures} == {str(d) for d in devs}
    assert all(isinstance(e, RuntimeError) for _, e in err.failures)


def test_serial_drive_error_names_device(monkeypatch):
    """The non-threaded drive wraps its exception with device
    attribution too (it used to escape raw)."""
    from wasmedge_tpu.batch import scheduler as sched_mod

    def boom(self):
        raise RuntimeError("injected launch failure")

    monkeypatch.setattr(sched_mod.BlockScheduler, "launch", boom)
    conf = _tiny_pallas_conf()
    store, inst = make_inst(build_fib(), conf)
    with pytest.raises(MeshDriveError) as ei:
        run_pallas_sharded(inst, store, conf, "fib",
                           [np.full(8, 5, np.int64)], devices=devices(2),
                           max_steps=10_000, interpret=True,
                           threaded=False)
    assert len(ei.value.failures) == 1
    dev, exc = ei.value.failures[0]
    assert dev is not None
    assert isinstance(exc, RuntimeError)


# ---------------------------------------------------------------------------
# fault-injection seams
# ---------------------------------------------------------------------------
def test_fault_match_counts_own_arrivals():
    """`match` faults index their OWN arrivals: "device 2's first
    launch" is deterministic regardless of the interleaving of other
    devices' arrivals at the shared seam."""
    inj = FaultInjector([Fault(point="device_launch", at=1,
                               match={"device": 2})])
    # other devices' arrivals don't advance device 2's counter
    inj.fire("device_launch", device=0)
    inj.fire("device_launch", device=1)
    inj.fire("device_launch", device=2)   # device 2 arrival 0: no fire
    inj.fire("device_launch", device=0)
    with pytest.raises(InjectedFault):
        inj.fire("device_launch", device=2)   # device 2 arrival 1: fires
    assert inj.fired == 1
    assert inj.log == [("device_launch", 1)]


def test_mesh_checkpoint_save_fault_never_kills_run(tmp_path,
                                                    fib_ref_result):
    """A failed coordinated snapshot is recorded, not raised — the
    healthy run continues to a bit-identical merge."""
    inj = FaultInjector([Fault(point="mesh_checkpoint_save", at=0)])
    conf = make_conf()
    store, inst = make_inst(build_fib(), conf)
    sup = MeshSupervisor(inst, store=store, conf=conf, devices=devices(2),
                         faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", FIB_ARGS, max_steps=500_000)
    assert inj.fired == 1
    assert_results_identical(res, fib_ref_result)
    assert any(f.fault_class == "mesh_checkpoint" for f in sup.failures)


# ---------------------------------------------------------------------------
# r15: shard-drive rung of the degradation ladder
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("obs", [False, True])
def test_shard_drive_fault_falls_back_to_threaded_rung(obs):
    """An injected shard-drive failure demotes the supervised run to
    the threaded per-device rung: the run completes bit-identical to
    an unfaulted one, with a FailureRecord('shard_drive') attributing
    the demotion (mirrored into the flight recorder when it is on)."""
    conf = make_conf(checkpoint_every_steps=None)
    conf.obs.enabled = obs
    store, inst = make_inst(build_fib(), conf)
    ref = MeshSupervisor(inst, store=store, conf=conf,
                         devices=devices(4)).run(
        "fib", FIB_ARGS, max_steps=200_000)
    assert (ref.results[0] == FIB_EXPECT).all()

    inj = FaultInjector([Fault(point="shard_launch", at=0)])
    sup = MeshSupervisor(inst, store=store, conf=conf,
                         devices=devices(4), faults=inj)
    res = sup.run("fib", FIB_ARGS, max_steps=200_000)
    assert inj.fired == 1
    assert any(f.fault_class == "shard_drive" for f in sup.failures)
    if obs:
        assert "failure/shard_drive" in sup.obs.event_names()
    assert_results_identical(res, ref)


def test_shard_drive_skipped_when_cadence_configured():
    """A checkpoint cadence needs the per-device SIMT tier (the
    coordinated mesh snapshots slice per-device states), so the shard
    tier must not even be attempted — an armed shard fault never
    fires."""
    conf = make_conf()   # checkpoint_every_steps=200 (cadence on)
    store, inst = make_inst(build_fib(), conf)
    inj = FaultInjector([Fault(point="shard_launch", at=0)])
    import tempfile

    with tempfile.TemporaryDirectory(prefix="mesh-ckpt-") as d:
        sup = MeshSupervisor(inst, store=store, conf=conf,
                             devices=devices(2), faults=inj,
                             checkpoint_dir=d)
        res = sup.run("fib", FIB_ARGS, max_steps=200_000)
    assert inj.fired == 0
    assert (res.results[0] == FIB_EXPECT).all()


def test_shard_drive_threaded_param_skips_shard_tier():
    """MeshSupervisor(drive='threaded') never attempts the shard rung
    even with the knob on; use_shard_drive=False does the same through
    the Configure."""
    conf = make_conf(checkpoint_every_steps=None)
    store, inst = make_inst(build_fib(), conf)
    inj = FaultInjector([Fault(point="shard_launch", at=0, times=99)])
    res = MeshSupervisor(inst, store=store, conf=conf,
                         devices=devices(2), faults=inj,
                         drive="threaded").run(
        "fib", FIB_ARGS, max_steps=200_000)
    assert inj.fired == 0
    assert (res.results[0] == FIB_EXPECT).all()

    conf2 = make_conf(checkpoint_every_steps=None,
                      use_shard_drive=False)
    store2, inst2 = make_inst(build_fib(), conf2)
    res2 = MeshSupervisor(inst2, store=store2, conf=conf2,
                          devices=devices(2), faults=inj).run(
        "fib", FIB_ARGS, max_steps=200_000)
    assert inj.fired == 0
    assert (res2.results[0] == FIB_EXPECT).all()


def test_unsupervised_shard_drive_wraps_failures():
    """The unsupervised shard drive wraps any drive failure in
    ShardDriveError with the cause chained (run_mesh's documented
    contract: the fallback ladder lives in the supervisor)."""
    from wasmedge_tpu.parallel.mesh import run_mesh
    from wasmedge_tpu.parallel.shard_drive import ShardDriveError

    conf = make_conf(checkpoint_every_steps=None)
    store, inst = make_inst(build_fib(), conf)
    inj = FaultInjector([Fault(point="shard_launch", at=0)])
    with pytest.raises(ShardDriveError) as ei:
        run_mesh(inst, store, conf, "fib", FIB_ARGS,
                 devices=devices(2), max_steps=200_000, faults=inj)
    assert isinstance(ei.value.__cause__, InjectedFault)


# ---------------------------------------------------------------------------
# threaded-rung stdout semantics across a device restore (ROADMAP #1
# carry-over, pinned in r16): at-least-once with a BOUNDED window
# ---------------------------------------------------------------------------
def _repeat_stamp_module():
    """Each lane fd_writes its 4-byte little-endian id `iters` times —
    a repeating self-identifying WASI record stream, so duplicated
    flushes are countable per lane."""
    from wasmedge_tpu.utils.builder import ModuleBuilder

    b = ModuleBuilder()
    b.import_func("wasi_snapshot_preview1", "fd_write",
                  ["i32", "i32", "i32", "i32"], ["i32"])
    b.add_memory(1, 1)
    b.add_function(["i32", "i32"], ["i32"], ["i32", "i32"], [
        ("block", None),
        ("loop", None),
        ("local.get", 2), ("local.get", 1), "i32.ge_u", ("br_if", 1),
        ("i32.const", 128), ("local.get", 0), ("i32.store", 2, 0),
        ("i32.const", 64), ("i32.const", 128), ("i32.store", 2, 0),
        ("i32.const", 68), ("i32.const", 4), ("i32.store", 2, 0),
        ("i32.const", 1), ("i32.const", 64), ("i32.const", 1),
        ("i32.const", 32), ("call", 0), ("local.set", 3),
        ("local.get", 2), ("i32.const", 1), "i32.add",
        ("local.set", 2),
        ("br", 0),
        "end",
        "end",
        ("local.get", 0),
    ], export="stamp")
    return b.build()


def _stamp_wasi_run(tmp_path, tag, conf, run_fn, lanes, iters):
    """Instantiate the repeat-stamp module with fd 1 redirected to a
    file; returns (result, per-lane-id record counts)."""
    from collections import Counter

    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.host.wasi import WasiModule
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    wasi = WasiModule()
    wasi.init_wasi(dirs=[], prog_name="stamp")
    path = str(tmp_path / f"rstamp-{tag}.bin")
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC)
    wasi.env.fds[1].os_fd = fd
    mod = Validator(conf).validate(
        Loader(conf).parse_module(_repeat_stamp_module()))
    store = StoreManager()
    ex = Executor(conf)
    ex.register_import_object(store, wasi)
    inst = ex.instantiate(store, mod)
    ids = np.arange(lanes, dtype=np.int64) + 1000
    res = run_fn(inst, store, [ids, np.full(lanes, iters, np.int64)])
    os.close(fd)
    with open(path, "rb") as f:
        records = np.frombuffer(f.read(), np.int32)
    return res, Counter(int(r) for r in records)


def test_threaded_restore_stdout_at_least_once_window_bounded(tmp_path):
    """The threaded rung's documented stdout caveat, pinned instead of
    folklore: a device restore replays tier-0 stdout AT-LEAST-ONCE,
    and the duplicated-flush window is BOUNDED by the region replayed
    since the restore point (here: the faulted device's single
    pre-fault launch — no mesh checkpoint exists yet, so the retry
    restores its initial sub-state).  Assertions:

      - every lane's records appear at least its true count (nothing
        is ever lost)
      - lanes on UNAFFECTED devices appear exactly once per write (the
        failure domain is one device)
      - the faulted device's extra records are bounded by what ONE
        launch window can flush per lane
      - results stay bit-identical to the unfaulted run (the replay is
        output-duplication only, never state corruption)

    (The shard drive resolved this caveat structurally — one engine,
    one stdout cursor; see README 'Single-program mesh'.)"""
    # 12 iterations: the faulted device must reach a SECOND launch
    # (chunk 100) even with r19 memory-run fusion retiring the stamp
    # loop's licensed stores in fused dispatch cells
    lanes, iters, chunk = 8, 12, 100
    dev_n = 4

    def base_conf():
        conf = make_conf(checkpoint_every_steps=None)
        conf.batch.steps_per_launch = chunk
        return conf

    def single(inst, store, args):
        from wasmedge_tpu.batch.engine import BatchEngine

        return BatchEngine(inst, store=store, conf=base_conf(),
                           lanes=lanes).run("stamp", args,
                                            max_steps=100_000)

    ref, ref_counts = _stamp_wasi_run(tmp_path, "single", base_conf(),
                                      single, lanes, iters)
    assert (ref.trap == -1).all()
    assert all(ref_counts[1000 + k] == iters for k in range(lanes))
    # DISPATCH steps one loop iteration takes (from the oracle run):
    # the launch-window write bound below derives from it.  Steps, not
    # retired — under superinstruction/memory-run fusion one dispatch
    # retires a whole run, and the launch window is denominated in
    # dispatches
    spi = int(ref.steps) // iters
    w_max = chunk // max(spi, 1) + 1   # writes one launch can flush

    fault_dev = 2
    inj = FaultInjector([Fault(point="device_launch", at=1,
                               match={"device": fault_dev})])

    def threaded(inst, store, args):
        conf = base_conf()
        conf.supervisor.use_kernel_tier = False
        return MeshSupervisor(inst, store=store, conf=conf,
                              devices=devices(dev_n), faults=inj,
                              drive="threaded",
                              checkpoint_dir=str(tmp_path)).run(
            "stamp", args, max_steps=100_000)

    res, counts = _stamp_wasi_run(tmp_path, "threaded", base_conf(),
                                  threaded, lanes, iters)
    assert inj.fired == 1, "the restore must actually have happened"
    # state recovery is bit-identical regardless of the stdout caveat
    assert (np.asarray(res.results[0])
            == np.asarray(ref.results[0])).all()
    assert (np.asarray(res.trap) == np.asarray(ref.trap)).all()
    # contiguous split: device d owns lanes [d*2, d*2+2) for 16/8
    per_dev = lanes // dev_n
    lo, hi = fault_dev * per_dev, (fault_dev + 1) * per_dev
    for k in range(lanes):
        n = counts[1000 + k]
        assert n >= iters, f"lane {k} lost stdout records"
        if lo <= k < hi:
            # the at-least-once window: bounded by one launch's flushes
            assert n <= iters + w_max, \
                f"lane {k} duplicated beyond the replay window"
        else:
            assert n == iters, \
                f"lane {k} is outside the failure domain but duplicated"
