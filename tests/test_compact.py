"""Divergence-aware lane compaction (batch/compact.py) — ISSUE 14.

Pins the PC-sorted lane regrouping pass and its hard guarantees:

  - compaction on/off bit-identical (results, traps, retired) on the
    single-device SIMT engine, the shard-drive mesh (per-shard
    permutations only), the multi-tenant concatenated image, and both
    fused and unfused builds;
  - the serving layer's lane->request bindings, recycling, hv
    swapping, checkpoints, and the exactly-once stdout cursor all
    follow their lane through a fired permutation;
  - the anti-thrash quantum and the cost model are deterministic pure
    functions of the mirrors;
  - every built permutation is a bijection (shard-blocked included);
  - `Configure.batch.compact` defaults OFF (the seed path by
    construction) and checkpoints refuse a permuted snapshot when
    compaction is unavailable.

Fast by construction (tiny lane counts, short chunks): tier-1.
"""

import os
import tempfile

import numpy as np
import pytest

from wasmedge_tpu.batch.compact import (
    LaneCompactor,
    build_permutation,
    compact_decision,
    estimate_breaks,
    live_mask,
)
from wasmedge_tpu.batch.engine import BatchEngine
from wasmedge_tpu.batch.image import TRAP_DONE, TRAP_HOSTCALL
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.executor import Executor
from wasmedge_tpu.loader import Loader
from wasmedge_tpu.models import build_fib, build_loop_sum
from wasmedge_tpu.runtime.store import StoreManager
from wasmedge_tpu.validator import Validator

pytestmark = pytest.mark.compact

LANES = 16


def fib_ref(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def make_conf(compact=True, fuse=True, forced=True, **batch):
    conf = Configure()
    conf.batch.compact = compact
    conf.batch.fuse_superinstructions = fuse
    conf.batch.steps_per_launch = 48
    conf.batch.value_stack_depth = 128
    conf.batch.call_stack_depth = 64
    if forced:
        # tiny test mixes would not clear the production cost model:
        # pin the policy fully open so fires are deterministic
        conf.batch.compact_min_interval = 1
        conf.batch.compact_trigger = 0.0
        conf.batch.compact_cost_factor = 0.0
        conf.batch.compact_width_floor = 4
    for k, v in batch.items():
        setattr(conf.batch, k, v)
    return conf


def instantiate(data, conf):
    mod = Validator(conf).validate(Loader(conf).parse_module(data))
    store = StoreManager()
    inst = Executor(conf).instantiate(store, mod)
    return inst, store


def make_engine(conf, lanes=LANES, data=None):
    inst, store = instantiate(data or build_fib(), conf)
    return BatchEngine(inst, store=store, conf=conf, lanes=lanes)


def div_args(lanes=LANES, lo=4, hi=11, seed=3):
    ns = (lo + np.arange(lanes) % (hi - lo + 1)).astype(np.int64)
    np.random.default_rng(seed).shuffle(ns)
    return ns


def assert_results_identical(a, b):
    for ra, rb in zip(a.results, b.results):
        assert (np.asarray(ra) == np.asarray(rb)).all()
    assert (np.asarray(a.trap) == np.asarray(b.trap)).all()
    assert (np.asarray(a.retired) == np.asarray(b.retired)).all()


# ---------------------------------------------------------------------------
# policy: bijection, quantum, cost model — pure-function determinism
# ---------------------------------------------------------------------------
def test_permutation_is_a_bijection():
    rng = np.random.default_rng(0)
    for n in (1, 7, 32, 257):
        pc = rng.integers(0, 50, n).astype(np.int64)
        trap = rng.choice([0, 0, 0, TRAP_DONE, 3, TRAP_HOSTCALL],
                          n).astype(np.int64)
        perm = build_permutation(pc, trap)
        assert sorted(perm.tolist()) == list(range(n))


def test_permutation_shard_blocked_is_a_bijection_within_shards():
    rng = np.random.default_rng(1)
    n, shards = 32, [slice(0, 8), slice(8, 16), slice(16, 32)]
    pc = rng.integers(0, 9, n).astype(np.int64)
    trap = rng.choice([0, 0, TRAP_DONE], n).astype(np.int64)
    perm = build_permutation(pc, trap, shard_slices=shards)
    assert sorted(perm.tolist()) == list(range(n))
    for sl in shards:   # no cross-device moves
        assert all(sl.start <= s < sl.stop for s in perm[sl])


def test_permutation_sorts_live_prefix_by_pc_stable():
    pc = np.asarray([9, 2, 9, 2, 5], np.int64)
    trap = np.asarray([0, TRAP_DONE, 0, 0, 0], np.int64)
    perm = build_permutation(pc, trap)
    # live lanes grouped by pc ascending (no divergence scores here),
    # original position breaking ties; the dead lane sinks to the tail
    assert perm.tolist() == [3, 4, 0, 2, 1]


def test_divergence_bias_groups_high_scores_first():
    pc = np.asarray([1, 7, 1, 7], np.int64)
    trap = np.zeros(4, np.int64)
    dscore = np.zeros(8, np.int64)
    dscore[7] = 5   # pc 7 is the high-divergence neighbourhood
    perm = build_permutation(pc, trap, dscore=dscore)
    assert perm.tolist() == [1, 3, 0, 2]


def test_function_key_groups_lanes_per_function():
    """r20 satellite: the engine-global function ordinal is the
    PRIMARY live key — lanes in the same function become contiguous
    even when a finer key (divergence, pc) would interleave them."""
    from wasmedge_tpu.batch.compact import function_key

    # two "functions": entry pcs 0 and 10; lanes alternate between them
    pc = np.asarray([12, 1, 11, 3, 10, 2], np.int64)
    trap = np.zeros(6, np.int64)
    fnkey = np.asarray([0] * 10 + [1] * 10, np.int64)
    # divergence says pc 12 is hottest — WITHOUT fnkey it would lead
    dscore = np.zeros(20, np.int64)
    dscore[12] = 9
    perm = build_permutation(pc, trap, dscore=dscore, fnkey=fnkey)
    # fn 0 lanes (pcs 1,2,3) first in pc order, then fn 1 lanes with
    # the divergence bias ordering inside the function group
    assert perm.tolist() == [1, 5, 3, 0, 4, 2]
    # same geometry WITHOUT the function key: divergence leads
    assert build_permutation(pc, trap, dscore=dscore).tolist() \
        == [0, 1, 5, 3, 4, 2]

    # function_key derives the ordinal plane from the image f_entry
    class _Img:
        f_entry = np.asarray([0, 10, -1], np.int64)   # one import
        code_len = 20

    fk = function_key(_Img())
    assert fk is not None
    assert fk.tolist() == [0] * 10 + [1] * 10

    class _Broken:
        f_entry = None
        code_len = 20

    assert function_key(_Broken()) is None   # never raises


def test_anti_thrash_quantum():
    pc = np.asarray([3, 1, 3, 1], np.int64)
    trap = np.zeros(4, np.int64)
    conf = make_conf(forced=False)
    conf.batch.compact_min_interval = 4
    conf.batch.compact_trigger = 0.0
    conf.batch.compact_cost_factor = 0.0
    early = compact_decision(pc, trap, 4, 48, 3, conf.batch, False)
    assert not early.fire and early.reason == "interval"
    due = compact_decision(pc, trap, 4, 48, 4, conf.batch, False)
    assert due.fire


def test_cost_model_deterministic_and_gating():
    pc = np.asarray([3, 1, 3, 1], np.int64)
    trap = np.zeros(4, np.int64)
    knobs = make_conf(forced=False).batch
    # breaks=3, ideal=1 -> win=2; cost model: win*spl >= factor*lanes
    a = compact_decision(pc, trap, 4, 48, 99, knobs, False)
    b = compact_decision(pc, trap, 4, 48, 99, knobs, False)
    assert a == b          # same mirrors -> same decision, always
    assert a.fire          # 2*48 >= 4.0*4
    knobs.compact_cost_factor = 1000.0
    c = compact_decision(pc, trap, 4, 48, 99, knobs, False)
    assert not c.fire and c.reason == "cost"
    # an idle population never fires
    idle = compact_decision(pc, np.full(4, TRAP_DONE, np.int64),
                            4, 48, 99, knobs, False)
    assert not idle.fire and idle.reason == "idle"


def test_estimate_breaks_and_live_mask():
    pc = np.asarray([5, 5, 9, 5], np.int64)
    trap = np.asarray([0, 0, 0, TRAP_HOSTCALL], np.int64)
    assert live_mask(trap).all()   # hostcall-parked lanes stay live
    breaks, ideal, unique, largest = estimate_breaks(pc, live_mask(trap))
    assert (breaks, ideal, unique) == (2, 1, 2)
    assert largest == pytest.approx(0.75)


def test_estimate_breaks_shard_blocked_ideal():
    # each shard already PC-sorted: a shard-blocked permutation can
    # buy nothing, so win must be 0 (a global ideal would leave
    # win > 0 forever and the mesh policy would fire no-ops every
    # quantum)
    pc = np.asarray([3, 3, 7, 7, 3, 3, 7, 7], np.int64)
    live = np.ones(8, bool)
    shards = [slice(0, 4), slice(4, 8)]
    breaks, ideal, unique, largest = estimate_breaks(pc, live, shards)
    assert breaks == ideal == 2     # per-shard minimum already met
    assert unique == 2 and largest == pytest.approx(0.5)
    # unsorted within a shard still shows a win
    pc2 = np.asarray([7, 3, 7, 3, 3, 3, 7, 7], np.int64)
    b2, i2, _, _ = estimate_breaks(pc2, live, shards)
    assert b2 - i2 > 0


def test_compact_defaults_off():
    conf = Configure()
    assert conf.batch.compact is False
    eng = make_engine(conf)
    eng.run("fib", [div_args()], max_steps=200_000)
    assert eng.compactor is None   # seed path by construction


# ---------------------------------------------------------------------------
# cohort parity: single device / fused & unfused / multitenant / mesh
# ---------------------------------------------------------------------------
def _ab(conf_on, conf_off, lanes=LANES, ns=None):
    ns = div_args(lanes) if ns is None else ns
    on = make_engine(conf_on, lanes).run("fib", [ns],
                                         max_steps=500_000)
    off_eng = make_engine(conf_off, lanes)
    off = off_eng.run("fib", [ns], max_steps=500_000)
    return on, off, ns


def test_single_device_bit_identical_and_correct():
    conf_on = make_conf(compact=True)
    eng = make_engine(conf_on)
    ns = div_args()
    on = eng.run("fib", [ns], max_steps=500_000)
    off = make_engine(make_conf(compact=False)).run(
        "fib", [ns], max_steps=500_000)
    assert eng.compactor.stats["fires"] >= 1
    assert eng.compactor.stats["min_width"] < LANES  # narrowing fired
    assert_results_identical(on, off)
    expect = np.asarray([fib_ref(int(n)) for n in ns], np.int64)
    assert (np.asarray(on.results[0]) == expect).all()
    # packing strictly reduced dispatch slots (retired/dispatch up)
    assert eng.compactor.stats["dispatch_slots"] < on.steps * LANES


def test_unfused_build_bit_identical():
    on, off, _ = _ab(make_conf(compact=True, fuse=False),
                     make_conf(compact=False, fuse=False))
    assert_results_identical(on, off)


def test_fused_vs_unfused_under_compaction():
    on_f, off_f, ns = _ab(make_conf(compact=True, fuse=True),
                          make_conf(compact=False, fuse=True))
    assert_results_identical(on_f, off_f)


def test_repeat_runs_reset_mapping():
    # a second run() on the same engine must start from the identity
    # mapping, not compose onto the previous run's permutation
    conf = make_conf(compact=True)
    eng = make_engine(conf)
    ns = div_args()
    expect = np.asarray([fib_ref(int(n)) for n in ns], np.int64)
    for _ in range(2):
        res = eng.run("fib", [ns], max_steps=500_000)
        assert (np.asarray(res.results[0]) == expect).all()


def test_multitenant_concat_image_bit_identical():
    from wasmedge_tpu.batch.multitenant import (
        MultiTenantBatchEngine, Tenant)

    def build(compact):
        conf = make_conf(compact=compact)
        tenants = []
        for data, fn, args in (
                (build_fib(), "fib", [div_args(8, 4, 9, seed=5)]),
                (build_loop_sum(), "loop_sum",
                 [(20 + 13 * np.arange(8)).astype(np.int64)])):
            inst, store = instantiate(data, conf)
            tenants.append(Tenant(
                engine=BatchEngine(inst, store=store, conf=conf,
                                   lanes=8),
                func_name=fn, args_lanes=args, lanes=8))
        return MultiTenantBatchEngine(tenants, conf=conf)

    mt_on = build(True)
    res_on = mt_on.run_tenants(max_steps=500_000)
    res_off = build(False).run_tenants(max_steps=500_000)
    assert mt_on.compactor is not None \
        and mt_on.compactor.stats["fires"] >= 1
    for a, b in zip(res_on, res_off):
        assert_results_identical(a, b)
        assert a.completed.all()


def test_shard_drive_mesh_bit_identical():
    from wasmedge_tpu.parallel.shard_drive import ShardDrive

    ns = div_args(22, 4, 9)   # uneven split: pads ride the last shard
    res = {}
    drives = {}
    for compact in (True, False):
        conf = make_conf(compact=compact, forced=True)
        inst, store = instantiate(build_fib(), conf)
        drv = ShardDrive(inst, store=store, conf=conf, devices=4)
        drives[compact] = drv
        res[compact] = drv.run("fib", [ns], max_steps=500_000)
    comp = drives[True].engine.compactor
    assert comp is not None and comp.stats["fires"] >= 1
    assert comp.narrow is False   # global width pinned by the sharding
    assert_results_identical(res[True], res[False])
    expect = np.asarray([fib_ref(int(n)) for n in ns], np.int64)
    assert (np.asarray(res[True].results[0]) == expect).all()


# ---------------------------------------------------------------------------
# checkpoint: the permutation rides the snapshot
# ---------------------------------------------------------------------------
def test_checkpoint_lane_src_roundtrip_and_refusal():
    from wasmedge_tpu.batch import checkpoint
    from wasmedge_tpu.batch.compact import arm

    conf = make_conf(compact=True)
    eng = make_engine(conf)
    arm(eng)
    ns = div_args()
    state = eng.initial_state(eng.export_func_idx("fib"), [ns])
    state, total = eng.run_from_state(state, 0, 96)   # two boundaries
    assert eng.compactor.stats["fires"] >= 1
    assert not eng.compactor.identity
    src = eng.compactor.src.copy()
    with tempfile.TemporaryDirectory(prefix="compact-ckpt-") as d:
        path = os.path.join(d, "ckpt.npz")
        checkpoint.save(path, eng, state, total)
        # fresh engine, compact on: src restores with the state and the
        # resumed run finishes bit-identical to an uncompacted one
        eng2 = make_engine(make_conf(compact=True))
        arm(eng2)
        st2, tot2 = checkpoint.load(path, eng2)
        assert (eng2.compactor.src == src).all()
        st2, tot2 = eng2.run_from_state(st2, tot2, 500_000)
        order = eng2.compactor.restore_order()
        got = np.asarray(st2.stack_lo)[0, order]
        ref = make_engine(make_conf(compact=False)).run(
            "fib", [ns], max_steps=500_000)
        assert (got == np.asarray(ref.results[0]).astype(
            np.uint64).astype(np.uint32).view(np.int32)).all()
        # compact-off engine must refuse the permuted snapshot loudly
        eng3 = make_engine(make_conf(compact=False))
        with pytest.raises(ValueError, match="lane compaction"):
            checkpoint.load(path, eng3)
        # ...and so must an externally-managed engine even with the
        # knob ON (what BatchSupervisor.run() marks before lineage
        # adoption: supervised rungs run uncompacted, so arming a
        # compactor they would discard = silent lane shuffle)
        eng4 = make_engine(make_conf(compact=True))
        eng4._compact_external = True
        with pytest.raises(ValueError, match="lane compaction"):
            checkpoint.load(path, eng4)


def test_supervised_run_is_uncompacted_and_marked():
    from wasmedge_tpu.batch.supervisor import BatchSupervisor

    conf = make_conf(compact=True)
    conf.supervisor.use_kernel_tier = False
    eng = make_engine(conf)
    ns = div_args()
    res = BatchSupervisor(eng, conf=conf).run("fib", [ns],
                                              max_steps=500_000)
    assert eng._compact_external and eng.compactor is None
    ref = make_engine(make_conf(compact=False)).run(
        "fib", [ns], max_steps=500_000)
    assert_results_identical(res, ref)


# ---------------------------------------------------------------------------
# serving: bindings / recycling / hv / checkpoints / stdout follow lanes
# ---------------------------------------------------------------------------
def _serve_conf(lanes=8, **kw):
    conf = make_conf(compact=True, **kw)
    conf.batch.lanes = lanes
    return conf


def _fib_server(conf, lanes=8, **kw):
    from wasmedge_tpu.serve.server import BatchServer

    inst, store = instantiate(build_fib(), conf)
    return BatchServer(inst, store=store, conf=conf, lanes=lanes, **kw)


def test_serving_bindings_follow_lanes_through_permutation():
    conf = _serve_conf()
    srv = _fib_server(conf)
    ns = [11, 4, 9, 6, 12, 5, 10, 7, 8, 13, 4, 9, 12, 6]
    futs = [(n, srv.submit("fib", [n])) for n in ns]
    srv.run_until_idle()
    assert srv._compactor.stats["fires"] >= 1
    assert srv.engine.compactor is None   # engine-level pass disarmed
    for n, f in futs:
        assert f.result(5)[0] == fib_ref(n)
    c = srv.counters
    assert c["completed"] == len(ns) and c["recycled_lanes"] > 0
    srv.shutdown()


def test_serving_hv_swap_through_permutation():
    conf = _serve_conf(lanes=4)
    conf.hv.max_virtual_lanes = 12
    conf.hv.min_resident_rounds = 1
    srv = _fib_server(conf, lanes=4)
    ns = [10, 5, 9, 6, 11, 7, 8, 12, 4, 9, 10, 6]
    futs = [(n, srv.submit("fib", [n])) for n in ns]
    srv.run_until_idle()
    for n, f in futs:
        assert f.result(5)[0] == fib_ref(n)
    assert srv._compactor.stats["fires"] >= 1
    assert srv.hv.counters["swaps_in"] > 0
    srv.shutdown()


def test_serving_checkpoint_resume_through_permutation():
    with tempfile.TemporaryDirectory(prefix="compact-serve-") as d:
        conf = _serve_conf()
        conf.serve.checkpoint_every_rounds = 2
        srv = _fib_server(conf, checkpoint_dir=d)
        ns = [12, 5, 11, 6, 13, 7, 10, 8, 12, 9, 11, 5]
        futs = {}
        for n in ns:
            f = srv.submit("fib", [n])
            futs[f.request_id] = n
        srv.run_until_idle(max_rounds=6)
        assert srv._compactor.stats["fires"] >= 1
        assert srv._lineage.newest() is not None
        # simulated crash: a fresh server adopts the lineage — the
        # binding journal was remapped under the same lock as every
        # permutation, so adopted ids resolve to THEIR results
        conf2 = _serve_conf()
        conf2.serve.checkpoint_every_rounds = 2
        srv2 = _fib_server(conf2, checkpoint_dir=d, resume=True)
        assert srv2.adopted   # something was in flight at the snapshot
        srv2.run_until_idle()
        for rid, fut in srv2.adopted.items():
            assert fut.result(5)[0] == fib_ref(futs[rid])
        srv2.shutdown()
        srv.shutdown(drain=False)


def test_serving_stdout_exactly_once_through_permutation():
    from wasmedge_tpu.models import build_echo
    from wasmedge_tpu.host.wasi import WasiModule
    from wasmedge_tpu.serve.server import BatchServer

    def run(compact, sink_path):
        conf = _serve_conf(lanes=4)
        conf.batch.compact = compact
        conf.batch.steps_per_launch = 24
        wasi = WasiModule()
        wasi.init_wasi(dirs=[], prog_name="echo")
        sink = os.open(sink_path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC)
        wasi.env.fds[1].os_fd = sink
        mod = Validator(conf).validate(
            Loader(conf).parse_module(build_echo()))
        store = StoreManager()
        ex = Executor(conf)
        ex.register_import_object(store, wasi)
        inst = ex.instantiate(store, mod)
        srv = BatchServer(inst, store=store, conf=conf, lanes=4)
        # VARIED iteration counts: identical args would keep every
        # lane perfectly convergent and the policy would (correctly)
        # never fire.  The message bytes are identical per write, so
        # the on/off byte STREAMS still compare equal regardless of
        # drain interleaving — only the total count is placement-
        # sensitive, and exactly-once pins it below.
        iters = [1, 6, 2, 5, 3, 4, 1, 6, 2, 5]
        futs = [srv.submit("echo", [k]) for k in iters]
        srv.run_until_idle()
        rets = [f.result(5)[0] for f in futs]
        srv.shutdown()
        os.close(sink)
        with open(sink_path, "rb") as f:
            return rets, f.read(), srv, iters

    with tempfile.TemporaryDirectory(prefix="compact-stdout-") as d:
        rets_on, bytes_on, srv_on, iters = run(True, os.path.join(d, "on"))
        rets_off, bytes_off, _, _ = run(False, os.path.join(d, "off"))
    assert srv_on._compactor.stats["fires"] >= 1
    assert rets_on == rets_off
    assert bytes_on == bytes_off and len(bytes_on) > 0
    # exactly-once: 2 fd_writes x 16 bytes per iteration per request,
    # no duplicates or losses through any fired permutation
    assert len(bytes_on) == sum(2 * 16 * k for k in iters)


# ---------------------------------------------------------------------------
# observability: convergence gauges, compact instants, Prometheus
# ---------------------------------------------------------------------------
def test_obs_convergence_and_compaction_metrics():
    from wasmedge_tpu.obs.metrics import (
        parse_prometheus, render_prometheus)

    conf = make_conf(compact=True)
    conf.obs.enabled = True
    eng = make_engine(conf)
    eng.run("fib", [div_args()], max_steps=500_000)
    rec = eng.obs
    assert rec.compactions_total >= 1
    assert rec.convergence["rounds"] >= 1
    assert "compact" in rec.event_names()
    text = render_prometheus(recorder=rec)
    parsed = parse_prometheus(text)   # {(name, labels_frozenset): val}
    names = {k[0] for k in parsed}
    assert "wasmedge_compactions_total" in names
    assert parsed[("wasmedge_compactions_total", frozenset())] >= 1
    assert "wasmedge_convergence_unique_pcs" in names
    assert "wasmedge_convergence_largest_group_fraction" in names
    assert "wasmedge_compaction_latency_seconds_count" in names


def test_obs_off_bit_identical_and_noop_recorder():
    from wasmedge_tpu.obs.recorder import NULL_RECORDER

    NULL_RECORDER.observe_convergence(3, 0.5)   # must be a no-op
    NULL_RECORDER.observe_compaction(0.1)
    conf = make_conf(compact=True)   # obs off
    eng = make_engine(conf)
    ns = div_args()
    res = eng.run("fib", [ns], max_steps=500_000)
    ref = make_engine(make_conf(compact=False)).run(
        "fib", [ns], max_steps=500_000)
    assert_results_identical(res, ref)


# ---------------------------------------------------------------------------
# satellite: divergence-aware fusion pattern selection
# ---------------------------------------------------------------------------
def test_fusion_divergence_bias_off_is_bit_identical_planning():
    from wasmedge_tpu.batch.fuse import plan_fusion
    from wasmedge_tpu.batch.image import build_device_image

    def plan(bias):
        conf = Configure()
        conf.batch.fuse_divergence_bias = bias
        mod = Validator(conf).validate(
            Loader(conf).parse_module(build_fib()))
        img = build_device_image(mod.lowered, mod=mod)
        rep = plan_fusion(img, conf.batch)
        return img, rep

    img0, rep0 = plan(0.0)
    imgd, repd = plan(0.0)
    assert rep0["divergence_bias"] == 0.0
    assert np.array_equal(np.asarray(getattr(img0, "fuse_len", [])),
                          np.asarray(getattr(imgd, "fuse_len", [])))
    # candidates carry divergence + planned-vs-realized delta fields
    for row in rep0["candidates"]:
        assert "divergence" in row
        assert row["delta_runs"] == row["planned"] - row["realized_runs"]
    # bias > 0 still plans valid non-overlapping runs, reports the knob
    imgb, repb = plan(4.0)
    assert repb["divergence_bias"] == 4.0
    for row in repb["candidates"]:
        assert "adjusted_saved_dispatches" in row
    if getattr(imgb, "fuse_len", None) is not None:
        flen = np.asarray(imgb.fuse_len)
        # runs never overlap: inside a run, no other head
        for pc in np.nonzero(flen >= 2)[0]:
            assert (flen[pc + 1:pc + int(flen[pc])] == 0).all()


def test_fusion_report_validates_with_deltas():
    from wasmedge_tpu.analysis import analyze_validated
    from wasmedge_tpu.analysis.report import validate_report
    from wasmedge_tpu.batch.fuse import plan_fusion
    from wasmedge_tpu.batch.image import build_device_image

    conf = Configure()
    mod = Validator(conf).validate(
        Loader(conf).parse_module(build_fib()))
    analysis = analyze_validated(mod)
    doc = analysis.to_dict()
    img = build_device_image(mod.lowered, mod=mod)
    doc["fusion"] = plan_fusion(img, conf.batch, analysis=analysis)
    assert validate_report(doc) == []
