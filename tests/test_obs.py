"""Batch observability subsystem (wasmedge_tpu/obs/): flight recorder,
Chrome trace export, Prometheus metrics, device opcode histogram, and
cross-process resume.

ISSUE 3 acceptance, pinned here:
  - obs-DISABLED runs produce bit-identical results to the seed engines
    (guard-object pattern: no recorder, no behavior change),
  - trace export is deterministic under testing/faults.py seeds (same
    seed => same event sequence modulo timestamps),
  - the Chrome trace validates against the trace_event schema,
  - Prometheus output parses and includes every failure class,
  - the Supervisor adopts an existing checkpoint_dir lineage at startup
    (--resume), recording corrupt members as FailureRecord("checkpoint").

Fast by construction (tiny lane counts, short chunks): tier-1 budget.
"""

import io
import json
import os

import numpy as np
import pytest

from wasmedge_tpu.batch.engine import BatchEngine
from wasmedge_tpu.batch.supervisor import BatchSupervisor
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.statistics import FailureRecord, Statistics
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.obs import (
    NULL_RECORDER,
    FlightRecorder,
    chrome_trace,
    parse_prometheus,
    recorder_of,
    render_prometheus,
    validate_chrome_trace,
)
from wasmedge_tpu.testing.faults import (
    Fault,
    FaultInjector,
    corrupt_checkpoint,
)
from tests.helpers import instantiate

pytestmark = pytest.mark.obs

LANES = 16

ALL_FAULT_CLASSES = ("launch", "serve", "checkpoint", "poison_lane",
                     "runaway", "demote", "scalar_rerun")


def make_conf(obs=False, **kw):
    conf = Configure()
    conf.batch.steps_per_launch = 100
    conf.batch.rng_seed = 7
    conf.supervisor.backoff_base_s = 0.0
    conf.supervisor.checkpoint_every_steps = 200
    conf.obs.enabled = obs
    for k, v in kw.items():
        setattr(conf.obs, k, v)
    return conf


def make_engine(data, conf, lanes=LANES):
    ex, store, inst = instantiate(data, conf)
    return BatchEngine(inst, store=store, conf=conf, lanes=lanes)


def echo_engine(conf, lanes=LANES, iters=2):
    """fd_write echo module, tier 0 off so calls hit the tier-1 drain."""
    from wasmedge_tpu.models import build_echo

    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.host.wasi import WasiModule
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf.batch.tier0_hostcalls = False
    wasi = WasiModule()
    wasi.init_wasi(dirs=[], prog_name="echo")
    sink = os.open(os.devnull, os.O_WRONLY)
    wasi.env.fds[1].os_fd = sink
    mod = Validator(conf).validate(
        Loader(conf).parse_module(build_echo()))
    store = StoreManager()
    ex = Executor(conf)
    ex.register_import_object(store, wasi)
    inst = ex.instantiate(store, mod)
    eng = BatchEngine(inst, store=store, conf=conf, lanes=lanes)
    return eng, np.full(lanes, iters, np.int64)


def assert_results_identical(a, b):
    for ra, rb in zip(a.results, b.results):
        assert (ra == rb).all()
    assert (a.trap == b.trap).all()
    assert (a.retired == b.retired).all()


# ---------------------------------------------------------------------------
# guard object / zero-overhead contract
# ---------------------------------------------------------------------------
def test_disabled_obs_is_null_recorder():
    eng = make_engine(build_fib(), make_conf(obs=False))
    assert eng.obs is NULL_RECORDER
    assert not eng.obs.enabled
    # the guard object records nothing, ever
    eng.obs.instant("x")
    eng.obs.counter("y", 1)
    with eng.obs.timed("z"):
        pass


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("pallas", [False, True])
def test_obs_enabled_output_bit_identical_to_disabled(pallas, traced,
                                                      tmp_path):
    """The recorder must observe, never perturb: identical BatchResults
    with obs on and off (the seed-engine bit-identical contract), on
    the SIMT engine and on the Pallas batch path, and with a profiler
    session running, which makes every obs.timed() span a live
    TraceAnnotation."""
    import jax

    # the Pallas path in interpret mode pays seconds per argument
    # group: two groups there, eleven on the SIMT engine
    args = [(np.arange(LANES) % (2 if pallas else 11) + 8 * pallas)
            .astype(np.int64)]

    def run(obs, **kw):
        conf = make_conf(obs=obs, **kw)
        if not pallas:
            eng = make_engine(build_fib(), conf)
        else:
            from wasmedge_tpu.batch.uniform import UniformBatchEngine

            conf.batch.interpret = True
            conf.batch.steps_per_launch = 10_000
            _ex, store, inst = instantiate(build_fib(), conf)
            eng = UniformBatchEngine(inst, store=store, conf=conf,
                                     lanes=LANES)
            assert eng.pallas is not None
        return eng.run("fib", args, max_steps=500_000)

    r_off = run(False)
    if traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        r_on = run(True, opcode_histogram=True)
        r_off_traced = run(False) if traced else r_off
    finally:
        if traced:
            jax.profiler.stop_trace()
    assert_results_identical(r_off, r_on)
    assert_results_identical(r_off, r_off_traced)


def test_shared_recorder_identity_across_deepcopy():
    import copy

    conf = make_conf(obs=True)
    rec = recorder_of(conf)
    assert recorder_of(copy.deepcopy(conf)) is rec


# ---------------------------------------------------------------------------
# launch spans, occupancy, retired deltas
# ---------------------------------------------------------------------------
def test_launch_spans_and_occupancy_counters():
    eng = make_engine(build_fib(), make_conf(obs=True))
    res = eng.run("fib", [np.full(LANES, 12, np.int64)],
                  max_steps=500_000)
    assert res.completed.all()
    rec = eng.obs
    launches = [e for e in rec.events if e["name"] == "launch"]
    assert launches, "no per-launch spans recorded"
    for e in launches:
        assert e["ph"] == "X" and e["dur"] >= 0
        assert "live_lanes" in e["args"]
    # retired deltas across launch spans sum to the run's total retired
    assert sum(e["args"]["retired_delta"] for e in launches) \
        == int(np.asarray(res.retired, np.int64).sum())
    assert any(e["name"] == "live_lanes" and e["ph"] == "C"
               for e in rec.events)


def test_hostcall_drain_latency_histogram():
    eng, args = echo_engine(make_conf(obs=True))
    res = eng.run("echo", [args], max_steps=1_000_000)
    assert res.completed.all()
    rec = eng.obs
    assert "fd_write" in rec.hostcalls
    h = rec.hostcalls["fd_write"]
    assert h.count > 0 and h.lanes > 0 and h.sum_s >= 0
    # cumulative buckets are monotone and end at the observation count
    cum = h.cumulative()
    assert all(b >= a for (_, a), (_, b) in zip(cum, cum[1:]))
    assert any(e["name"] == "serve" for e in rec.events)
    assert any(e["name"] == "hostcall_queue_depth" for e in rec.events)


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------
def test_trace_export_valid_schema(tmp_path):
    eng, args = echo_engine(make_conf(obs=True))
    eng.run("echo", [args], max_steps=1_000_000)
    from wasmedge_tpu.obs import export_chrome_trace

    path = tmp_path / "trace.json"
    export_chrome_trace(eng.obs, str(path))
    obj = json.loads(path.read_text())
    assert validate_chrome_trace(obj) == []
    names = {e["name"] for e in obj["traceEvents"]}
    assert {"launch", "serve", "live_lanes", "process_name",
            "thread_name"} <= names
    # spans carry microsecond timestamps and durations
    x = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert x and all("dur" in e for e in x)


def test_trace_deterministic_under_seeded_faults(tmp_path):
    """Same fault schedule => same event sequence (modulo timestamps)."""
    def one_run(sub):
        conf = make_conf(obs=True)
        inj = FaultInjector([Fault(point="launch", at=2)])
        sup = BatchSupervisor(make_engine(build_fib(), conf), conf=conf,
                              faults=inj,
                              checkpoint_dir=str(tmp_path / sub))
        res = sup.run("fib", [(np.arange(LANES) % 9).astype(np.int64)],
                      max_steps=500_000)
        assert res.completed.all() and inj.fired == 1
        return sup.obs.event_names()

    assert one_run("a") == one_run("b")


def test_validator_rejects_malformed_trace():
    assert validate_chrome_trace([]) == ["top level is not an object"]
    assert validate_chrome_trace({}) == ["missing traceEvents array"]
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1,
                            "ts": 0.0}]}  # X without dur
    assert validate_chrome_trace(bad)


# ---------------------------------------------------------------------------
# prometheus export
# ---------------------------------------------------------------------------
def test_prometheus_includes_all_failure_classes():
    rec = FlightRecorder()
    stats = Statistics()
    for fc in ALL_FAULT_CLASSES:
        r = FailureRecord(fault_class=fc).stamp()
        rec.failure(r)
        stats.add_failure(r)
    text = render_prometheus(recorder=rec, stats=stats)
    parsed = parse_prometheus(text)
    for fc in ALL_FAULT_CLASSES:
        key = ("wasmedge_failures_total",
               frozenset({("fault_class", fc)}))
        # the SAME record is mirrored into recorder and stats: the
        # export must count each incident once, not per source
        assert parsed[key] == 1.0, (fc, parsed.get(key))
    # a class only one source observed still shows up
    stats.add_failure(FailureRecord(fault_class="launch").stamp())
    only = FlightRecorder()
    parsed = parse_prometheus(render_prometheus(recorder=only,
                                                stats=stats))
    assert parsed[("wasmedge_failures_total",
                   frozenset({("fault_class", "launch")}))] == 2.0


def test_prometheus_snapshot_parses_end_to_end():
    eng, args = echo_engine(make_conf(obs=True))
    eng.run("echo", [args], max_steps=1_000_000)
    text = render_prometheus(recorder=eng.obs, stats=Statistics(),
                             hostcall_stats=eng.hostcall_stats)
    parsed = parse_prometheus(text)
    name = "wasmedge_hostcall_drain_latency_seconds"
    cnt = parsed[(f"{name}_count", frozenset({("kind", "fd_write")}))]
    inf = parsed[(f"{name}_bucket",
                  frozenset({("kind", "fd_write"), ("le", "+Inf")}))]
    assert cnt == inf > 0
    assert (f"{name}_sum", frozenset({("kind", "fd_write")})) in parsed
    assert parsed[("wasmedge_hostcall_pipeline_total",
                   frozenset({("counter", "tier1_calls")}))] > 0


# ---------------------------------------------------------------------------
# device opcode histogram plane
# ---------------------------------------------------------------------------
def test_opcode_histogram_counts_match_retired():
    conf = make_conf(obs=True, opcode_histogram=True)
    eng = make_engine(build_fib(), conf)
    res = eng.run("fib", [np.full(LANES, 10, np.int64)],
                  max_steps=500_000)
    assert res.completed.all()
    counts = eng.obs.opcode_counts
    assert counts is not None
    assert int(counts.sum()) == int(np.asarray(res.retired,
                                               np.int64).sum())
    # fold into Statistics cost_table accounting
    stats = Statistics()
    stats.add_opcode_counts(counts)
    dump = stats.dump()
    assert sum(dump["opcode_counts"].values()) == int(counts.sum())
    assert dump["opcode_cost"] == int(counts.sum())  # flat-1 table


# ---------------------------------------------------------------------------
# supervisor events + failure mirroring
# ---------------------------------------------------------------------------
def test_supervisor_mirrors_failures_and_tiers(tmp_path):
    conf = make_conf(obs=True)
    inj = FaultInjector([Fault(point="launch", at=1)])
    sup = BatchSupervisor(make_engine(build_fib(), conf), conf=conf,
                          faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", [np.full(LANES, 20, np.int64)],
                  max_steps=500_000)
    assert res.completed.all()
    names = sup.obs.event_names()
    assert "failure/launch" in names
    assert "retry" in names
    assert "tier/simt" in names
    assert sup.obs.failure_counts.get("launch") == 1
    assert sup.obs.tier_seconds.get("simt", 0) > 0


def test_failure_record_monotonic_stamp():
    rec = FailureRecord(fault_class="launch").stamp()
    assert rec.time_s > 0 and rec.mono_s > 0
    # idempotent: a second stamp never rewrites the clocks
    t, m = rec.time_s, rec.mono_s
    rec.stamp()
    assert rec.time_s == t and rec.mono_s == m


# ---------------------------------------------------------------------------
# cross-process resume
# ---------------------------------------------------------------------------
def _interrupted_then_resume(tmp_path, corrupt_newest=False):
    """Process 1 runs out of budget mid-run (leaving its lineage);
    process 2 adopts the dir and completes."""
    args = [(np.arange(LANES) % 7 + 10).astype(np.int64)]
    d = str(tmp_path / "lineage")

    ref = BatchSupervisor(make_engine(build_fib(), make_conf()),
                          checkpoint_dir=str(tmp_path / "ref"))
    rres = ref.run("fib", args, max_steps=500_000)
    assert rres.completed.all()

    sup1 = BatchSupervisor(make_engine(build_fib(), make_conf()),
                           checkpoint_dir=d)
    r1 = sup1.run("fib", args, max_steps=600)  # "crash": budget cut
    assert not r1.completed.all()
    members = sorted(f for f in os.listdir(d) if f.endswith(".npz"))
    assert members, "interrupted run left no lineage to adopt"
    if corrupt_newest:
        corrupt_checkpoint(os.path.join(d, members[-1]))

    conf2 = make_conf(obs=True)
    sup2 = BatchSupervisor(make_engine(build_fib(), conf2), conf=conf2,
                           checkpoint_dir=d, resume=True)
    r2 = sup2.run("fib", args, max_steps=500_000)
    return rres, r2, sup2, members


def test_resume_adopts_existing_lineage(tmp_path):
    rres, r2, sup2, _ = _interrupted_then_resume(tmp_path)
    assert sup2._resumed
    assert r2.completed.all()
    assert_results_identical(rres, r2)
    assert "resume_adopted" in sup2.obs.event_names()
    assert not [f for f in sup2.failures
                if f.fault_class == "checkpoint"]


def test_resume_skips_corrupt_newest_member(tmp_path):
    rres, r2, sup2, members = _interrupted_then_resume(
        tmp_path, corrupt_newest=True)
    assert r2.completed.all()
    assert_results_identical(rres, r2)
    recs = [f for f in sup2.failures if f.fault_class == "checkpoint"]
    assert len(recs) == 1 and members[-1] in recs[0].checkpoint
    if len(members) > 1:
        assert sup2._resumed  # older good member adopted


def test_reused_supervisor_second_run_starts_fresh(tmp_path):
    """A second run() on the same supervisor must NOT restore the first
    run's leftover checkpoint lineage (only --resume adopts state)."""
    conf = make_conf()
    conf.supervisor.checkpoint_every_steps = 100
    sup = BatchSupervisor(make_engine(build_fib(), conf), conf=conf,
                          checkpoint_dir=str(tmp_path))
    r1 = sup.run("fib", [np.full(LANES, 15, np.int64)],
                 max_steps=500_000)
    assert r1.completed.all() and sup._lineage  # lineage left behind
    r2 = sup.run("fib", [np.full(LANES, 6, np.int64)],
                 max_steps=500_000)
    assert r2.completed.all()
    assert (r2.results[0] == 8).all()  # fib(6), not run 1's state


def test_resume_refuses_different_invocation(tmp_path):
    """A lineage taken for f(args A) must not answer f(args B): the
    invocation fingerprint in the checkpoint metadata is checked at
    adoption, mismatched members are recorded and skipped."""
    d = str(tmp_path / "lineage")
    args_a = [np.full(LANES, 12, np.int64)]
    sup1 = BatchSupervisor(make_engine(build_fib(), make_conf()),
                           checkpoint_dir=d)
    sup1.run("fib", args_a, max_steps=600)  # interrupted, lineage left
    assert os.listdir(d)

    conf2 = make_conf()
    sup2 = BatchSupervisor(make_engine(build_fib(), conf2), conf=conf2,
                           checkpoint_dir=d, resume=True)
    args_b = [np.full(LANES, 6, np.int64)]
    r2 = sup2.run("fib", args_b, max_steps=500_000)
    assert not sup2._resumed  # every member is for args A: all refused
    assert r2.completed.all() and (r2.results[0] == 8).all()  # fib(6)
    recs = [f for f in sup2.failures if f.fault_class == "checkpoint"]
    assert recs and all("invocation" in f.error for f in recs)


def test_resume_with_empty_dir_starts_fresh(tmp_path):
    conf = make_conf()
    sup = BatchSupervisor(make_engine(build_fib(), conf), conf=conf,
                          checkpoint_dir=str(tmp_path), resume=True)
    res = sup.run("fib", [np.full(LANES, 9, np.int64)],
                  max_steps=500_000)
    assert not sup._resumed
    assert res.completed.all()


# ---------------------------------------------------------------------------
# VM + CLI plumbing
# ---------------------------------------------------------------------------
def test_vm_execute_batch_exports_trace_and_metrics(tmp_path):
    from wasmedge_tpu.vm import VM

    trace_path = tmp_path / "run.trace.json"
    metrics_path = tmp_path / "run.prom"
    conf = Configure()
    conf.batch.steps_per_launch = 100
    vm = VM(conf)
    vm.load_wasm(build_fib()).validate().instantiate()
    res = vm.execute_batch("fib", [np.full(8, 10, np.int64)], lanes=8,
                           trace_out=str(trace_path),
                           metrics_out=str(metrics_path))
    assert res.completed.all()
    obj = json.loads(trace_path.read_text())
    assert validate_chrome_trace(obj) == []
    parsed = parse_prometheus(metrics_path.read_text())
    assert ("wasmedge_obs_events_total", frozenset()) in parsed


def test_export_to_filelike():
    from wasmedge_tpu.obs import export_chrome_trace, export_prometheus

    rec = FlightRecorder()
    rec.instant("x", cat="test")
    buf = io.StringIO()
    export_chrome_trace(rec, buf)
    assert validate_chrome_trace(json.loads(buf.getvalue())) == []
    buf2 = io.StringIO()
    export_prometheus(buf2, recorder=rec)
    assert parse_prometheus(buf2.getvalue())


def test_ring_bounded_with_drop_count():
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.instant(f"e{i}")
    assert len(rec.events) == 8
    assert rec.dropped == 12
    assert rec.event_names()[0] == "e12"  # oldest dropped first
