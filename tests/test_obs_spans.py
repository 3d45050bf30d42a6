"""The program's phase spans (`obs.timed`): on the profiler's clock with
the recorder off, on the ring with their parent when it is on, and read
by the benchmark's `trace_program_span` and `trace_span_self` readers.

Everything here runs on the CPU (Pallas in interpret mode) at tiny sizes
and under `jax.profiler` with the Python tracer off, as the benchmark's
traced slice does.
"""

import glob
import importlib.util
import os

import numpy as np
import pytest

from wasmedge_tpu.batch.engine import BatchEngine
from wasmedge_tpu.batch.uniform import UniformBatchEngine
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.obs import (
    NULL_RECORDER,
    chrome_trace,
    recorder_of,
    validate_chrome_trace,
)
from wasmedge_tpu.obs.recorder import SPAN_PREFIX
from wasmedge_tpu.serve import BatchServer
from tests.helpers import instantiate

pytestmark = pytest.mark.obs

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
LANES = 16
ROUNDS = 3

# what crosses the host link (batch/pallas_engine.py HostLink): leaves,
# each inside whichever phase made the crossing
LINK_SPANS = {"batch/d2h", "batch/h2d", "batch/enqueue"}
BATCH_SPANS = {"batch/run", "batch/plan", "batch/group",
               "batch/initial_state", "batch/launch", "batch/sync",
               "batch/statuses", "batch/harvest", "batch/result"} \
    | LINK_SPANS
SERVE_SPANS = {"serve/step_enter", "serve/step_exit", "serve/round",
               "serve/lock_wait", "serve/admit",
               "serve/install", "serve/launch", "serve/enforce",
               "serve/harvest", "serve/park", "simt/chunk"}
# opened only where a block splits in flight: tests/test_split_batch_config.py
SPLIT_SPANS = {"batch/recheck", "batch/split", "batch/install"}
# opened only where a block parks at a host call:
# tests/test_chacha20_wasi_config.py
HOSTCALL_SPANS = {"batch/hostcall_begin", "batch/hostcall_finish"}
# child -> the span it must lie inside
PARENTS = {"batch/plan": "batch/run", "batch/launch": "batch/run",
           "batch/sync": "batch/run", "batch/statuses": "batch/run",
           "batch/result": "batch/run",
           "batch/group": "batch/plan", "batch/initial_state": "batch/plan",
           "batch/harvest": "batch/statuses",
           "batch/d2h": "batch/run", "batch/h2d": "batch/run",
           "batch/enqueue": "batch/run",
           "serve/lock_wait": "serve/round", "serve/admit": "serve/round",
           "serve/launch": "serve/round", "serve/enforce": "serve/round",
           "serve/harvest": "serve/round",
           "serve/install": "serve/admit", "serve/park": "serve/harvest",
           "simt/chunk": "serve/launch"}


def _conf(obs=False, pallas=False):
    conf = Configure()
    conf.batch.steps_per_launch = 256
    conf.batch.value_stack_depth = 128
    conf.batch.call_stack_depth = 64
    conf.batch.interpret = pallas   # opts the Pallas path in on the CPU
    conf.obs.enabled = obs
    return conf


def _batch_job(obs=False):
    conf = _conf(obs, pallas=True)
    conf.batch.steps_per_launch = 10_000
    _ex, store, inst = instantiate(build_fib(), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    assert eng.pallas is not None and eng.pallas.eligible
    res = eng.run("fib", [np.full(LANES, 10, np.int64)],
                  max_steps=500_000)
    assert not eng.fell_back_to_simt
    return eng, res


def _serve_rounds(obs=False):
    """ROUNDS serving rounds: fib(5) answers within the first, fib(12)
    (4879 instructions) is still running after the last."""
    conf = _conf(obs)
    _ex, store, inst = instantiate(build_fib(), conf)
    srv = BatchServer(inst, store=store, conf=conf, lanes=4)
    futs = [srv.submit("fib", [n]) for n in (5, 12, 6)]
    for _ in range(ROUNDS):
        srv.step()
    assert srv.counters["rounds"] == ROUNDS
    assert futs[0].result(0)[0] == 5 and futs[2].result(0)[0] == 8
    return srv, futs


def _profiled(tmp_path, work):
    """Run `work()` under a profiler session as the benchmark's traced
    slice starts one; -> (work's result, {thread line: [(name, start,
    end)]} of the `wasm/` events on the host plane)."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    lines.setdefault(line.name, []).append(
                        (ev.name[len(SPAN_PREFIX):], ev.start_ns,
                         ev.start_ns + ev.duration_ns))
    return out, lines


def _assert_nested(events):
    """Every child lies inside a span of its parent's name, on one
    thread's line."""
    for name, a, b in events:
        parent = PARENTS.get(name)
        if parent is not None:
            assert any(p == parent and pa <= a and b <= pb
                       for p, pa, pb in events), (name, parent)


def test_profiler_trace_holds_the_spans_with_obs_off(tmp_path):
    def work():
        eng, res = _batch_job()
        srv, _futs = _serve_rounds()
        assert eng.obs is NULL_RECORDER and srv.obs is NULL_RECORDER
        return res, eng.pallas

    (res, pallas), lines = _profiled(tmp_path, work)
    (events,) = lines.values()      # all on the calling thread
    names = [name for name, _a, _b in events]
    assert BATCH_SPANS | SERVE_SPANS <= set(names)
    _assert_nested(events)
    assert names.count("serve/round") == ROUNDS
    assert names.count("serve/step_enter") == ROUNDS
    assert names.count("serve/step_exit") == ROUNDS
    assert names.count("batch/run") == names.count("batch/plan") == 1
    assert names.count("batch/group") == 1
    assert names.count("batch/initial_state") == 1
    # every crossing of the link is a span, counted with obs off too:
    # the one pass record at the sync; the argument rows, the globals
    # and ctrl at entry, ctrl after the harvest; one launch of the
    # optimistic kernel and the pack of its record behind it
    assert (names.count("batch/d2h"), names.count("batch/h2d"),
            names.count("batch/enqueue")) == \
        (pallas.d2h_transfers, pallas.h2d_transfers,
         pallas.programs_enqueued) == (1, 6, 2)
    # both locked sections of every round wait for the lock in a span
    assert names.count("serve/lock_wait") == 2 * ROUNDS
    # opened only where the subsystem is configured
    assert not {"serve/hv", "serve/effects", "serve/compact",
                "serve/checkpoint", "batch/residue"} & set(names)
    # (c) a running trace changes no result
    _eng, plain = _batch_job()
    for got, want in zip(res.results, plain.results):
        assert (got == want).all()
    assert (res.trap == plain.trap).all()
    assert (res.retired == plain.retired).all()


def test_a_scheduler_dies_with_its_run_and_not_at_the_next_gc():
    """Nothing the link or a span holds points back at the scheduler: a
    cycle would keep a finished job's planes on the device until the
    collector next runs (on the chip that was gigabytes: a job's planes
    several times over)."""
    import gc
    import weakref

    from wasmedge_tpu.batch.scheduler import BlockScheduler
    from tests.test_scheduler import make_engine

    _ex, _store, _inst, eng = make_engine(build_fib(), lanes=8)
    gc.collect()
    gc.disable()
    try:
        sched = BlockScheduler(eng, "fib", [np.arange(3, 11, dtype=np.int64)],
                               500_000)
        sched.run()                 # seven splits: every kind of crossing
        assert sched.link.d2h_transfers and sched.link.programs_enqueued
        gone = weakref.ref(sched)
        del sched
        assert gone() is None
    finally:
        gc.enable()


def test_drive_thread_spends_no_time_between_rounds_outside_a_span(
        tmp_path):
    """The background driver (`BatchServer.start`): between one
    `serve/round` and the next its thread is in `serve/step_exit`, then
    `serve/drive_wait` (one span however long it waits), then
    `serve/step_enter`, so the gap between rounds has names."""
    def work():
        conf = _conf()
        _ex, store, inst = instantiate(build_fib(), conf)
        srv = BatchServer(inst, store=store, conf=conf, lanes=4)
        srv.start()
        try:
            assert srv.submit("fib", [12]).result(120)[0] == 144
        finally:
            srv.shutdown(timeout_s=60)
        return srv.counters["rounds"]

    rounds, lines = _profiled(tmp_path, work)
    (events,) = [evs for evs in lines.values()
                 if any(name == "serve/round" for name, _a, _b in evs)]
    top = [name for name, _a, _b in sorted(events, key=lambda e: e[1])
           if name in ("serve/round", "serve/step_enter",
                       "serve/step_exit", "serve/drive_wait")]
    assert rounds > 1 and top.count("serve/round") == rounds
    assert top[:3] == ["serve/drive_wait", "serve/step_enter",
                       "serve/round"]
    between = ["serve/step_exit", "serve/drive_wait", "serve/step_enter"]
    at = [i for i, name in enumerate(top) if name == "serve/round"]
    for a, b in zip(at, at[1:]):
        assert top[a + 1:b] == between
    # the last wait ends when shutdown() stops the thread
    assert top[at[-1] + 1:] == ["serve/step_exit", "serve/drive_wait"]


def test_ring_holds_the_spans_with_their_parents():
    eng, _res = _batch_job(obs=True)
    rec = eng.obs
    assert rec is recorder_of(eng.simt.conf) and rec.enabled
    srv, _futs = _serve_rounds(obs=True)
    events = [e for r in (rec, srv.obs) for e in r.events
              if e["ph"] == "X" and "parent" in e["args"]]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert BATCH_SPANS | SERVE_SPANS <= set(by_name)
    for name, evs in by_name.items():
        want = PARENTS.get(name)
        if name == "simt/chunk":    # also the batch engines' own loop
            continue
        if name in LINK_SPANS:      # a leaf of whichever phase crossed
            continue
        assert {e["args"]["parent"] for e in evs} == {want}, name
    assert {(e["args"]["what"], e["args"]["parent"])
            for e in by_name["batch/d2h"]} == {("pass", "batch/sync")}
    assert {(e["args"]["what"], e["args"]["parent"])
            for e in by_name["batch/h2d"]} == {
        ("args_lo", "batch/initial_state"),
        ("args_hi", "batch/initial_state"),
        ("globals_lo", "batch/initial_state"),
        ("globals_hi", "batch/initial_state"),
        ("ctrl", "batch/initial_state"), ("ctrl", "batch/launch")}
    assert [(e["args"]["program"], e["args"]["parent"])
            for e in by_name["batch/enqueue"]] == \
        [("optimistic", "batch/launch"), ("pack", "batch/launch")]
    # a download's size is known when it has come: it rides the ring
    assert all(e["args"]["bytes"] > 0 for n in ("batch/d2h", "batch/h2d")
               for e in by_name[n])
    # a child lands on its parent's track, so the Chrome export nests it
    assert {e["track"] for n in SERVE_SPANS - {"simt/chunk"}
            for e in by_name[n]} == {"serve/phases"}
    # what is known at the end rides the ring event
    assert [e["args"]["admitted"] for e in by_name["serve/admit"]] \
        == [3, 0, 0]
    assert by_name["serve/round"][0]["args"]["round"] == 1
    assert by_name["serve/launch"][0]["args"]["steps"] == 256
    assert sum(e["args"]["harvested"]
               for e in by_name["serve/harvest"]) == 2
    assert by_name["batch/launch"][0]["args"]["blocks"] == 1
    # the ring spans that were there keep their names
    assert "kernel_round" in rec.event_names()
    assert "launch" in srv.obs.event_names()
    for r in (rec, srv.obs):
        assert validate_chrome_trace(chrome_trace(r)) == []


def test_null_recorder_timed_keeps_no_state():
    assert recorder_of(Configure()) is NULL_RECORDER
    span = NULL_RECORDER.timed("x", lanes=3)
    assert not hasattr(span, "__dict__")
    with span as inside:
        inside.set(harvested=2)     # reaches no sink, raises nothing
    assert not vars(NULL_RECORDER)
    assert span is not NULL_RECORDER.timed("x")


def test_simt_step_carries_its_named_scope():
    conf = _conf()
    _ex, store, inst = instantiate(build_fib(), conf)
    eng = BatchEngine(inst, store=store, conf=conf, lanes=4)
    eng._build()
    state = eng.initial_state(eng.export_func_idx("fib"),
                              [np.full(4, 5, np.int64)])
    text = eng._run_chunk.lower(
        state, np.zeros((2, 2), np.int32)).as_text(debug_info=True)
    assert "wasm_simt_step" in text


@pytest.mark.parametrize("careful", [False, True])
def test_pallas_kernels_are_named_apart(careful):
    eng, _res = _batch_job()
    inner = next(iter(eng.simt._sched_cache.values()))
    fn = inner._fn_careful() if careful else inner._fn
    name = "wasm_kernel_careful" if careful else "wasm_kernel_optimistic"
    import jax

    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype)
             for s in inner._arg_specs()]
    assert f"@jit_{name}" in fn.lower(*specs).as_text()


# -- the benchmark's reader ------------------------------------------------
def _bench_module(*parts):
    path = os.path.join(BENCH, *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def hand_built_obs():
    """A slice of 10 s; the device busy 1..2 s and 5..7 s; two
    `wasm/batch/run` spans inside it, one `wasm/serve/round` across its
    end, one `wasm/batch/plan` before it."""
    reduce_trace = _bench_module("reduce_trace")
    spans = [(0.5, 2.5, "wasm/batch/run"), (4.0, 8.0, "wasm/batch/run"),
             (4.5, 7.5, "PjitFunction(add)"),
             (9.0, 12.0, "wasm/serve/round"),
             (-2.0, -1.0, "wasm/batch/plan")]
    host = (np.asarray([s[0] for s in spans]),
            np.asarray([s[1] for s in spans]), [s[2] for s in spans])
    trace = reduce_trace.Trace((0.0, 10.0), [[[1.0, 2.0], [5.0, 7.0]]],
                               {}, {}, {}, host)
    return {"trace": trace, "samples": {},
            "counters": {"trace_jobs": 2, "none": 0}}


@pytest.mark.parametrize("args, want", [
    (dict(span="wasm/batch/run", stat="median"), 3.0),
    (dict(span="wasm/batch/run", stat="median", host_only=True,
          scale=1000.0), 1500.0),
    (dict(span="wasm/batch/run", stat="sum", per="trace_jobs"), 3.0),
    (dict(span="wasm/batch/run", stat="sum", per="trace_jobs",
          host_only=True), 1.5),
    # across the slice's end: the part inside counts in a sum, and the
    # span is no sample of a median
    (dict(span="wasm/serve/round", stat="sum", per="trace_jobs"), 0.5),
    (dict(span="wasm/serve/round", stat="median"), None),
    # nothing to read: outside the slice, no such span, no such counter
    (dict(span="wasm/batch/plan", stat="median"), None),
    (dict(span="wasm/batch/result", stat="median"), None),
    (dict(span="wasm/batch/run", stat="sum", per="none"), None),
    (dict(span="wasm/batch/run", stat="sum", per="absent"), None),
])
def test_trace_program_span_reader(hand_built_obs, args, want):
    reader = _bench_module("readers", "trace_program_span")
    got = reader.read(hand_built_obs, **args)
    assert got == (want if want is None else pytest.approx(want))


def test_trace_program_span_reader_without_a_trace(hand_built_obs):
    reader = _bench_module("readers", "trace_program_span")
    obs = dict(hand_built_obs, trace=None)
    assert reader.read(obs, span="wasm/batch/run", stat="median") is None
    with pytest.raises(ValueError):
        reader.read(hand_built_obs, span="wasm/batch/run", stat="mean")


@pytest.fixture(scope="module")
def hand_built_nest():
    """A slice of 10 s; the device busy 1..2 s and 5..7 s.  A first
    `wasm/batch/run` 0.5..4 s holds a plan that starts with it, and a
    `statuses` that holds a `d2h` under a JAX event of the same length;
    a second one runs across the slice's end with a `d2h` across it too
    and an `h2d` beyond it; one `d2h` lies between the two runs."""
    reduce_trace = _bench_module("reduce_trace")
    spans = [(0.5, 4.0, "wasm/batch/run"), (0.5, 0.7, "wasm/batch/plan"),
             (1.5, 3.5, "wasm/batch/statuses"),
             (1.8, 2.4, "wasm/batch/d2h"),
             (1.8, 2.4, "np.asarray(jax.Array)"),
             (4.5, 4.8, "wasm/batch/d2h"),
             (8.0, 12.0, "wasm/batch/run"), (9.5, 10.5, "wasm/batch/d2h"),
             (10.2, 10.4, "wasm/batch/h2d")]
    host = (np.asarray([s[0] for s in spans]),
            np.asarray([s[1] for s in spans]), [s[2] for s in spans])
    trace = reduce_trace.Trace((0.0, 10.0), [[[1.0, 2.0], [5.0, 7.0]]],
                               {}, {}, {}, host)
    return {"trace": trace, "samples": {},
            "counters": {"trace_jobs": 2, "none": 0}}


# every `wasm/` name of the nest with its idle self time over both runs:
# together the 2.5 s and the 2 s in which the device idles inside them
NEST_SELF_IDLE = {"wasm/batch/run": 0.8 + 1.5, "wasm/batch/plan": 0.2,
                  "wasm/batch/statuses": 1.1, "wasm/batch/d2h": 0.4 + 0.5}


@pytest.mark.parametrize("args, want", [
    # a leaf inside a phase inside a root, the device busy over the
    # leaf's first 0.2 s; the part of the second leaf inside the slice
    (dict(span="wasm/batch/d2h", stat="self_idle"), 0.45),
    (dict(span="wasm/batch/d2h", stat="self_idle", scale=1000.0), 450.0),
    # a phase less its leaf, its first 0.3 s under a busy device
    (dict(span="wasm/batch/statuses", stat="self_idle"), 0.55),
    # of two spans that start together the shorter is the inner one
    (dict(span="wasm/batch/plan", stat="self_idle"), 0.1),
    # the roots' own remainder: what no child span names
    (dict(span="wasm/batch/run", stat="self_idle"), 1.15),
    # the leaf between the runs and the one beyond the slice count
    # nowhere; the one across the slice's end starts inside it
    (dict(span="wasm/batch/d2h", stat="count"), 1.0),
    (dict(span="wasm/batch/h2d", stat="count"), None),
    (dict(span="wasm/batch/h2d", stat="self_idle"), None),
    (dict(span="wasm/batch/result", stat="self_idle"), None),
    # another root: only what lies inside it
    (dict(span="wasm/batch/d2h", stat="self_idle",
          root="wasm/batch/statuses"), 0.2),
    (dict(span="wasm/batch/statuses", stat="self_idle",
          root="wasm/batch/statuses"), 0.55),
    (dict(span="wasm/batch/d2h", stat="count",
          root="wasm/batch/statuses"), 0.5),
    (dict(span="wasm/batch/d2h", stat="self_idle",
          root="wasm/batch/result"), None),
    # no such counter, or nothing counted
    (dict(span="wasm/batch/d2h", stat="count", per="absent"), None),
    (dict(span="wasm/batch/d2h", stat="count", per="none"), None),
])
def test_trace_span_self_reader(hand_built_nest, args, want):
    reader = _bench_module("readers", "trace_span_self")
    got = reader.read(hand_built_nest, **{"per": "trace_jobs", **args})
    assert got == (want if want is None else pytest.approx(want))


def test_trace_span_self_sums_to_the_roots_idle_time(hand_built_nest):
    """What makes the table of self times an account: over all names it
    is the idle time inside the roots, to the last gap."""
    reader = _bench_module("readers", "trace_span_self")
    trace = hand_built_nest["trace"]
    jobs = hand_built_nest["counters"]["trace_jobs"]
    got = {name: reader.read(hand_built_nest, span=name, stat="self_idle",
                             per="trace_jobs") for name in NEST_SELF_IDLE}
    assert got == {name: pytest.approx(s / jobs)
                   for name, s in NEST_SELF_IDLE.items()}
    roots_idle = sum((b - a) - trace.busy_in(a, b)
                     for a, b in ((0.5, 4.0), (8.0, 10.0)))
    assert sum(got.values()) * jobs == pytest.approx(roots_idle) \
        == pytest.approx(4.5)


def test_trace_span_self_reader_refuses_what_does_not_nest(
        hand_built_nest):
    reader = _bench_module("readers", "trace_span_self")
    trace = hand_built_nest["trace"]
    starts, ends, names = trace._host
    overlapping = type(trace)(
        trace.window, trace.busy, {}, {}, {},
        (np.append(starts, 3.0), np.append(ends, 6.0),
         names + ["wasm/batch/run"]))
    args = dict(span="wasm/batch/d2h", stat="self_idle", per="trace_jobs")
    assert reader.read(dict(hand_built_nest, trace=overlapping),
                       **args) is None
    assert reader.read(dict(hand_built_nest, trace=None), **args) is None
    with pytest.raises(ValueError):
        reader.read(hand_built_nest, **dict(args, stat="self"))


def test_span_metrics_name_spans_the_program_opens():
    """Every `program_span` layer metric reads a span of this file's
    lists, so a renamed span cannot leave its metric silent unseen."""
    import json

    known = {SPAN_PREFIX + n
             for n in BATCH_SPANS | SERVE_SPANS | SPLIT_SPANS
             | HOSTCALL_SPANS | {"serve/drive_wait"}}
    seen = {"trace_program_span": 0, "trace_span_self": 0}
    for path in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec["source"] == "program_span":
            assert spec["args"]["span"] in known, spec["name"]
            assert spec["args"].get("root", "wasm/batch/run") in known
            seen[spec["reader"]] += 1
    # PR 26's fifteen and the three of the serve loop's gap; the host's
    # account of a batch job: five self times and three counts; the
    # hostcall serve's two halves and the finish's self time (PR 40)
    assert seen == {"trace_program_span": 20, "trace_span_self": 9}
