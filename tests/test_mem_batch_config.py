"""The deployment `mem-batch-4096` (benchmark/configs/mem-batch-4096.json)
at small sizes on the CPU: its plain reference against the scalar engine
and against the Pallas kernel (interpret mode) with the memory plane
resident and behind the HBM window, the guest's zero-argument builder,
the window's DMA counters against the count its policy gives for a
sweep, and what the `wasm/batch/run` span says of the kernel's memory.
"""

import importlib.util
import os

import numpy as np
import pytest

from tests.helpers import instantiate, run_wasm
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.models import (build_fib, build_memory_batch,
                                 build_memory_workload)

LANES = 16
ROWS = 128      # PallasUniformEngine.HBM_WINDOW_ROWS
# (words, passes): every n crosses a window border (n > 128); 129 takes
# two windows a sweep, 300 three, 520 five
SIZES = [(129, 3), (300, 2), (520, 3)]
# how a loaded word joins the checksum: the configuration's guest adds,
# build_memory_workload's default xors (0 at every even pass count)
FOLDS = ["add", "xor"]


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "references",
        "mem_checksum.py")
    spec = importlib.util.spec_from_file_location("ref_mem_checksum", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _retired(n, passes):
    """The scalar engine's instruction count: 20 a stored word, 16 a
    loaded one, 19 a pass around them, 3 around the passes."""
    return passes * (36 * n + 19) + 3


def _engine(wasm, mem_hbm, obs=False):
    from wasmedge_tpu.batch.uniform import UniformBatchEngine

    conf = Configure()
    conf.batch.steps_per_launch = 50_000_000
    conf.batch.value_stack_depth = 128
    conf.batch.call_stack_depth = 64
    conf.batch.interpret = True
    conf.batch.mem_hbm = mem_hbm
    conf.obs.enabled = obs
    _ex, store, inst = instantiate(wasm, conf)
    return UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)


def _window_dmas(n, passes):
    """(fills, write-backs) of mem_checksum(n) behind the window, from
    the policy in _opt_window.  An i32 access at word u wants rows u
    and u + 1 resident, so a sweep from word 0 misses at u = 0 and then
    at u = 127, 247, ...: window k starts at row 120 k, aligned down to
    8, and overlaps window k - 1 by 8 rows, which evicts that one (a
    row lives in one way at most).  So a sweep of n > 127 words fills
    K = 1 + (n - 8) // 120 windows, the first touch at word 0 among
    them, and a pass is a store sweep and a load sweep.  Every window
    of a store sweep is written back once, dirty, when the next window
    evicts it (the last one by the load sweep's first or second fill),
    and the load sweep leaves nothing dirty, so the final flush at exit
    writes nothing.  One write-back more: the first commit, 512 steps
    into the run, publishes window 0 while the first store sweep is
    still writing it.  No later commit falls due: each dirty eviction
    is a commit point, and these runs stay under SNAP_STEPS."""
    assert n > ROWS - 1 and _retired(n, passes) < 131072
    k = 1 + (n - 8) // 120
    return 2 * passes * k, passes * k + 1


@pytest.mark.parametrize("n,passes", SIZES)
@pytest.mark.parametrize("fold", FOLDS)
def test_reference_matches_the_scalar_engine(fold, n, passes):
    got = run_wasm(build_memory_workload(passes=passes, fold=fold),
                   "mem_checksum", [n])[0]
    assert int(got) & 0xFFFFFFFF == _reference().mem_checksum(
        n, passes, fold)


def test_reference_follows_the_configurations_guest():
    ref = _reference()
    assert ref.reference("mem_checksum", [130]) == [
        int(run_wasm(build_memory_batch(), "mem_checksum", [130])[0])
        & 0xFFFFFFFF]
    assert (ref.PASSES, ref.FOLD) == (64, "add")
    with pytest.raises(KeyError):
        ref.reference("fib", [3])


def test_batch_builder_is_the_64_pass_guest_that_adds():
    assert build_memory_batch() == build_memory_workload(passes=64,
                                                         fold="add")
    assert build_memory_batch() != build_memory_workload(passes=63,
                                                         fold="add")
    # one opcode apart from the xor-folding build, same length
    xor = build_memory_workload(passes=64)
    assert len(xor) == len(build_memory_batch())
    assert sum(a != b for a, b in zip(xor, build_memory_batch())) == 1


@pytest.mark.parametrize("n", [2, 130, 8192])
def test_the_configurations_answer_depends_on_what_memory_held(n):
    """What the Checker compares with: under xor 64 passes cancel to 0
    for every n, so a memory that read zeros would pass; under add the
    reference differs from what each broken memory would give."""
    ref = _reference()
    assert ref.mem_checksum(n, 64, "xor") == 0
    want = ref.mem_checksum(n)
    words = np.arange(n, dtype=np.uint32) * np.uint32(0x9E3779B1)

    def summed(stored):     # the load sweeps over what the plane held
        return sum(int(w.sum(dtype=np.uint64)) for w in stored) % 2**32

    zeros = summed([np.zeros(n, np.uint32)] * 64)
    # write-backs lost by a whole pass: the load sweep of pass p reads
    # what pass p + 1 stored, and the first one the fresh plane
    stale = summed([np.zeros(n, np.uint32)]
                   + [words ^ np.uint32(p) for p in range(63, 0, -1)])
    # one store dropped in one pass: word n - 1 keeps the pass before's
    dropped = (want - int(words[-1] ^ np.uint32(10))
               + int(words[-1] ^ np.uint32(11))) % 2**32
    assert len({want, zeros, stale, dropped}) == 4


@pytest.mark.parametrize("n,passes", SIZES)
@pytest.mark.parametrize("mem_hbm", [False, True])
def test_pallas_kernel_matches_the_reference(mem_hbm, n, passes):
    eng = _engine(build_memory_workload(passes=passes, fold="add"),
                  mem_hbm)
    res = eng.run("mem_checksum", [np.full(LANES, n, np.int64)],
                  max_steps=10_000_000)
    assert eng.pallas._mem_mode() is mem_hbm
    assert not eng.fell_back_to_simt and eng.pallas.splits == 0
    assert (np.asarray(res.trap) == -1).all()
    got = np.asarray(res.results[0]).astype(np.uint64) & np.uint64(0xFFFFFFFF)
    assert (got == _reference().mem_checksum(n, passes, "add")).all()
    assert (np.asarray(res.retired) == _retired(n, passes)).all()
    # the window's counters count the window's DMAs and nothing else
    want = _window_dmas(n, passes) if mem_hbm else (0, 0)
    assert (eng.pallas.window_fills, eng.pallas.window_writebacks) == want
    # and one access for every word a sweep stores or loads
    accesses = 2 * n * passes if mem_hbm else 0
    assert eng.pallas.window_accesses == accesses
    assert eng.pallas.window_hit_share == (
        1 - want[0] / accesses if mem_hbm else None)


def test_window_counters_reach_metrics_and_the_run_span():
    from wasmedge_tpu.obs import parse_prometheus, render_prometheus

    n, passes = SIZES[0]
    eng = _engine(build_memory_workload(passes=passes), True, obs=True)

    def exported():
        parsed = parse_prometheus(render_prometheus(recorder=eng.obs))
        return {name: (dict(labels), v) for (name, labels), v
                in parsed.items() if "window" in name or "memory" in name}

    assert exported() == {}     # nothing before a kernel exists
    args = [np.full(LANES, n, np.int64)]
    fills, wbs = _window_dmas(n, passes)
    for jobs in (1, 2):         # the counters rise by a job's count
        eng.run("mem_checksum", args, max_steps=10_000_000)
        assert exported() == {
            "wasmedge_memory_lane_block": (
                {"mem_mode": "hbm_window", "window": "128x2"}, LANES),
            "wasmedge_hbm_window_fills_total": ({}, jobs * fills),
            "wasmedge_hbm_window_writebacks_total": ({}, jobs * wbs),
            "wasmedge_hbm_window_accesses_total": (
                {}, jobs * 2 * n * passes)}
        assert (eng.pallas.window_fills,
                eng.pallas.window_writebacks) == (fills, wbs)
    runs = [e["args"] for e in eng.obs.events if e["name"] == "batch/run"]
    assert len(runs) == 2
    for a in runs:
        assert (a["mem_mode"], a["lane_block"], a["window"]) == (
            "hbm_window", LANES, "128x2")
        assert a["window_hit_share"] == round(
            1 - fills / (2 * n * passes), 6)


def test_run_span_of_a_resident_memory_and_of_no_memory():
    from wasmedge_tpu.obs import parse_prometheus, render_prometheus

    eng = _engine(build_memory_workload(), False, obs=True)
    eng.run("mem_checksum", [np.full(LANES, 40, np.int64)],
            max_steps=1_000_000)
    (a,) = [e["args"] for e in eng.obs.events if e["name"] == "batch/run"]
    assert (a["mem_mode"], a["lane_block"]) == ("resident", LANES)
    assert "window" not in a
    parsed = parse_prometheus(render_prometheus(recorder=eng.obs))
    assert [(dict(labels), v) for (name, labels), v in parsed.items()
            if name == "wasmedge_memory_lane_block"] == [
        ({"mem_mode": "resident"}, LANES)]
    assert not any("hbm_window" in name for (name, _labels) in parsed)

    fib = _engine(build_fib(), None, obs=True)
    res = fib.run("fib", [np.full(LANES, 10, np.int64)], max_steps=500_000)
    assert (np.asarray(res.results[0]) == 55).all()
    (a,) = [e["args"] for e in fib.obs.events if e["name"] == "batch/run"]
    assert a["mem_mode"] == "none"
    assert "lane_block" not in a and "window" not in a
    # a guest without a memory exports neither the gauge nor counters
    parsed = parse_prometheus(render_prometheus(recorder=fib.obs))
    assert not any("memory" in name or "window" in name
                   for (name, _labels) in parsed)


def test_window_counters_outlive_the_kernel_that_counted():
    """The counters are the recorder's, so a later kernel built without
    the window leaves them in /metrics; the gauge follows the newest
    kernel, and the recorder holds the engine's own record of it."""
    from wasmedge_tpu.obs import (FlightRecorder, parse_prometheus,
                                  render_prometheus)

    rec = FlightRecorder()
    static = {"mem_mode": "hbm_window", "lane_block": 4096,
              "window": "128x2"}
    rec.set_memory_static(static)
    assert rec.memory_static is static
    rec.add_window_counts(8832, 4480, 1048576)
    rec.set_memory_static({"mem_mode": "none"})
    rec.add_window_counts(0, 0, 0)
    parsed = parse_prometheus(render_prometheus(recorder=rec))
    assert {name: v for (name, _l), v in parsed.items()
            if "memory" in name or "window" in name} == {
        "wasmedge_hbm_window_fills_total": 8832,
        "wasmedge_hbm_window_writebacks_total": 4480,
        "wasmedge_hbm_window_accesses_total": 1048576}
