"""The configuration `coremark-2k-4096` (EEMBC CoreMark 1.0, the 2K
performance run) on the CPU: its plain reference against CoreMark's own
published CRCs, the guest on the scalar engine against both, the SIMT
engine and the Pallas kernel in interpret mode (the plane behind the HBM
window) against the reference lane for lane, the kernel's count of
`br_table` and `call_indirect` against the scalar engine's, the ctrl row
of every other guest left as it was, and the constants the cell
`batch-coremark-2k` pins."""

import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

from tests.helpers import instantiate
from wasmedge_tpu.models.programs import build_coremark

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "batch-coremark-2k"
PERFORMANCE = (0x0, 0x0, 0x66)
VALIDATION = (0x3415, 0x3415, 0x66)
# core_main.c's known CRCs: seeds -> seedcrc, crclist, crcmatrix, crcstate
# (known_id 3, "2K performance run parameters"; known_id 4, "2K
# validation run parameters")
PUBLISHED = {PERFORMANCE: (0xe9f5, 0xe714, 0x1fd7, 0x8e3a),
             VALIDATION: (0x18f2, 0xe3c1, 0x0747, 0x8d84)}
REHEARSAL = 420     # TOTAL_DATA_SIZE of the rehearsal: every CRC non-zero


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _bench_module(kind, name):
    """A file of benchmark/ as run.py would find it (benchmark/ on the
    path while it loads: a driver imports `harness`)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(BENCH)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "coremark")


def _scalar(wasm, func, args, indirect=False):
    """-> (raw result cells, instructions retired, br_table and
    call_indirect among them) on the scalar engine."""
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.common.opcodes import NAME_TO_ID
    from wasmedge_tpu.common.statistics import Statistics
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf = Configure()  # as the cell's retired_per_lane_made_by says
    conf.statistics.instr_counting = True
    conf.statistics.cost_measuring = indirect
    stat = Statistics(conf)
    stat.cost_table = [0] * len(stat.cost_table)
    for name in ("br_table", "call_indirect"):
        stat.cost_table[NAME_TO_ID[name]] = 1
    ex = Executor(conf, stat)
    store = StoreManager()
    inst = ex.instantiate(store, Validator(conf).validate(
        Loader(conf).parse_module(wasm)))
    cells = ex.invoke_raw(store, inst.find_func(func), list(args))
    return ([int(c) & (2**64 - 1) for c in cells], stat.instr_count,
            stat.total_cost)


def _crcs(packed):
    """(crcfinal, crclist, crcmatrix, crcstate) out of the i64."""
    return tuple((packed >> s) & 0xFFFF for s in (0, 16, 32, 48))


@pytest.mark.parametrize("seeds", [PERFORMANCE, VALIDATION],
                         ids=["2k-performance", "2k-validation"])
def test_the_reference_gives_coremarks_published_crcs(ref, seeds):
    crcfinal, crclist, crcmatrix, crcstate, seedcrc = ref.run(
        1, 2000, *seeds)
    assert (seedcrc, crclist, crcmatrix, crcstate) == PUBLISHED[seeds]
    # one iteration: crcfinal is crclist, what iterate() kept after it
    assert crcfinal == crclist
    assert ref.KNOWN[seedcrc] == PUBLISHED[seeds][1:]


@pytest.mark.parametrize("seeds,retired", [
    (PERFORMANCE, 834415), (VALIDATION, 835139)],
    ids=["2k-performance", "2k-validation"])
def test_the_guest_on_the_scalar_engine_is_coremark(ref, seeds, retired):
    (got,), count, _ = _scalar(build_coremark(2000, *seeds), "coremark", [1])
    assert _crcs(got)[1:] == PUBLISHED[seeds][1:]
    assert got == ref.reference("coremark", [1], total_data_size=2000,
                                seed1=seeds[0], seed2=seeds[1],
                                seed3=seeds[2])[0]
    assert count == retired


@pytest.mark.parametrize("size,iterations", [(REHEARSAL, 1), (666, 3),
                                             (1200, 2)])
def test_the_guest_is_the_reference_at_other_sizes(ref, size, iterations):
    """Smaller blocks: a shorter list, N 4 to 6, the state input cut,
    and crcfinal after more than one iteration."""
    (got,), _count, _ = _scalar(build_coremark(size), "coremark",
                                [iterations])
    assert got == ref.reference("coremark", [iterations],
                                total_data_size=size)[0]
    assert all(_crcs(got))


def test_the_reference_answers_every_lane_once_an_argument(ref):
    calls = []
    run = ref.run

    def counted(*a, **k):
        calls.append(a)
        return run(*a, **k)

    ref.run = counted
    try:
        got = ref.reference_lanes("coremark", [1, 2, 1, 1],
                                  total_data_size=REHEARSAL)
    finally:
        ref.run = run
    assert len(calls) == 2 and got[0] == got[2] == got[3] != got[1]
    with pytest.raises(AssertionError):
        ref.reference("fib", [1])
    with pytest.raises(ValueError):
        build_coremark(65536)


def _engine(conf, size=REHEARSAL, lanes=16):
    from wasmedge_tpu.batch.uniform import UniformBatchEngine

    geometry = _load(BENCH, "configs", "coremark-2k-4096.json")["geometry"]
    for key, value in geometry.items():
        setattr(conf.batch, key, value)
    _ex, store, inst = instantiate(build_coremark(size), conf)
    return UniformBatchEngine(inst, store=store, conf=conf, lanes=lanes)


def test_the_simt_engine_agrees_with_the_reference_on_every_lane(ref):
    from wasmedge_tpu.common.configure import Configure

    conf = Configure()
    conf.batch.use_pallas = False
    eng = _engine(conf)
    assert eng.pallas is None or not eng.pallas.eligible
    res = eng.run("coremark", [np.ones(16, np.int64)], max_steps=10_000_000)
    got = np.asarray(res.results[0]).astype(np.uint64)
    assert np.all(got == np.uint64(ref.reference(
        "coremark", [1], total_data_size=REHEARSAL)[0]))
    assert np.all(np.asarray(res.trap) == -1)
    assert np.all(np.asarray(res.retired) == 81797)


def test_the_pallas_kernel_agrees_and_counts_its_indirect_ops(ref):
    """Interpret mode, the plane behind the HBM window as in the cell:
    all 64 bits of every lane, the scalar engine's count, no split and
    no fall-back, and one br_table or call_indirect counted a lane-block
    step (one block of uniform lanes: the scalar engine's count)."""
    from wasmedge_tpu.common.configure import Configure

    conf = Configure()
    conf.batch.interpret = True
    conf.batch.mem_hbm = True
    conf.obs.enabled = True
    eng = _engine(conf)
    pallas = eng.pallas
    assert pallas is not None and pallas.eligible
    assert pallas.counts_indirect and pallas.ctrl_width == 17
    res = eng.run("coremark", [np.ones(16, np.int64)], max_steps=10_000_000)
    assert not eng.fell_back_to_simt and pallas.splits == 0
    (want,), retired, indirect = _scalar(build_coremark(REHEARSAL),
                                         "coremark", [1], indirect=True)
    assert want == ref.reference("coremark", [1],
                                 total_data_size=REHEARSAL)[0]
    got = np.asarray(res.results[0]).astype(np.uint64)
    assert np.all(got == np.uint64(want))
    assert np.all(np.asarray(res.trap) == -1)
    assert retired == 81797
    assert np.all(np.asarray(res.retired) == retired)
    assert pallas.mem_static["mem_mode"] == "hbm_window"
    assert pallas.indirect_ops == indirect == 265
    assert eng.obs.indirect_ops == indirect
    # the plane the loop-depth rule made, and the dispatches
    # tests/test_block_shapes.py replays on it (38,895 in code order)
    assert pallas.block_shapes == {"kept": 96, "wanted": 177}
    assert pallas.dispatches == 28_504
    from wasmedge_tpu.obs import parse_prometheus, render_prometheus

    (run,) = [e for e in eng.obs.events if e["name"] == "batch/run"]
    assert run["args"]["block_shapes"] == "96/177"
    parsed = parse_prometheus(render_prometheus(recorder=eng.obs))
    assert {dict(labels)["kind"]: v for (name, labels), v in parsed.items()
            if name == "wasmedge_block_shapes"} == {"kept": 96, "wanted": 177}


# A small guest of both classes: n turns of a loop whose br_table picks
# one of three arms by i % 3 (the default past the table for i % 3 == 2)
# and whose arms call one of two functions through the table
_SMALL_WAT = """
(module
  (type $t (func (param i32) (result i32)))
  (table 2 2 funcref)
  (func $inc (type $t) (param $x i32) (result i32)
    (i32.add (local.get $x) (i32.const 1)))
  (func $dbl (type $t) (param $x i32) (result i32)
    (i32.shl (local.get $x) (i32.const 1)))
  (func (export "mix") (param $n i32) (result i32)
    (local $i i32) (local $acc i32)
    (block $done
      (loop $turn
        (br_if $done (i32.ge_u (local.get $i) (local.get $n)))
        (block $two
          (block $one
            (block $zero
              (br_table $zero $one $two
                (i32.rem_u (local.get $i) (i32.const 3))))
            (local.set $acc
              (call_indirect (type $t) (local.get $acc) (i32.const 0)))
            (br $two))
          (local.set $acc
            (call_indirect (type $t) (local.get $acc) (i32.const 1))))
        (local.set $i (i32.add (local.get $i) (i32.const 1)))
        (br $turn)))
    (local.get $acc))
  (elem (i32.const 0) $inc $dbl))
"""


@pytest.mark.parametrize("optimistic", [True, False],
                         ids=["optimistic", "careful"])
def test_the_kernel_counts_what_the_scalar_engine_runs(optimistic):
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.utils.wat import parse_wat

    wasm = parse_wat(_SMALL_WAT)
    (want,), retired, indirect = _scalar(wasm, "mix", [10], indirect=True)
    # ten br_table, and a call_indirect in two turns of three
    assert indirect == 10 + 7
    conf = Configure()
    conf.batch.interpret = True
    conf.batch.optimistic = optimistic
    conf.obs.enabled = True
    _ex, store, inst = instantiate(wasm, conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=8)
    res = eng.run("mix", [np.full(8, 10, np.int64)], max_steps=1_000_000)
    assert not eng.fell_back_to_simt and eng.pallas.splits == 0
    assert np.all(np.asarray(res.results[0]) == want)
    assert np.all(np.asarray(res.retired) == retired)
    assert eng.pallas.indirect_ops == indirect
    assert eng.obs.indirect_ops == indirect


def test_guests_without_either_class_keep_their_ctrl_row():
    """The listed cells' guests hold no br_table and no call_indirect:
    their kernels keep the parent's ctrl width (16, 17 with v128) and
    count nothing; CoreMark's row is one column wider."""
    from wasmedge_tpu.batch.pallas_engine import (
        ctrl_width, holds_indirect, indirect_column)
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.host.wasi import WasiModule
    from wasmedge_tpu.models import build_fib, build_memory_batch
    from wasmedge_tpu.models.programs import (
        build_chacha20, build_chacha20_wasi, build_polybench_gemm)

    assert (ctrl_width(False), ctrl_width(True)) == (16, 17)
    assert (ctrl_width(False, True), ctrl_width(True, True)) == (17, 18)
    assert (indirect_column(False), indirect_column(True)) == (16, 17)
    conf = Configure()
    conf.batch.interpret = True     # a Pallas engine on the CPU; none runs
    for build, width, indirect in (
            (build_fib, 16, False), (build_memory_batch, 16, False),
            (build_polybench_gemm, 16, False),
            (lambda: build_chacha20(1), 17, False),
            (lambda: build_chacha20_wasi(8, 2), 17, False),
            (lambda: build_coremark(REHEARSAL), 17, True)):
        _ex, store, inst = instantiate(build(), conf,
                                       imports=[WasiModule()])
        eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=8)
        assert holds_indirect(eng.pallas.img) is indirect
        assert eng.pallas.counts_indirect is indirect
        assert eng.pallas.ctrl_width == width
        assert eng.pallas.indirect_ops is None


def _driver():
    return _bench_module("drivers", "batch_seeded_indirect")


def test_the_driver_is_the_seeded_one_with_one_more_counter():
    driver = _driver()
    assert driver.seeded.ENGINE_COUNTERS[-1] == "indirect_ops"
    assert driver.seeded.Checker is driver.Checker
    assert driver.seeded.build_engine is driver.build_engine
    spec = _load(BENCH, "workloads", CELL + ".json")["traffic"]["args"]
    assert driver.seeded.lane_args(spec, 4096, 2147483659).tolist() == \
        [20] * 4096


def test_the_drivers_checker_holds_lanes_to_coremarks_crcs(ref):
    driver = _driver()
    workload = _load(BENCH, "workloads", CELL + ".json")
    config = _load(BENCH, "configs", "coremark-2k-4096.json")
    run = types.SimpleNamespace(
        config=config, workload=workload, rehearse=False,
        reference=lambda: ref)
    args = np.full(4, 20, np.int64)
    checker = driver.Checker(run, "coremark", args)
    assert checker.retired == 16075632
    right = np.full(4, 0x8e3a1fd7e7144983, np.uint64)
    assert np.array_equal(checker.expect, right)
    driver.Checker.engine = types.SimpleNamespace(
        pallas=types.SimpleNamespace(splits=0))
    res = types.SimpleNamespace(
        results=[right.astype(np.int64)], trap=np.full(4, -1),
        retired=np.full(4, 16075632))
    assert checker.bad_lanes(res) == (0, 4 * 16075632, 16075632)
    res.results[0] = res.results[0] ^ np.array([0, 1 << 40, 0, 0], np.int64)
    res.trap[2] = 5
    assert checker.bad_lanes(res)[0] == 2
    driver.Checker.engine.pallas.splits = 1    # a split fails every lane
    assert checker.bad_lanes(res)[0] == 4
    # a reference that misses the published CRCs is refused before a lane
    workload["expected"]["crcmatrix"] = "0x1fd6"
    with pytest.raises(RuntimeError, match="published CRCs"):
        driver.Checker(run, "coremark", args)
    # a rehearsal holds lanes to the rehearsal's own count
    config.update(config["rehearse"])
    run.rehearse = True
    checker = driver.Checker(run, "coremark", np.ones(4, np.int64))
    assert checker.retired == workload["rehearse"]["retired_per_lane"]


def test_the_cell_pins_the_parameters_and_the_counts(ref):
    config = _load(BENCH, "configs", "coremark-2k-4096.json")
    workload = _load(BENCH, "workloads", CELL + ".json")
    assert config["guest"] == {
        "builder": "build_coremark", "export": "coremark",
        "args": {"total_data_size": 2000, "seed1": 0, "seed2": 0,
                 "seed3": 0x66}}
    assert build_coremark() == build_coremark(**config["guest"]["args"])
    assert config["driver"] == "batch_seeded_indirect"
    assert config["family"] == "batch" and config["reference"] == "coremark"
    assert config["lanes"] == 4096 and config["chips"] == 1
    assert config["reduced"] == [] and config["architecture"] is None
    assert workload["traffic"] == {
        "func": "coremark", "args": {"kind": "uniform", "value": 20},
        "max_steps": 2000000000, "trace_jobs": 3}
    expected = workload["expected"]
    assert (expected["crclist"], expected["crcmatrix"],
            expected["crcstate"]) == ("0xe714", "0x1fd7", "0x8e3a")
    crcs = _crcs(ref.reference("coremark", [20])[0])
    assert crcs == tuple(int(expected[k], 16) for k in (
        "crcfinal", "crclist", "crcmatrix", "crcstate"))
    (got,), retired, indirect = _scalar(build_coremark(), "coremark", [20],
                                        indirect=True)
    assert _crcs(got) == crcs
    assert retired == expected["retired_per_lane"] == 16075632
    assert indirect == expected["indirect_ops_per_lane"] == 98106
    rehearse = config["rehearse"]
    assert rehearse["guest"]["args"]["total_data_size"] == REHEARSAL
    assert rehearse["lanes"] == 16 and rehearse["geometry"]["mem_hbm"]
    assert workload["rehearse"]["args"] == {"kind": "uniform", "value": 1}
    assert workload["rehearse"]["retired_per_lane"] == 81797
    manifest = _load(ROOT, "BENCHMARK.json")
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == config["name"]
    (metric,) = [m for m in manifest["per_layer"]
                 if m["name"] == "indirect_ops_per_job.batch"]
    assert metric["workloads"] == [CELL]
