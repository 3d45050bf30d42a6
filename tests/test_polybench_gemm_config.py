"""The configuration `polybench-gemm-4096` (PolyBench/C gemm, binary64)
on the CPU: its plain reference against the scalar engine bit for bit,
the Pallas kernel in interpret mode (the plane resident and behind the
HBM window) against the reference with every count it reports, how far
the folded answer moves with a single bit of C, and the constants the
cell `batch-gemm-small` pins."""

import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

from tests.helpers import instantiate
from wasmedge_tpu.models.programs import build_polybench_gemm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
MINI = (20, 25, 30)
SMALL = (60, 70, 80)
SEEDS = (0, 12345, 1048575)


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
    return _bench_module("references", "polybench_gemm")


def retired(ni, nj, nk):
    """The guest's instruction count, in closed form (the cell's
    `expected.retired_formula` says which loop gives which term)."""
    return (24 + 46 * ni + 9 * nk + 60 * ni * nj + 45 * ni * nk
            + 25 * nk * nj + 24 * ni * nj * nk)


def softfloat_ops(ni, nj, nk):
    """The binary64 routines a lane runs: a convert and a divide an
    initialised element, a multiply a scaled element and an
    alpha * A[i][k], a multiply and an add an inner iteration."""
    return 2 * ni * nj * nk + 3 * ni * nj + 3 * ni * nk + 2 * nk * nj


def _scalar(dims, seed):
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.common.statistics import Statistics
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf = Configure()  # as the cell's retired_per_lane_made_by says
    conf.statistics.instr_counting = True
    stat = Statistics(conf)
    ex = Executor(conf, stat)
    store = StoreManager()
    inst = ex.instantiate(store, Validator(conf).validate(
        Loader(conf).parse_module(build_polybench_gemm(*dims))))
    (cell,) = ex.invoke_raw(store, inst.find_func("gemm"), [seed])
    return int(cell), stat.instr_count


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("dims", [MINI, (3, 70, 8)])
def test_reference_is_the_scalar_engine_bit_for_bit(ref, dims, seed):
    got, count = _scalar(dims, seed)
    assert got == int(ref.reference_lanes("gemm", [seed], *dims)[0])
    assert count == retired(*dims)


def test_reference_answers_one_lane_at_small_sizes_by_default(ref):
    assert (ref.NI, ref.NJ, ref.NK) == SMALL
    assert (ref.ALPHA, ref.BETA) == (1.5, 1.2)
    with pytest.raises(KeyError):
        ref.reference("fib", [1])
    # seed 0 is the source's own arrays: C[0][0] = (0*0+1) % ni / ni
    C = ref.gemm_c([0], 2, 3, 4)
    assert C.shape == (1, 2, 3) and C.dtype == np.float64


@pytest.mark.parametrize("mem_hbm", [True, False],
                         ids=["hbm-window", "resident"])
def test_pallas_kernel_matches_the_reference(ref, mem_hbm):
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure

    dims, lanes = (2, 70, 3), 16
    conf = Configure()
    geometry = _load(BENCH, "configs",
                     "polybench-gemm-4096.json")["geometry"]
    for key, value in geometry.items():
        setattr(conf.batch, key, value)
    conf.batch.interpret = True
    conf.batch.mem_hbm = mem_hbm
    conf.obs.enabled = True
    _ex, store, inst = instantiate(build_polybench_gemm(*dims), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=lanes)
    assert eng.pallas is not None and eng.pallas.eligible
    seeds = np.random.default_rng(7).choice(
        1 << 20, size=lanes, replace=False).astype(np.int64)
    res = eng.run("gemm", [seeds], max_steps=10_000_000)
    assert not eng.fell_back_to_simt and eng.pallas.splits == 0
    got = np.asarray(res.results[0]).astype(np.uint64)
    assert np.array_equal(got, ref.reference_lanes("gemm", seeds, *dims))
    assert len(set(got.tolist())) == lanes   # the seed reaches the data
    assert np.all(np.asarray(res.trap) == -1)
    assert np.all(np.asarray(res.retired) == retired(*dims))
    pallas = eng.pallas
    assert pallas.softfloat_ops == softfloat_ops(*dims)
    assert pallas.softfloat_share == \
        pytest.approx(softfloat_ops(*dims) / retired(*dims))
    assert pallas.mem_static["mem_mode"] == \
        ("hbm_window" if mem_hbm else "resident")
    # the count reaches /metrics and its share the run's span
    from wasmedge_tpu.obs import parse_prometheus, render_prometheus

    parsed = parse_prometheus(render_prometheus(recorder=eng.obs))
    assert [v for (name, _labels), v in parsed.items()
            if name == "wasmedge_softfloat_ops_total"] == \
        [softfloat_ops(*dims)]
    (span,) = [e["args"] for e in eng.obs.events
               if e["name"] == "batch/run"]
    assert span["softfloat_share"] == round(pallas.softfloat_share, 6)
    if mem_hbm:
        ni, nj, nk = dims
        # every load and store of the guest went through the window
        assert pallas.window_accesses == \
            3 * ni * nj * nk + 4 * ni * nj + 2 * ni * nk + nk * nj
        assert 0 < pallas.window_writebacks < pallas.window_fills


def test_a_guest_without_f64_counts_no_softfloat():
    from wasmedge_tpu.batch.pallas_engine import holds_softfloat
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.models import build_fib, build_memory_batch

    conf = Configure()
    conf.batch.interpret = True     # a Pallas engine on the CPU; none runs
    for build, expect in ((build_fib, False), (build_memory_batch, False),
                          (build_polybench_gemm, True)):
        _ex, store, inst = instantiate(build(), conf)
        eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=8)
        assert holds_softfloat(eng.pallas.img) is expect
        assert eng.pallas.counts_softfloat is expect
        assert eng.pallas.softfloat_ops is None


def test_the_folded_answer_moves_with_every_fault(ref):
    """One element of C one bit off, one store dropped and a zeroed
    plane give three answers, none of them the right one."""
    dims = (3, 70, 8)
    C = ref.gemm_c([12345], *dims)
    right = int(ref.fold(C)[0])
    assert right == int(ref.reference_lanes("gemm", [12345], *dims)[0])
    one_bit = C.copy()
    one_bit.view(np.uint64)[0, 1, 37] ^= np.uint64(1)
    # the last store of C[2][69] never arrived: the element keeps the
    # value the iteration before it left
    dropped = C.copy()
    dropped[0, 2, 69] = ref.gemm_c([12345], 3, 70, 7)[0, 2, 69]
    assert dropped[0, 2, 69] != C[0, 2, 69]
    answers = {right, int(ref.fold(one_bit)[0]), int(ref.fold(dropped)[0]),
               int(ref.fold(np.zeros_like(C))[0])}
    assert len(answers) == 4 and int(ref.fold(np.zeros_like(C))[0]) == 0


def _driver():
    return _bench_module("drivers", "batch_seeded")


@pytest.mark.parametrize("seed", [1, 2147483659, 2**31 + 12345])
def test_the_driver_deals_distinct_seeds_from_the_runs_seed(seed):
    driver = _driver()
    spec = _load(BENCH, "workloads", "batch-gemm-small.json")["traffic"]["args"]
    args = driver.lane_args(spec, 4096, seed)
    assert args.dtype == np.int64 and len(set(args.tolist())) == 4096
    assert args.min() >= 0 and args.max() < spec["below"]
    assert np.array_equal(args, driver.lane_args(spec, 4096, seed))
    assert not np.array_equal(args, driver.lane_args(spec, 4096, seed + 1))
    # the other kinds are drivers/batch.py's
    assert driver.lane_args({"kind": "uniform", "value": 3}, 4, seed) \
        .tolist() == [3, 3, 3, 3]


def test_the_drivers_checker_holds_every_lane_to_all_64_bits(ref):
    driver = _driver()
    workload = _load(BENCH, "workloads", "batch-gemm-small.json")
    sizes = {"ni": 2, "nj": 3, "nk": 4}
    run = types.SimpleNamespace(
        config={"guest": {"args": sizes}}, workload=workload,
        rehearse=True, reference=lambda: ref)
    seeds = np.arange(5, dtype=np.int64) * 1000
    checker = driver.Checker(run, "gemm", seeds)
    assert checker.retired == retired(**sizes) == driver.formula(
        workload["expected"]["retired_formula"], sizes)
    right = ref.reference_lanes("gemm", seeds, **sizes)
    res = types.SimpleNamespace(
        results=[right.astype(np.int64)], trap=np.full(5, -1),
        retired=np.full(5, retired(**sizes)))
    assert checker.bad_lanes(res) == (0, 5 * retired(**sizes),
                                      retired(**sizes))
    res.results[0] = res.results[0] ^ np.array(
        [0, 1, 0, -2**63, 0], np.int64)        # a low bit, a top bit
    res.trap[4] = 7
    res.retired[0] -= 1
    assert checker.bad_lanes(res)[0] == 4
    # at the listed sizes the formula must give the pinned constant
    run.rehearse, run.config = False, {"guest": {"args": sizes}}
    with pytest.raises(RuntimeError, match="retired_per_lane"):
        driver.Checker(run, "gemm", seeds)


@pytest.mark.parametrize("missing", [
    None, "trace", "trace_window_fills", "window_dma_bytes",
    "hbm_bytes_per_s", "kernel"])
def test_window_hbm_share_reads_bytes_over_kernel_time_over_peak(missing):
    """readers/window_hbm_share.py on a hand-built obs: the share, and
    nothing (not an error) where the program, the driver or the trace
    left a part out, as on the parent."""
    reader = _bench_module("readers", "window_hbm_share")
    args = _load(BENCH, "layer_metrics", "window_hbm_share.batch.json")["args"]
    seconds = 0.0 if missing == "kernel" else 2.0
    trace = types.SimpleNamespace(
        op_seconds=lambda match: seconds if match == args["match"] else 0.0)
    counters = {"trace_window_fills": 300, "trace_window_writebacks": 100,
                "window_dma_bytes": 128 * 4096 * 4, "hbm_bytes_per_s": 819e9}
    counters.pop(missing, None)
    obs = {"counters": counters, "samples": {},
           "trace": None if missing == "trace" else trace}
    got = reader.read(obs, **args)
    if missing is None:
        assert got == pytest.approx(
            100 * 400 * 2097152 / 2.0 / 819e9) and 0 < got < 100
    else:
        assert got is None


def test_the_cell_pins_the_sizes_and_the_count(ref):
    config = _load(BENCH, "configs", "polybench-gemm-4096.json")
    workload = _load(BENCH, "workloads", "batch-gemm-small.json")
    sizes = config["guest"]["args"]
    assert (sizes["ni"], sizes["nj"], sizes["nk"]) == SMALL
    assert config["guest"]["builder"] == "build_polybench_gemm"
    assert config["lanes"] == 4096 and config["reduced"]
    expected = workload["expected"]
    assert expected["retired_per_lane"] == retired(*SMALL) == 8675504
    assert expected["softfloat_ops_per_lane"] == softfloat_ops(*SMALL)
    # the formula the driver evaluates is the one above, term by term
    terms = {"1": 1, "ni": 60, "nk": 80, "ni*nj": 60 * 70,
             "ni*nk": 60 * 80, "nk*nj": 80 * 70, "ni*nj*nk": 60 * 70 * 80}
    assert sum(c * terms[t] for t, c in
               expected["retired_formula"].items()) == retired(*SMALL)
    # the guest at SMALL: two pages, the three arrays from address 0
    data = build_polybench_gemm()
    assert data == build_polybench_gemm(*SMALL)
    _ex, _store, inst = instantiate(data)
    assert inst.memories[0].pages == 2
    spec = workload["traffic"]["args"]
    assert spec == {"kind": "distinct", "below": 1 << 20}
    rehearse = config["rehearse"]["guest"]
    assert rehearse["builder"] == "build_polybench_gemm" \
        and rehearse["args"]["nj"] == 70
