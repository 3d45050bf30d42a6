"""Every cell of BENCHMARK.json finds what benchmark/harness.py looks up
by name (the cell's workload file, its configuration's file, driver and
plain reference, each of its per-layer metrics' file and reader), the
guest a batch configuration names exists, every guest the models export
loads, and the instruction counts the fib cells pin are the closed form
of the guest."""

import json
import os

import pytest

from wasmedge_tpu import models

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


MANIFEST = _load(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _cell(name):
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    conf = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    return (cell, _load(ROOT, conf["file"]),
            _load(BENCH, "workloads", name + ".json"))


# what the cell that splits in flight reports for itself (PR 32): the two
# kernels apart, the phases of a split, and the counts a job
SPLIT_CELL_METRICS = [
    "kernel_optimistic_ms.batch", "kernel_careful_ms.batch",
    "recheck_ms.batch", "split_ms.batch", "install_ms.batch",
    "launches_per_job.batch", "careful_steps_per_job.batch"]
# and the cell of the compiled numeric guest (PR 34): the HBM window's
# traffic, a dispatch's length and the softfloat routines, off counters
# the driver sums job by job
GEMM_CELL_METRICS = [
    "window_fills_per_job.batch", "window_writebacks_per_job.batch",
    "window_miss_share.batch", "instr_per_dispatch.batch",
    "softfloat_ops_per_job.batch", "window_hbm_share.batch"]
# and the cell of the v128 guest (PR 38): gemm's but the softfloat
# routines, and the v128 instructions a job in their place
CHACHA_CELL_METRICS = [
    m for m in GEMM_CELL_METRICS if m != "softfloat_ops_per_job.batch"] \
    + ["simd_ops_per_job.batch"]


# and the cell of the WASI command (PR 40): the ChaCha20 cell's, and the
# hostcall serve's rounds, calls, bytes, vectorised share and spans
WASI_CELL_METRICS = CHACHA_CELL_METRICS + [
    "hostcall_rounds_per_job.batch", "hostcalls_per_job.batch",
    "hostcall_out_bytes_per_job.batch", "hostcall_vectorized_share.batch",
    "hostcall_begin_ms.batch", "hostcall_finish_ms.batch",
    "host_hostcall_ms.batch"]


# and the cell of CoreMark: the ChaCha20 cell's but the v128
# instructions, and the br_table and call_indirect a job in their place
COREMARK_CELL_METRICS = [
    m for m in CHACHA_CELL_METRICS if m != "simd_ops_per_job.batch"] \
    + ["indirect_ops_per_job.batch"]


# the self times and counts of the scheduler's transfers and enqueues
# (PR 36), read by `readers/trace_span_self.py` in every batch cell
HOST_LINK_METRICS = [
    "host_d2h_ms.batch", "host_h2d_ms.batch", "host_enqueue_ms.batch",
    "host_initial_state_ms.batch", "host_unspanned_ms.batch",
    "d2h_per_job.batch", "h2d_per_job.batch", "enqueues_per_job.batch"]


def test_the_manifest_lists_the_batch_cells():
    assert CELLS == ["batch-fib30-uniform", "batch-mem-uniform",
                     "batch-fib-divergent", "batch-fib-split",
                     "batch-gemm-small", "batch-chacha20-192k",
                     "batch-chacha20-write8k", "batch-coremark-2k"]
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    # every batch cell reports what the uniform fib cell reports
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]

    def reported(cell):
        return [m["name"] for m in metrics
                if cell in m.get("workloads", ())]

    for cell in CELLS[1:3]:
        assert reported(cell) == reported(CELLS[0])
    # but for one difference in the cell that splits: ` custom-call$`
    # would add the careful kernel's time to the optimistic one's and
    # `trace_steps` is the longest block's, so `kernel_ns_per_step.batch`
    # gives way to the two kernels' milliseconds a job and five more
    # (sorted: the manifest lists its metrics in the order PRs appended
    # them, and a metric of every cell may come after a cell's own)
    assert sorted(reported("batch-fib-split")) == sorted([
        m for m in reported(CELLS[0]) if m != "kernel_ns_per_step.batch"
    ] + SPLIT_CELL_METRICS)
    # the gemm cell reports all the uniform fib cell does, and six more
    assert sorted(reported("batch-gemm-small")) == sorted(
        reported(CELLS[0]) + GEMM_CELL_METRICS)
    # the ChaCha20 cell the same but softfloat's for its own one
    assert sorted(reported("batch-chacha20-192k")) == sorted(
        reported(CELLS[0]) + CHACHA_CELL_METRICS)
    # the WASI command's cell all of that and the serve's seven
    assert sorted(reported("batch-chacha20-write8k")) == sorted(
        reported(CELLS[0]) + WASI_CELL_METRICS)
    # the CoreMark cell the window's, the dispatch's and its own count
    assert sorted(reported("batch-coremark-2k")) == sorted(
        reported(CELLS[0]) + COREMARK_CELL_METRICS)
    # the host's account (PR 36) is every batch cell's
    assert set(HOST_LINK_METRICS) <= set(reported(CELLS[0]))
    for m in MANIFEST["per_layer"]:
        for own, cells in (
                (SPLIT_CELL_METRICS, ["batch-fib-split"]),
                (["softfloat_ops_per_job.batch"], ["batch-gemm-small"]),
                (set(GEMM_CELL_METRICS) & set(CHACHA_CELL_METRICS),
                 ["batch-gemm-small", "batch-chacha20-192k",
                  "batch-chacha20-write8k", "batch-coremark-2k"]),
                (["simd_ops_per_job.batch"],
                 ["batch-chacha20-192k", "batch-chacha20-write8k"]),
                (WASI_CELL_METRICS[len(CHACHA_CELL_METRICS):],
                 ["batch-chacha20-write8k"]),
                (["indirect_ops_per_job.batch"], ["batch-coremark-2k"])):
            if m["name"] in own:
                assert m["workloads"] == cells
                assert m["moves"] == "batch_ginstr_per_s"


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_its_files(name):
    cell, config, workload = _cell(name)
    assert workload["name"] == name and workload["config"] == cell["config"]
    assert config["name"] == cell["config"]
    assert config["chips"] == cell["chips"]
    for kind, stem in (("drivers", config["driver"]),
                       ("references", config["reference"])):
        assert os.path.isfile(os.path.join(BENCH, kind, stem + ".py")), stem
    reported = 0
    for group in ("end_to_end", "per_layer"):
        for m in MANIFEST[group]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            reported += 1
            if group == "per_layer":
                spec = _load(BENCH, "layer_metrics", m["name"] + ".json")
                # a metric's file names the driver families it fits; a
                # driver built on another names that one as its `family`
                assert {config["driver"], config.get("family")} \
                    & set(spec["drivers"]), m["name"]
                assert os.path.isfile(os.path.join(
                    BENCH, "readers", spec["reader"] + ".py")), m["name"]
                assert {k: spec[k] for k in ("layer", "unit", "better",
                                             "source", "moves")} == \
                    {k: m[k] for k in ("layer", "unit", "better",
                                       "source", "moves")}, m["name"]
    assert reported >= 3    # setup_s, one more end to end, one layer


@pytest.mark.parametrize("name", [c for c in CELLS if c.startswith("batch-")])
def test_batch_cell_names_a_guest_the_program_has(name):
    from tests.helpers import load_validate

    _cell_entry, config, workload = _cell(name)
    mod = load_validate(getattr(models, config["guest"]["builder"])(
        **config["guest"].get("args", {})))
    exports = {e.name for e in mod.exports}
    assert config["guest"]["export"] in exports
    assert workload["traffic"]["func"] == config["guest"]["export"]
    assert set(config["geometry"]) == {
        "value_stack_depth", "call_stack_depth", "steps_per_launch"}
    # exact results, completion, the scalar engine's count; the cell
    # that splits and the ones with 4096 arguments add that nothing falls
    # back; the WASI command's cell, that every acknowledged write is
    # read back and that the vectorised serve took every call
    assert len(config["guarantees"]) == (
        5 if name == "batch-chacha20-write8k" else
        4 if name in ("batch-fib-split", "batch-gemm-small",
                      "batch-chacha20-192k", "batch-coremark-2k") else 3)


# (length, sha256) of the two guests that moved out of the root's
# benchmark scripts in PR 30, taken from the parent's
# bench_echo.build_module() and parse_wat(bench_simd._SRC)
_MOVED_GUESTS = {
    "build_echo": (299, "cf220fbdba3d115859dfb9de78cc3e8e"
                        "9de8828239afe74b4076ce8d18bff881"),
    "build_simd_kernel": (193, "c016b6adf02c00cc0ec4fedc630a452d"
                               "2c39189908a85d9a89f3f5bac45c3ee6"),
}


# (length, sha256) of the guests the benchmark's configurations build by
# name with their `guest.args` (not in `models.__all__`): the bytes a
# cell ran are the bytes its numbers belong to
_CELL_GUESTS = {
    "build_memory_batch": ({}, (
        165, "d10af4ba8559e4ebc5f46539df878cc9"
             "be1309399ce46f4ac7addb106bef258d")),
    "build_polybench_gemm": ({"ni": 60, "nj": 70, "nk": 80}, (
        557, "adff256bdd086d57115061d1a608a75a"
             "ddc42fed1aab130f4b2fc8d9e6a23cbb")),
    "build_chacha20": ({"blocks": 3072}, (
        1341, "d66ea7d01a6c52b2c26ad42759e19780"
              "31ad524be70bd25dcf60daf91575caf7")),
    "build_chacha20_wasi": ({"blocks": 3072, "chunk_blocks": 128}, (
        1472, "b2cec3411c9aeb0737a90d75498373c2"
              "fd6dceb36fc492404ba7dc36e8a2626d")),
    "build_coremark": ({"total_data_size": 2000, "seed1": 0, "seed2": 0,
                        "seed3": 102}, (
        4933, "3647ef02d3b7419c34c595d0dd76ceb4"
              "b0d8a119a5987b0e494a18be1a729bcd")),
}


@pytest.mark.parametrize("builder", sorted(_CELL_GUESTS))
def test_the_cells_guests_are_the_bytes_that_were_measured(builder):
    import hashlib

    kwargs, pinned = _CELL_GUESTS[builder]
    data = getattr(models, builder)(**kwargs)
    assert (len(data), hashlib.sha256(data).hexdigest()) == pinned
    assert data == getattr(models, builder)()     # the defaults are these
    configs = [_cell(name)[1] for name in CELLS]
    assert any(c["guest"]["builder"] == builder
               and c["guest"].get("args", {}) == kwargs for c in configs)


@pytest.mark.parametrize("builder", models.__all__)
def test_every_guest_the_models_export_loads_and_validates(builder):
    """A new batch cell costs a builder here and data files: the driver
    finds a guest by getattr(wasmedge_tpu.models, builder) and calls it
    without arguments."""
    import hashlib

    from tests.helpers import load_validate

    data = getattr(models, builder)()
    mod = load_validate(data)
    assert any(e.name for e in mod.exports)
    if builder in _MOVED_GUESTS:
        assert (len(data), hashlib.sha256(data).hexdigest()) == \
            _MOVED_GUESTS[builder]


def _fib(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


@pytest.mark.parametrize("name,args", [
    ("batch-fib30-uniform", [30]),
    ("batch-fib-divergent", list(range(20, 31))),
    ("batch-fib-split", list(range(20, 31)))])
def test_fib_cells_pin_the_guests_instruction_count(name, args):
    """A leaf call of build_fib retires 7 instructions and an inner call
    14; fib(n) makes F(n+1) leaf calls and F(n+1) - 1 inner ones."""
    _cell_entry, _config, workload = _cell(name)
    counts = workload["expected"]["retired_by_arg"]
    assert sorted(counts, key=int) == [str(n) for n in args]
    for n in args:
        assert counts[str(n)] == 21 * _fib(n + 1) - 14
    spec = workload["traffic"]["args"]
    lo, hi = (spec["value"],) * 2 if spec["kind"] == "uniform" \
        else (spec["lo"], spec["hi"])
    assert list(range(lo, hi + 1)) == args


def test_the_count_the_fib_cells_pin_is_the_scalar_engines():
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.common.statistics import Statistics
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.models import build_fib
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf = Configure()  # as the cells' retired_by_arg_made_by says
    conf.statistics.instr_counting = True
    stat = Statistics(conf)
    ex = Executor(conf, stat)
    store = StoreManager()
    inst = ex.instantiate(store, Validator(conf).validate(
        Loader(conf).parse_module(build_fib())))
    ex.invoke_raw(store, inst.find_func("fib"), [15])
    assert stat.instr_count == 21 * _fib(16) - 14
    # a leaf call, whatever n < 2 it is given: `batch-fib-split` pins it
    split = _cell("batch-fib-split")[2]["expected"]
    for n in (-1024, -1, 0, 1):
        before = stat.instr_count
        ex.invoke_raw(store, inst.find_func("fib"), [n])
        assert stat.instr_count - before == split["retired_below_2"] == 7


def test_the_memory_cell_pins_an_answer_that_its_reference_gives():
    """`batch-mem-uniform` records what the scalar engine returned at
    the timed size beside its instruction count; the plain reference,
    which the Checker asks, gives the same, it is not the 0 that 64
    xored passes would give, and the count is the guest's closed form."""
    import importlib.util

    _cell_entry, config, workload = _cell("batch-mem-uniform")
    spec = importlib.util.spec_from_file_location(
        "ref_mem", os.path.join(BENCH, "references",
                                config["reference"] + ".py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    n = workload["traffic"]["args"]["value"]
    expected = workload["expected"]
    assert ref.reference("mem_checksum", [n]) == [
        expected["result_by_arg"][str(n)]]
    assert expected["result_by_arg"][str(n)] != 0
    assert expected["retired_by_arg"] == {
        str(n): ref.PASSES * (36 * n + 19) + 3}
