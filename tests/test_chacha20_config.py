"""The configuration `chacha20-simd-4096` (ChaCha20, RFC 8439, v128 from
end to end) on the CPU: its plain reference against the RFC's test
vector 2.3.2 and against the scalar engine bit for bit, the Pallas kernel
in interpret mode (the plane resident and behind the HBM window) against
the reference with every count it reports, how far the folded answer
moves with a single bit of ciphertext, and the constants the cell
`batch-chacha20-192k` pins."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from tests.helpers import instantiate
from tests.test_polybench_gemm_config import _bench_module, _load
from wasmedge_tpu.models.programs import build_chacha20

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "batch-chacha20-192k"
BLOCKS = 3072
SEEDS = (0, 524287, 1048575)


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "chacha20")


def retired(blocks):
    """The guest's instruction count, in closed form (the cell's
    `expected.retired_formula_is` says which loop gives which term)."""
    return 129 + 1706 * blocks


def simd_ops(blocks):
    """The instructions of a v128 class a lane runs: 20 a block of
    message, 478 a block encrypted (46 a double round), 11 once."""
    return 11 + 498 * blocks


def dispatches(blocks):
    """Handlers the kernel dispatches a lane block, counted on the CPU
    at 2, 4 and 3,072 blocks: a `v128.load` or `v128.store` ends a fused
    block (twelve a block of the message) and the 149 instructions of
    a double round are cut at 24."""
    return 14 + 93 * blocks


def _scalar(blocks, seed):
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
        Loader(conf).parse_module(build_chacha20(blocks))))
    (cell,) = ex.invoke_raw(store, inst.find_func("chacha20"), [seed])
    return int(cell) & (2 ** 64 - 1), stat.instr_count


def test_reference_reproduces_rfc_8439_vector_2_3_2(ref):
    key = np.frombuffer(bytes(range(32)), "<u4")
    nonce = np.frombuffer(bytes.fromhex("000000090000004a00000000"), "<u4")
    words = ref.block_words([np.array([k]) for k in key],
                            np.array([1], np.uint32),
                            [np.array([n]) for n in nonce])
    assert " ".join(f"{int(w[0]):08x}" for w in words) == (
        "e4e7f110 15593bd1 1fdd0f50 c47120a3 c7f4d1c7 0368c033 9aaa2204 "
        "4e6cd4c3 466482d2 09aa9f07 05d7c214 a2028bd9 d19c12b5 b94e16de "
        "e883d0cb 4e3c50a2")
    # the constants are "expand 32-byte k", the counter is word 12
    assert [int(w) for w in ref.SIGMA] == [
        0x61707865, 0x3320646e, 0x79622d32, 0x6b206574]
    other = ref.block_words([np.array([k]) for k in key],
                            np.array([2], np.uint32),
                            [np.array([n]) for n in nonce])
    assert all(int(a[0]) != int(b[0]) for a, b in zip(words, other))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("blocks", [1, 5])
def test_reference_is_the_scalar_engine_bit_for_bit(ref, blocks, seed):
    got, count = _scalar(blocks, seed)
    assert got == int(ref.reference_lanes("chacha20", [seed], blocks)[0])
    assert count == retired(blocks)


def test_reference_answers_one_lane_and_chunks_many(ref, monkeypatch):
    assert ref.BLOCKS == BLOCKS
    with pytest.raises(KeyError):
        ref.reference("gemm", [1])
    seeds = np.arange(7, dtype=np.int64) * 9973
    whole = ref.reference_lanes("chacha20", seeds, 2)
    monkeypatch.setattr(ref, "LANE_CHUNK", 3)     # 3 + 3 + 1 lanes
    assert np.array_equal(ref.reference_lanes("chacha20", seeds, 2), whole)
    assert whole.dtype == np.uint64 and len(set(whole.tolist())) == 7
    # the message is the recurrence's, the context's key and nonce too
    key, nonce, message = ref.derive([5], 1)
    w = 5
    for word in key + nonce:
        w = (w * 1664525 + 1013904223) % 2 ** 32
        assert int(word[0]) == w
    w = (w * 1664525 + 1013904223) % 2 ** 32
    first = [(w * m + a) % 2 ** 32 for m, a in zip(ref.MSG_MUL.tolist(),
                                                   ref.MSG_ADD.tolist())]
    assert message[0, :4].tolist() == first
    assert message[0, 4:8].tolist() == [
        (x * 1664525 + 1013904223) % 2 ** 32 for x in first]


@pytest.mark.parametrize("mem_hbm", [True, False],
                         ids=["hbm-window", "resident"])
def test_pallas_kernel_matches_the_reference(ref, mem_hbm):
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure

    blocks, lanes = 2, 16
    conf = Configure()
    geometry = _load(BENCH, "configs",
                     "chacha20-simd-4096.json")["geometry"]
    for key, value in geometry.items():
        setattr(conf.batch, key, value)
    conf.batch.interpret = True
    conf.batch.mem_hbm = mem_hbm
    conf.obs.enabled = True
    _ex, store, inst = instantiate(build_chacha20(blocks), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=lanes)
    assert eng.pallas is not None and eng.pallas.eligible
    seeds = np.random.default_rng(7).choice(
        1 << 20, size=lanes, replace=False).astype(np.int64)
    res = eng.run("chacha20", [seeds], max_steps=10_000_000)
    assert not eng.fell_back_to_simt and eng.pallas.splits == 0
    got = np.asarray(res.results[0]).astype(np.uint64)
    assert np.array_equal(got,
                          ref.reference_lanes("chacha20", seeds, blocks))
    assert len(set(got.tolist())) == lanes   # the seed reaches the data
    assert np.all(np.asarray(res.trap) == -1)
    assert np.all(np.asarray(res.retired) == retired(blocks))
    pallas = eng.pallas
    assert pallas.ctrl_width == 17
    assert pallas.simd_ops == simd_ops(blocks)
    assert pallas.simd_share == \
        pytest.approx(simd_ops(blocks) / retired(blocks))
    assert pallas.softfloat_ops is None
    assert pallas.dispatches == dispatches(blocks)
    # the six shuffles move whole 32-bit lanes: row moves in their blocks
    assert pallas.shuffle_sites == {"word": 6, "dynamic": 0}
    assert pallas.mem_static["mem_mode"] == \
        ("hbm_window" if mem_hbm else "resident")
    # the count reaches /metrics and its share the run's span
    from wasmedge_tpu.obs import parse_prometheus, render_prometheus

    parsed = parse_prometheus(render_prometheus(recorder=eng.obs))
    assert [v for (name, _labels), v in parsed.items()
            if name == "wasmedge_simd_ops_total"] == [simd_ops(blocks)]
    (span,) = [e["args"] for e in eng.obs.events
               if e["name"] == "batch/run"]
    assert span["simd_share"] == round(pallas.simd_share, 6)
    assert "softfloat_share" not in span
    assert span["shuffle_sites"] == "6/0"
    assert {dict(labels)["kind"]: v for (name, labels), v in parsed.items()
            if name == "wasmedge_shuffle_sites"} == {"word": 6, "dynamic": 0}
    if mem_hbm:
        # every load and store of the guest went through the window: 20
        # a block (4 stores of message, 4 loads and 4 stores encrypting,
        # 8 loads folding), 18 for the context
        assert pallas.window_accesses == 18 + 20 * blocks
        # page 0 and the message's 32 rows: one window each, both dirty
        assert (pallas.window_fills, pallas.window_writebacks) == (2, 2)
    else:
        assert pallas.window_accesses == 0


def test_a_guest_without_v128_counts_none_and_keeps_sixteen_columns():
    from wasmedge_tpu.batch.pallas_engine import ctrl_width
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.models import (
        build_fib, build_memory_batch, build_simd_kernel)
    from wasmedge_tpu.models.programs import build_polybench_gemm

    assert (ctrl_width(False), ctrl_width(True)) == (16, 17)
    conf = Configure()
    conf.batch.interpret = True     # a Pallas engine on the CPU; none runs
    for build, expect in ((build_fib, False), (build_memory_batch, False),
                          (build_polybench_gemm, False),
                          (build_simd_kernel, True),
                          (lambda: build_chacha20(1), True)):
        _ex, store, inst = instantiate(build(), conf)
        eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=8)
        assert eng.pallas.img.has_simd is expect
        assert eng.pallas.ctrl_width == (17 if expect else 16)
        assert eng.pallas.simd_ops is None
        assert eng.pallas.shuffle_sites is None     # before a build


def test_the_folded_answer_moves_with_every_fault(ref):
    """One ciphertext bit flipped, one 16-byte store dropped, one row
    rotated by the wrong number of lanes and a zeroed plane give four
    answers, none of them the right one."""
    blocks, seed = 3, 12345
    ct = ref.encrypt([seed], blocks)
    right = int(ref.fold(ct)[0])
    assert right == int(ref.reference_lanes("chacha20", [seed], blocks)[0])
    one_bit = ct.copy()
    one_bit[0, 37] ^= np.uint32(1 << 9)
    # the last `v128.store` of a block never arrived: the plaintext stays
    dropped = ct.copy()
    dropped[0, 28:32] = ref.derive([seed], blocks)[2][0, 28:32]
    assert not np.array_equal(dropped, ct)
    # a diagonal taken one lane off (a wrong shuffle mask) in one block
    rotated = ct.copy()
    rotated[0, 16:20] = np.roll(ct[0, 16:20], 1)
    answers = {right, int(ref.fold(one_bit)[0]), int(ref.fold(dropped)[0]),
               int(ref.fold(rotated)[0]),
               int(ref.fold(np.zeros_like(ct))[0])}
    assert len(answers) == 5 and int(ref.fold(np.zeros_like(ct))[0]) == 0


def test_the_guest_is_v128_where_the_cell_says():
    """The module's own bytes: ten v128 locals, six shuffles whose masks
    move whole 32-bit lanes, sixteen-byte alignment in every v128
    memarg, four pages at 3,072 blocks and the constants as data."""
    from tests.helpers import load_validate
    from wasmedge_tpu.batch.image import (
        CLS_VLOAD, CLS_VSHUFFLE, CLS_VSTORE)
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure

    _ex, _store, inst = instantiate(build_chacha20())
    assert inst.memories[0].pages == 4
    mod = load_validate(build_chacha20(2))
    assert any(bytes(seg.data) == b"expand 32-byte k" for seg in mod.datas)
    conf = Configure()
    conf.batch.interpret = True     # a Pallas engine on the CPU; none runs
    _ex, store, small = instantiate(build_chacha20(2), conf)
    img = UniformBatchEngine(small, store=store, conf=conf,
                             lanes=8).pallas.img
    cls = np.asarray(img.cls)
    assert img.has_simd
    assert int((cls == CLS_VSHUFFLE).sum()) == 6
    masks = np.asarray(img.v128, np.int32)[
        np.asarray(img.a)[cls == CLS_VSHUFFLE]].view(np.uint8).reshape(6, 16)
    for mask in masks.tolist():
        lanes = [mask[4 * i] // 4 for i in range(4)]
        assert mask == [4 * lane + k for lane in lanes for k in range(4)]
        assert sorted(lanes) == [0, 1, 2, 3] and lanes != [0, 1, 2, 3]
    # 1 + 4 + 4 loads (the constants, the rows, a block), 1 + 4 + 4 stores
    assert int((cls == CLS_VLOAD).sum()) == 9
    assert int((cls == CLS_VSTORE).sum()) == 9


def _driver():
    return _bench_module("drivers", "batch_seeded_simd")


def test_the_driver_is_the_seeded_one_with_one_more_counter():
    driver = _driver()
    seeded = _bench_module("drivers", "batch_seeded")
    assert driver.seeded.ENGINE_COUNTERS == \
        seeded.ENGINE_COUNTERS + ("simd_ops",)
    assert "simd_ops" not in seeded.ENGINE_COUNTERS   # its own copy only
    assert driver.run is driver.seeded.run
    spec = _load(BENCH, "workloads", CELL + ".json")["traffic"]["args"]
    args = driver.seeded.lane_args(spec, 4096, 2147483659)
    assert args.dtype == np.int64 and len(set(args.tolist())) == 4096
    assert args.min() >= 0 and args.max() < spec["below"] == 1 << 20
    # a program that lacks the builder ends before it touches the device
    with pytest.raises(AttributeError, match="build_no_such_guest"):
        driver.seeded.guest_builder(
            {"guest": {"builder": "build_no_such_guest"}})


def test_the_drivers_checker_holds_every_lane_to_all_64_bits(ref):
    driver = _driver().seeded
    workload = _load(BENCH, "workloads", CELL + ".json")
    sizes = {"blocks": 2}
    run = types.SimpleNamespace(
        config={"guest": {"args": sizes}}, workload=workload,
        rehearse=True, reference=lambda: ref)
    seeds = np.arange(5, dtype=np.int64) * 1000
    checker = driver.Checker(run, "chacha20", seeds)
    assert checker.retired == retired(2) == driver.formula(
        workload["expected"]["retired_formula"], sizes)
    right = ref.reference_lanes("chacha20", seeds, **sizes)
    res = types.SimpleNamespace(
        results=[right.astype(np.int64)], trap=np.full(5, -1),
        retired=np.full(5, retired(2)))
    assert checker.bad_lanes(res) == (0, 5 * retired(2), retired(2))
    res.results[0] = res.results[0] ^ np.array(
        [0, 1, 0, -2**63, 0], np.int64)        # a low bit, a top bit
    res.trap[4] = 7
    res.retired[0] -= 1
    assert checker.bad_lanes(res)[0] == 4
    # at the listed size the formula must give the pinned constant
    run.rehearse = False
    with pytest.raises(RuntimeError, match="retired_per_lane"):
        driver.Checker(run, "chacha20", seeds)


def test_the_cell_pins_the_sizes_and_the_counts(ref):
    config = _load(BENCH, "configs", "chacha20-simd-4096.json")
    workload = _load(BENCH, "workloads", CELL + ".json")
    assert config["guest"] == {"builder": "build_chacha20",
                               "export": "chacha20",
                               "args": {"blocks": BLOCKS}}
    assert config["driver"] == "batch_seeded_simd"
    assert config["reference"] == "chacha20" and config["family"] == "batch"
    assert config["lanes"] == 4096 and config["chips"] == 1
    assert config["reduced"] == [] and config["architecture"] is None
    assert config["assumed"][0].startswith("the message length")
    assert config["geometry"] == {"value_stack_depth": 64,
                                  "call_stack_depth": 16,
                                  "steps_per_launch": 50000000}
    expected = workload["expected"]
    assert expected["retired_per_lane"] == retired(BLOCKS) == 5240961
    assert expected["retired_formula"] == {"1": 129, "blocks": 1706}
    assert expected["simd_ops_per_lane"] == simd_ops(BLOCKS) == 1529867
    # nine tenths of a lane's instructions are the block loop's
    assert 1551 * BLOCKS / retired(BLOCKS) > 0.9
    assert workload["traffic"] == {
        "func": "chacha20", "args": {"kind": "distinct", "below": 1 << 20},
        "max_steps": 2000000000, "trace_jobs": 3}
    assert build_chacha20() == build_chacha20(BLOCKS)
    rehearse = config["rehearse"]
    assert rehearse["guest"]["builder"] == "build_chacha20"
    assert rehearse["guest"]["args"]["blocks"] == 4
    assert rehearse["lanes"] == 16 and rehearse["geometry"]["mem_hbm"]
    manifest = _load(ROOT, "BENCHMARK.json")
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == config["name"]
    (metric,) = [m for m in manifest["per_layer"]
                 if m["name"] == "simd_ops_per_job.batch"]
    # its own cell, and the WASI command's that shares the guest (PR 40)
    assert metric["workloads"] == [CELL, "batch-chacha20-write8k"]


def test_the_cell_rehearses_on_the_cpu():
    """`run.py --rehearse`: the cell's whole path at the rehearsal's
    sizes, the traced slice included, every value null."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--trace", "1", "--seed", "2147483659"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] % 16 == 0 and last["attempted"] >= 48
    assert "simd_ops_per_job.batch" in last["metrics"]
    assert all(m["value"] is None for m in last["metrics"].values())
    warm = lines[0] if "simd_ops" in lines[0] else lines[1]
    assert warm["simd_ops"] == simd_ops(4)
    assert warm["lane_steps"] == retired(4)
    assert warm["dispatches"] == dispatches(4)
    assert warm["splits"] == 0 and warm["bad_lanes"] == 0
