"""The configuration `chacha20-wasi-4096` (a WASI command that writes its
output: ChaCha20 whose ciphertext leaves through `fd_write`) on the CPU:
its plain reference against RFC 8439's test vector 2.3.2 and against the
scalar engine's result, instruction count and own stdout; the Pallas
kernel in interpret mode behind the HBM window against the reference
(results, the stream on fd 1, `retired`, every count of the hostcall
serve, the host link's crossings against their spans) in one block and in
two; the serve's small repairs (a run's own `hostcall_stats`, the
import's stub retiring nothing, pad lanes not served, a partly parked
plane); and the constants the cell `batch-chacha20-write8k` pins."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from tests.helpers import instantiate
from tests.test_polybench_gemm_config import _bench_module, _load
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import ErrCode
from wasmedge_tpu.host.wasi import WasiModule
from wasmedge_tpu.models.programs import build_chacha20, build_chacha20_wasi
from wasmedge_tpu.utils.builder import ModuleBuilder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "batch-chacha20-write8k"
FUNC = "chacha20_write"
BLOCKS, CHUNK = 3072, 128
SIZES = [(8, 2), (4, 4)]        # (blocks, chunk_blocks)
SEEDS = (0, 524287, 1048575)
LANES = 16
WASI = "wasi_snapshot_preview1"


@pytest.fixture(scope="module")
def ref():
    return _bench_module("references", "chacha20_wasi")


def retired(blocks, chunk):
    """The guest's instruction count, in closed form (the cell's
    `expected.retired_formula_is` says which part gives which term)."""
    return 129 + 1706 * blocks + 27 * (blocks // chunk)


def _wasi(out):
    wasi = WasiModule()
    wasi.init_wasi()
    wasi.env.fds[1].os_fd = out.fileno()
    return wasi


def _scalar(blocks, chunk, seed, tmp_path):
    """-> (result cell, instructions retired, stdout) of one lane on the
    scalar engine."""
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
    path = tmp_path / "scalar.out"
    with open(path, "w+b") as out:
        ex.register_import_object(store, _wasi(out))
        inst = ex.instantiate(store, Validator(conf).validate(
            Loader(conf).parse_module(build_chacha20_wasi(blocks, chunk))))
        (cell,) = ex.invoke_raw(store, inst.find_func(FUNC), [seed])
    return int(cell) & (2 ** 64 - 1), stat.instr_count, path.read_bytes()


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


def test_reference_is_a_file_of_its_own():
    """No import from the program or from another configuration's copy."""
    with open(os.path.join(BENCH, "references", "chacha20_wasi.py")) as f:
        imports = [ln.split()[1] for ln in f
                   if ln.startswith(("import ", "from "))]
    assert imports == ["numpy"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("blocks,chunk", SIZES)
def test_reference_is_the_scalar_engine_bit_for_bit(ref, blocks, chunk,
                                                    seed, tmp_path):
    got, count, out = _scalar(blocks, chunk, seed, tmp_path)
    cells, stream = ref.reference_job(FUNC, [seed], blocks, chunk)
    assert got == int(cells[0])
    assert got == int(ref.reference_lanes(FUNC, [seed], blocks, chunk)[0])
    # the scalar engine's own stdout for the lane, and the count
    assert out == stream.tobytes() and len(out) == 64 * blocks
    assert count == retired(blocks, chunk)
    # the answer is build_chacha20's for the same seed
    _ex, store, inst = instantiate(build_chacha20(blocks))
    (plain,) = _ex.invoke_raw(store, inst.find_func("chacha20"), [seed])
    assert int(plain) & (2 ** 64 - 1) == got


def test_reference_stream_is_call_by_call_lane_ascending(ref, monkeypatch):
    assert (ref.BLOCKS, ref.CHUNK_BLOCKS) == (BLOCKS, CHUNK)
    with pytest.raises(KeyError):
        ref.reference_lanes("chacha20", [1], 4, 2)
    with pytest.raises(ValueError):
        ref.reference_stream([1], 5, 2)
    seeds = np.arange(7, dtype=np.int64) * 9973
    blocks, chunk = 6, 2
    stream = ref.reference_stream(seeds, blocks, chunk)
    rec = 64 * chunk
    assert stream.dtype == np.uint8 and stream.size == 7 * 64 * blocks
    text = ref.encrypt(seeds, blocks).view(np.uint8)    # [lanes, bytes]
    for r in range(blocks // chunk):
        for lane in range(7):
            at = (r * 7 + lane) * rec
            assert np.array_equal(stream[at:at + rec],
                                  text[lane, r * rec:(r + 1) * rec])
    cells, again = ref.reference_job(FUNC, seeds, blocks, chunk)
    assert np.array_equal(again, stream)
    assert np.array_equal(cells, ref.fold(ref.encrypt(seeds, blocks)))
    monkeypatch.setattr(ref, "LANE_CHUNK", 3)     # 3 + 3 + 1 lanes
    assert np.array_equal(ref.reference_stream(seeds, blocks, chunk), stream)


# -- the Pallas path, interpret mode, behind the HBM window ---------------
_ENGINES = {}


def _engine(blocks, chunk, tmp_factory):
    """One engine a size for the module (an interpret-mode kernel takes a
    minute to trace): 16 lanes, the cell's geometry, `mem_hbm` on, obs
    on.  At (4, 4) the lane block is capped at 8, so the job is two
    blocks of 8 lanes."""
    from wasmedge_tpu.batch.uniform import UniformBatchEngine

    key = (blocks, chunk)
    if key not in _ENGINES:
        conf = Configure()
        geometry = _load(BENCH, "configs",
                         "chacha20-wasi-4096.json")["geometry"]
        for name, value in geometry.items():
            setattr(conf.batch, name, value)
        conf.batch.interpret = True
        conf.batch.mem_hbm = True
        conf.obs.enabled = True
        out = open(tmp_factory.mktemp("fd1") / "out", "w+b")
        wasi = _wasi(out)
        _ex, store, inst = instantiate(build_chacha20_wasi(blocks, chunk),
                                       conf, imports=[wasi])
        eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
        assert eng.pallas is not None and eng.pallas.eligible
        if key == (4, 4):
            eng.pallas._blk_cap = 8
        _ENGINES[key] = (eng, wasi, out)
    return _ENGINES[key]


def _run(eng, out, seeds):
    os.ftruncate(out.fileno(), 0)
    os.lseek(out.fileno(), 0, os.SEEK_SET)
    res = eng.run(FUNC, [seeds], max_steps=10_000_000)
    return res, os.pread(out.fileno(), 1 << 24, 0)


@pytest.mark.parametrize("seed", [7, 11, 13])
@pytest.mark.parametrize("blocks,chunk", SIZES)
def test_pallas_job_matches_the_reference(ref, blocks, chunk, seed,
                                          tmp_path_factory, tmp_path):
    eng, _wasi_mod, out = _engine(blocks, chunk, tmp_path_factory)
    seeds = np.random.default_rng(seed).choice(
        1 << 20, size=LANES, replace=False).astype(np.int64)
    events0 = len(eng.obs.events)
    res, written = _run(eng, out, seeds)
    cells, stream = ref.reference_job(FUNC, seeds, blocks, chunk)
    calls = blocks // chunk
    assert not eng.fell_back_to_simt and eng.pallas.splits == 0
    assert np.array_equal(np.asarray(res.results[0]).astype(np.uint64),
                          cells)
    assert len(set(cells.tolist())) == LANES  # the seed reaches the data
    assert np.all(np.asarray(res.trap) == -1)
    # every write once, whole, in the documented order
    assert written == stream.tobytes()
    # the scalar engine's count and the formula, through every re-arm
    count = _scalar(blocks, chunk, int(seeds[0]), tmp_path)[1]
    assert np.all(np.asarray(res.retired) == count)
    assert count == retired(blocks, chunk)
    pallas = eng.pallas
    assert (pallas.hostcall_rounds, pallas.hostcall_calls,
            pallas.hostcall_vectorized, pallas.hostcall_out_bytes) == \
        (calls, LANES * calls, LANES * calls, LANES * 64 * blocks)
    # the run's own stats, not a growing total (each job reads the same)
    stats = eng.simt.hostcall_stats
    assert (stats["serve_rounds"], stats["tier1_calls"],
            stats["tier1_vectorized"], stats["out_bytes"]) == \
        (calls, LANES * calls, LANES * calls, LANES * 64 * blocks)
    assert stats["tier0_calls"] == 0
    nblk = 2 if (blocks, chunk) == (4, 4) else 1
    assert (LANES, LANES // nblk) in eng.simt._sched_cache
    assert pallas.mem_static["mem_mode"] == "hbm_window"
    # the guest's six shuffles, as in `build_chacha20`: all row moves
    assert pallas.shuffle_sites == {"word": 6, "dynamic": 0}
    # the host link's counts are the leaf spans of this run, the serve's
    # among them; its phases lie under spans of their own
    mine = list(eng.obs.events)[events0:]
    names = [e["name"] for e in mine]
    assert (names.count("batch/d2h"), names.count("batch/h2d"),
            names.count("batch/enqueue")) == \
        (pallas.d2h_transfers, pallas.h2d_transfers,
         pallas.programs_enqueued)
    what = [e["args"].get("what") or e["args"].get("program")
            for e in mine if e["name"] in ("batch/d2h", "batch/enqueue")]
    # a round: the pass record, two slabs, the iovec's rows and the
    # payload's cut on the device, the result rows and nwritten's row
    # set in place (a block each), the kernel and its pack
    assert what.count("pass") == calls + 1
    assert what.count("slab_lo") == what.count("slab_hi") == calls
    # (a block that is not the whole plane reads nwritten's row before
    # it sets its lanes in it; the next block finds it patched)
    assert what.count("hc_rows") == what.count("mem_rows") \
        == (2 * nblk + (nblk > 1)) * calls
    assert what.count("mem_chunk") == what.count("hc_gather") == 0
    assert what.count("hc_results") == what.count("hc_scatter") \
        == nblk * calls
    assert names.count("batch/hostcall_begin") == calls
    finishes = [e["args"] for e in mine
                if e["name"] == "batch/hostcall_finish"]
    assert [(f["calls"], f["bytes"]) for f in finishes] == \
        [(LANES, LANES * 64 * chunk)] * calls
    (run,) = [e["args"] for e in mine if e["name"] == "batch/run"]
    assert run["hostcall_rounds"] == calls
    assert run["shuffle_sites"] == "6/0"
    assert run["donated_planes"] == pallas.donated_planes == 2
    from wasmedge_tpu.obs import parse_prometheus, render_prometheus

    parsed = parse_prometheus(render_prometheus(recorder=eng.obs))
    assert {dict(labels)["kind"]: v for (name, labels), v in parsed.items()
            if name == "wasmedge_shuffle_sites"} == {"word": 6, "dynamic": 0}
    got = {(name, tuple(sorted(labels))): v
           for (name, labels), v in parsed.items()
           if name.startswith("wasmedge_hostcall_") and "drain" not in name}
    assert got[("wasmedge_hostcall_calls_total",
                (("path", "per_lane"),))] == 0
    assert got[("wasmedge_hostcall_calls_total",
                (("path", "vectorized"),))] % (LANES * calls) == 0
    assert got[("wasmedge_hostcall_out_bytes_total", ())] % \
        (LANES * 64 * blocks) == 0
    assert got[("wasmedge_hostcall_rounds_total", ())] % calls == 0


@pytest.mark.parametrize("piece,parts", [(512, 4), (768, 3)],
                         ids=["even-pieces", "a-short-last-piece"])
def test_a_payload_over_the_piece_size_comes_down_in_pieces(
        ref, piece, parts, monkeypatch, tmp_path_factory):
    """A read of more than `ROWS_PIECE_BYTES` is cut into pieces on the
    device, laid into the engine's own buffer (the same one every round)
    and written from it: the job reads as it does in one piece, and each
    piece is a call and a download of the link."""
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine

    blocks, chunk = 8, 2
    calls = blocks // chunk
    eng, _wasi_mod, out = _engine(blocks, chunk, tmp_path_factory)
    monkeypatch.setattr(PallasUniformEngine, "ROWS_PIECE_BYTES", piece)
    seeds = np.random.default_rng(piece).choice(
        1 << 20, size=LANES, replace=False).astype(np.int64)
    events0 = len(eng.obs.events)
    res, written = _run(eng, out, seeds)
    cells, stream = ref.reference_job(FUNC, seeds, blocks, chunk)
    assert np.array_equal(np.asarray(res.results[0]).astype(np.uint64),
                          cells)
    assert np.all(np.asarray(res.trap) == -1)
    assert written == stream.tobytes()
    assert np.all(np.asarray(res.retired) == retired(blocks, chunk))
    mine = list(eng.obs.events)[events0:]
    what = [e["args"].get("what") or e["args"].get("program")
            for e in mine if e["name"] in ("batch/d2h", "batch/enqueue")]
    # a round: the iovec's rows in one piece, the payload's 2,048 bytes
    # cut by one call of the program, a download a piece
    assert what.count("hc_rows") == 2 * calls
    assert what.count("mem_rows") == (1 + parts) * calls
    names = [e["name"] for e in mine]
    assert (names.count("batch/d2h"), names.count("batch/enqueue")) == \
        (eng.pallas.d2h_transfers, eng.pallas.programs_enqueued)
    buffers = [e._rows_buffers for e in eng.simt._sched_cache.values()
               if getattr(e, "_rows_buffers", None)]
    assert [list(b) for b in buffers] == [[(LANES, 16 * chunk)]]


def test_a_closed_fd_traps_every_lane(ref, tmp_path_factory):
    """`fd_write` on an fd the environ does not hold returns EBADF, the
    guest executes `unreachable`: every lane traps, nothing is written,
    and the count is the scalar engine's up to the trap."""
    eng, wasi, out = _engine(4, 4, tmp_path_factory)
    seeds = np.arange(LANES, dtype=np.int64) + 5
    entry = wasi.env.fds.pop(1)
    try:
        res, written = _run(eng, out, seeds)
    finally:
        wasi.env.fds[1] = entry
    assert np.all(np.asarray(res.trap) == int(ErrCode.Unreachable))
    assert written == b"" and eng.pallas.hostcall_out_bytes == 0
    assert eng.pallas.hostcall_rounds == 1
    assert not eng.fell_back_to_simt
    # every lane reads the same count, short of a job's
    counts = set(np.asarray(res.retired).tolist())
    assert len(counts) == 1 and 0 < counts.pop() < retired(4, 4)


# -- blocks that park at different times: the gathered columns ------------
def _stagger_module():
    """f(n): n turns of a counting loop, then two `fd_write`s of the
    lane's 4-byte record (n, then n + 1000) from the iovec at 0.  Lanes
    of one n group into a block; a small n parks while a large n still
    runs."""
    b = ModuleBuilder()
    b.import_func(WASI, "fd_write", ["i32"] * 4, ["i32"])
    b.add_memory(1, 1)
    write = [("i32.const", 1), ("i32.const", 0), ("i32.const", 1),
             ("i32.const", 16), ("call", 0), "drop"]
    body = [
        ("i32.const", 0), ("i32.const", 64), ("i32.store", 2, 0),
        ("i32.const", 4), ("i32.const", 4), ("i32.store", 2, 0),
        ("block", None), ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0), "end", "end",
        ("i32.const", 64), ("local.get", 0), ("i32.store", 2, 0), *write,
        ("i32.const", 64), ("local.get", 0), ("i32.const", 1000),
        "i32.add", ("i32.store", 2, 0), *write,
        ("i32.const", 16), ("i32.load", 2, 0),
    ]
    b.add_function(["i32"], ["i32"], ["i32"], body, export="f")
    return b.build()


@pytest.mark.parametrize("mem_hbm", [True, False],
                         ids=["hbm-window", "resident"])
def test_a_partly_parked_plane_is_served_beside_a_launch(mem_hbm,
                                                         tmp_path):
    """Two groups of 12 lanes in blocks of 8 (two blocks a group, the
    second half pads): the short group parks while the long one runs
    on, so its columns are gathered into a buffer of their own, its
    serve overlaps the next launch (tier 2), its rows go back into its
    blocks' columns only, and a pad lane is never served: 24 lanes
    write twice each, in their own order."""
    from wasmedge_tpu.batch.uniform import UniformBatchEngine

    conf = Configure()
    conf.batch.interpret = True
    conf.batch.mem_hbm = mem_hbm
    conf.batch.steps_per_launch = 200
    conf.obs.enabled = True
    lanes = 24
    with open(tmp_path / "out", "w+b") as out:
        _ex, store, inst = instantiate(_stagger_module(), conf,
                                       imports=[_wasi(out)])
        eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=lanes)
        eng.pallas._blk_cap = 16
        n = np.where(np.arange(lanes) % 2 == 0, 3, 400).astype(np.int64)
        res = eng.run("f", [n], max_steps=1_000_000)
        written = os.pread(out.fileno(), 1 << 16, 0)
    assert np.all(np.asarray(res.trap) == -1)
    assert np.all(np.asarray(res.results[0]) == 4)      # nwritten
    assert not eng.fell_back_to_simt and eng.pallas.splits == 0
    assert (32, 8) in eng.simt._sched_cache      # four blocks, 8 pads
    records = np.frombuffer(written, "<u4").tolist()
    assert sorted(records) == sorted(
        [3] * 12 + [1003] * 12 + [400] * 12 + [1400] * 12)
    # a group's first writes before its second, the short group first
    assert records[:24] == [3] * 12 + [1003] * 12
    assert records[24:] == [400] * 12 + [1400] * 12
    pallas = eng.pallas
    assert (pallas.hostcall_calls, pallas.hostcall_vectorized,
            pallas.hostcall_out_bytes) == (48, 48, 192)
    assert pallas.hostcall_rounds == 4
    names = [(e["name"], e["args"].get("what") or e["args"].get("program"))
             for e in eng.obs.events]
    assert names.count(("batch/enqueue", "hc_gather")) == 4
    # a launch went out between a serve's begin and its finish
    order = [n for n, _w in names if n in (
        "batch/hostcall_begin", "batch/launch", "batch/hostcall_finish")]
    first = order.index("batch/hostcall_begin")
    assert order[first:first + 3] == [
        "batch/hostcall_begin", "batch/launch", "batch/hostcall_finish"]
    # what the scalar engine retires: the stub's two are the engine's
    for arg in (3, 400):
        with open(tmp_path / f"s{arg}", "w+b") as out:
            from wasmedge_tpu.common.statistics import Statistics
            from wasmedge_tpu.executor import Executor
            from wasmedge_tpu.loader import Loader
            from wasmedge_tpu.runtime.store import StoreManager
            from wasmedge_tpu.validator import Validator

            sconf = Configure()
            sconf.statistics.instr_counting = True
            stat = Statistics(sconf)
            ex = Executor(sconf, stat)
            sstore = StoreManager()
            ex.register_import_object(sstore, _wasi(out))
            sinst = ex.instantiate(sstore, Validator(sconf).validate(
                Loader(sconf).parse_module(_stagger_module())))
            assert ex.invoke(sstore, sinst.find_func("f"), [arg]) == [4]
        assert set(np.asarray(res.retired)[n == arg].tolist()) == \
            {stat.instr_count}


# -- the vectorised accessors of the serve --------------------------------
def _views():
    """The same 2-page plane of 6 lanes behind both MemView backends:
    the SIMT serve's host plane, and the Pallas serve's cache with rows
    cut 'on the device' (a numpy slice here)."""
    from wasmedge_tpu.batch.hostcall import (
        PlaneMemoryCache, make_cached_view)
    from wasmedge_tpu.host.wasi.vectorized import SoAMemView

    rng = np.random.default_rng(3)
    plane = rng.integers(-2 ** 31, 2 ** 31, size=(2 * 16384, 6),
                         dtype=np.int64).astype(np.int32)
    lanes = np.arange(6, dtype=np.int64)
    dev = plane.copy()

    def read_rows(w0, k, lane_major):
        rows = dev[w0:w0 + k]
        return np.ascontiguousarray(rows.T) if lane_major else rows

    cache = PlaneMemoryCache(dev, read_rows=read_rows)
    return (plane, SoAMemView(plane.copy(), lanes, 2),
            make_cached_view(cache, lanes, np.full(6, 2)), cache)


@pytest.mark.parametrize("off,ln", [
    (4096, 64), (4097, 61), (4095 + 3, 8), (1024 * 4 - 8, 24),
    ([8, 12, 1024, 8, 4000, 6], 16)],
    ids=["aligned", "unaligned", "short", "across-chunks", "per-lane"])
def test_gather_matrix_is_gather_bytes(off, ln):
    plane, soa, cached, _cache = _views()
    offs = np.broadcast_to(np.asarray(off, np.int64), (6,)).copy()
    lens = np.full(6, ln, np.int64)
    want = [plane[:, i].tobytes()[int(o):int(o) + ln]
            for i, o in enumerate(offs)]
    for view in (soa, cached):
        assert view.gather_bytes(offs, lens) == want
        m = view.gather_matrix(offs, lens)
        assert m.shape == (6, ln) and [r.tobytes() for r in m] == want
        a, b = view.load_u32x2(offs)
        assert np.array_equal(a, view.load_u32(offs))
        assert np.array_equal(b, view.load_u32(offs + 4))
    assert soa.gather_matrix(offs, np.array([1, 2, 3, 4, 5, 6])) is None


@pytest.mark.parametrize("case", ["aligned-all", "aligned-masked",
                                  "unaligned", "u64", "per-lane"])
def test_vector_stores_are_the_per_lane_stores(case):
    plane, soa, cached, cache = _views()
    offs = np.full(6, 4096 if case != "unaligned" else 4098, np.int64)
    if case == "per-lane":
        offs = np.array([0, 4, 4096, 8192, 65536, 131068], np.int64)
    vals = np.arange(6, dtype=np.uint64) * np.uint64(0x01020304) \
        + np.uint64(0x1_0000_0007 if case == "u64" else 0x8000_0007)
    mask = np.array([1, 1, 0, 1, 0, 1], bool) \
        if case == "aligned-masked" else None
    nbytes = 8 if case == "u64" else 4
    want = plane.copy()
    for i in range(6):
        if mask is not None and not mask[i]:
            continue
        col = bytearray(want[:, i].tobytes())
        col[int(offs[i]):int(offs[i]) + nbytes] = \
            (int(vals[i]) & (2 ** (8 * nbytes) - 1)).to_bytes(
                nbytes, "little")
        want[:, i] = np.frombuffer(bytes(col), np.int32)
    for view in (soa, cached):
        (view.store_u64 if nbytes == 8 else view.store_u32)(
            offs, vals, mask)
    assert np.array_equal(soa.plane, want)
    # the cache: what it would set on the device, and what it reads back
    dev = cache.dev
    for row0, rows in cache.dirty_rows():
        assert rows.shape[0] & (rows.shape[0] - 1) == 0 \
            or case in ("aligned-all", "aligned-masked", "u64")
        dev[row0:row0 + rows.shape[0]] = rows
    assert np.array_equal(dev, want)
    if case.startswith("aligned") or case == "u64":
        # one address in every lane: whole rows, no chunk came down
        assert not cache._chunks
        assert [r for r, _rows in cache.dirty_rows()] == [1024]
    assert cached.gather_bytes(offs, np.full(6, nbytes)) == [
        want[:, i].tobytes()[int(o):int(o) + nbytes]
        for i, o in enumerate(offs)]
    lane = 3
    assert (int(offs[lane]), nbytes) in cache.writes_of(lane)


# -- the cell's files ------------------------------------------------------
def _driver():
    return _bench_module("drivers", "batch_wasi")


def _checker(ref, lanes, blocks, chunk, out_fd):
    driver = _driver()
    workload = _load(BENCH, "workloads", CELL + ".json")
    sizes = {"blocks": blocks, "chunk_blocks": chunk}
    run = types.SimpleNamespace(
        config={"guest": {"args": sizes}}, workload=workload,
        rehearse=True, reference=lambda: ref)
    seeds = np.arange(lanes, dtype=np.int64) * 1000 + 1
    return driver, run, seeds, driver.Checker(run, FUNC, seeds, out_fd)


def _fake_engine(lanes, calls, nbytes, **over):
    counts = dict(splits=0, hostcall_rounds=calls,
                  hostcall_calls=lanes * calls,
                  hostcall_vectorized=lanes * calls,
                  hostcall_out_bytes=nbytes)
    counts.update(over)
    return types.SimpleNamespace(pallas=types.SimpleNamespace(**counts))


@pytest.mark.parametrize("fault", [
    "none", "blocks-in-another-order", "a-write-dropped",
    "a-write-doubled", "a-write-torn", "a-lane-out-of-order",
    "a-short-stream", "a-wrong-result", "a-wrong-count", "a-trap",
    "per-lane-loop", "a-split"])
def test_the_drivers_checker_holds_a_job_to_the_guarantees(ref, fault):
    lanes, blocks, chunk = 5, 6, 2
    calls, rec = blocks // chunk, 64 * chunk
    out_fd = os.memfd_create("test-fd1")
    driver, run, seeds, checker = _checker(ref, lanes, blocks, chunk,
                                           out_fd)
    assert checker.retired == retired(blocks, chunk)
    cells, stream = ref.reference_job(FUNC, seeds, blocks, chunk)
    records = stream.reshape(calls, lanes, rec).copy()
    res = types.SimpleNamespace(
        results=[cells.astype(np.int64)], trap=np.full(lanes, -1),
        retired=np.full(lanes, retired(blocks, chunk)))
    eng = _fake_engine(lanes, calls, stream.size)
    want_bad = 0
    if fault == "blocks-in-another-order":
        # lanes 3, 4 (another block) served before lanes 0..2, every call
        records = records[:, [3, 4, 0, 1, 2]]
    elif fault == "a-write-dropped":
        records[1, 2] = records[2, 2]           # lane 2's call 2 twice
        want_bad = lanes
    elif fault == "a-write-doubled":
        records[2, 4] = records[1, 4]
        want_bad = lanes
    elif fault == "a-write-torn":
        records[1, 3, 40:] = records[1, 2, 40:]
        want_bad = 1
    elif fault == "a-lane-out-of-order":
        records[[0, 1], 1] = records[[1, 0], 1]
        want_bad = 1
    elif fault == "a-wrong-result":
        res.results[0] = res.results[0] ^ np.array([0, 0, 1, 0, 0])
        want_bad = 1
    elif fault == "a-wrong-count":
        res.retired[0] += 2     # the import's stub counted
        want_bad = 1
    elif fault == "a-trap":
        res.trap[4] = int(ErrCode.Unreachable)
        want_bad = 1
    elif fault == "per-lane-loop":
        eng = _fake_engine(lanes, calls, stream.size,
                           hostcall_vectorized=0)
        want_bad = lanes
    elif fault == "a-split":
        eng = _fake_engine(lanes, calls, stream.size, splits=1)
        want_bad = lanes
    data = records.tobytes()
    if fault == "a-short-stream":
        data = data[:-rec]
        want_bad = lanes
    checker.rewind()
    os.write(out_fd, data)
    bad, total, one = checker.bad_lanes(res, eng)
    assert bad == want_bad
    assert one == int(res.retired[0]) and total == int(res.retired.sum())
    # a second job on the same fd: rewound, mapped anew
    checker.rewind()
    os.write(out_fd, stream.tobytes())
    res.results[0] = cells.astype(np.int64)
    res.trap[:] = -1
    res.retired[:] = retired(blocks, chunk)
    assert checker.bad_lanes(
        res, _fake_engine(lanes, calls, stream.size))[0] == 0
    assert len(checker.check_s) == 2
    # the file is not truncated between jobs: a job that writes nothing,
    # or stops short, leaves the last job's right bytes, and is wrong
    for part in (b"", stream.tobytes()[:-rec]):
        checker.rewind()
        os.write(out_fd, part)
        assert checker.bad_lanes(
            res, _fake_engine(lanes, calls, stream.size))[0] == lanes
    checker.rewind()
    os.write(out_fd, stream.tobytes())
    assert checker.bad_lanes(
        res, _fake_engine(lanes, calls, stream.size))[0] == 0
    os.close(out_fd)
    # at the listed sizes the formula must give the pinned constants
    run.rehearse = False
    with pytest.raises(RuntimeError, match="retired_per_lane"):
        driver.Checker(run, FUNC, seeds, out_fd)


def test_the_driver_names_its_counters_and_finds_the_builder_first():
    driver = _driver()
    seeded = _bench_module("drivers", "batch_seeded")
    assert driver.ENGINE_COUNTERS == seeded.ENGINE_COUNTERS + (
        "simd_ops", "hostcall_rounds", "hostcall_calls",
        "hostcall_vectorized", "hostcall_out_bytes")
    assert driver.sizes_of({"guest": {"args": {
        "blocks": BLOCKS, "chunk_blocks": CHUNK}}})["calls"] == 24
    # a program that lacks the builder ends before it touches the device
    with pytest.raises(AttributeError, match="build_no_such_guest"):
        driver.seeded.guest_builder(
            {"guest": {"builder": "build_no_such_guest"}})


def test_the_cell_pins_the_sizes_and_the_counts():
    config = _load(BENCH, "configs", "chacha20-wasi-4096.json")
    workload = _load(BENCH, "workloads", CELL + ".json")
    assert config["guest"] == {
        "builder": "build_chacha20_wasi", "export": FUNC,
        "args": {"blocks": BLOCKS, "chunk_blocks": CHUNK}}
    assert config["driver"] == "batch_wasi" and config["family"] == "batch"
    assert config["reference"] == "chacha20_wasi"
    # the whole host, for steadiness alone (chips_is)
    assert config["lanes"] == 4096 and config["chips"] == 4
    assert config["reduced"] == [] and config["architecture"] is None
    simd = _load(BENCH, "configs", "chacha20-simd-4096.json")
    assert config["geometry"] == simd["geometry"]
    assert len(config["guarantees"]) == 5
    expected = workload["expected"]
    assert expected["retired_per_lane"] == retired(BLOCKS, CHUNK) == 5241609
    assert expected["retired_formula"] == {"1": 129, "blocks": 1706,
                                           "calls": 27}
    assert expected["hostcalls_per_lane"] == BLOCKS // CHUNK == 24
    assert expected["out_bytes_per_lane"] == 64 * BLOCKS == 196608
    assert 4096 * 24 == 98304 and 4096 * 196608 == 805306368
    assert workload["traffic"] == {
        "func": FUNC, "args": {"kind": "distinct", "below": 1 << 20},
        "max_steps": 2000000000, "trace_jobs": 3}
    assert build_chacha20_wasi() == build_chacha20_wasi(BLOCKS, CHUNK)
    with pytest.raises(ValueError):
        build_chacha20_wasi(10, 4)
    rehearse = config["rehearse"]
    assert rehearse["guest"]["args"] == {"blocks": 8, "chunk_blocks": 2}
    assert rehearse["lanes"] == 16 and rehearse["geometry"]["mem_hbm"]
    manifest = _load(ROOT, "BENCHMARK.json")
    # the cell, its configuration and its seven metrics, in the place
    # they were appended to (later entries come after them)
    names = [w["name"] for w in manifest["workloads"]]
    cell = manifest["workloads"][names.index(CELL)]
    assert names[:names.index(CELL)] == [
        "batch-fib30-uniform", "batch-mem-uniform", "batch-fib-divergent",
        "batch-fib-split", "batch-gemm-small", "batch-chacha20-192k"]
    assert cell["chips"] == 4 and cell["config"] == config["name"]
    assert "steadiness alone" in cell["why"] and len(cell["why"]) <= 200
    assert cell["traffic"] == "chacha20-192k-write8k-distinct-seeds"
    assert [c["name"] for c in manifest["configs"]].index(
        config["name"]) == 5
    hostcall = [m for m in manifest["per_layer"]
                if m["name"].startswith(("hostcall", "host_hostcall"))]
    assert [m["name"] for m in hostcall] == [
        "hostcall_rounds_per_job.batch", "hostcalls_per_job.batch",
        "hostcall_out_bytes_per_job.batch",
        "hostcall_vectorized_share.batch", "hostcall_begin_ms.batch",
        "hostcall_finish_ms.batch", "host_hostcall_ms.batch"]
    for m in hostcall:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "batch_ginstr_per_s"
    # whatever batch-chacha20-192k reports, this cell reports
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "batch-chacha20-192k" in m.get("workloads", ()):
            assert CELL in m["workloads"]


def test_the_guest_is_build_chacha20_with_the_write_added():
    """One import, one more local, and between the block loop and the
    fold one call site: two `i32.store`s for the iovec, the call, one
    `i32.load` of nwritten, two `unreachable`s."""
    from tests.helpers import load_validate

    plain = load_validate(build_chacha20(4))
    wasi = load_validate(build_chacha20_wasi(4, 2))
    assert [(i.module, i.name) for i in wasi.imports] == [(WASI, "fd_write")]
    assert not plain.imports
    assert {e.name for e in wasi.exports} == {FUNC}
    _ex, _store, inst = instantiate(
        build_chacha20_wasi(), imports=[WasiModule()])
    assert inst.memories[0].pages == 4


def test_the_cell_rehearses_on_the_cpu():
    """`run.py --rehearse`: the cell's whole path at the rehearsal's
    sizes, the traced slice included, every value null."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse", "--trace", "1", "--seed", "2147483659"],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] % 16 == 0 and last["attempted"] >= 48
    for name in ("hostcall_rounds_per_job.batch", "hostcalls_per_job.batch",
                 "hostcall_out_bytes_per_job.batch",
                 "hostcall_vectorized_share.batch",
                 "simd_ops_per_job.batch", "splits_per_job.batch"):
        assert name in last["metrics"]
    assert all(m["value"] is None for m in last["metrics"].values())
    warm = next(x for x in lines if "hostcall_rounds" in x)
    assert (warm["hostcall_rounds"], warm["hostcall_calls"],
            warm["hostcall_vectorized"], warm["hostcall_out_bytes"]) == \
        (4, 64, 64, 16 * 64 * 8)
    assert warm["lane_steps"] == retired(8, 2)
    assert warm["splits"] == 0 and warm["bad_lanes"] == 0
    note = next(x for x in lines if "check_s" in x)
    assert len(note["check_s"]) == 3 and note["compiled_in_window"] == []
