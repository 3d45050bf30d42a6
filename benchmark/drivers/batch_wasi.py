"""drivers/batch_wasi.py: `drivers/batch_seeded.py`'s jobs, back to back
on one engine build, for a guest that is a WASI command: it imports
`wasi_snapshot_preview1.fd_write` and writes its output to fd 1, so a
job's answer is every lane's 64-bit result AND the bytes on fd 1.

What it adds to the seeded driver (loaded for the guest's builder looked
up before anything touches the device, the distinct seeds, the retired
formula and the window's DMA bytes):

- the engine build with a `WasiModule` registered: one environ and one
  fd table for all the lanes of the engine, fd 1 a memfd of the
  driver's (anonymous memory: no path, nothing beside the checkout)
  that is rewound before every job and must be written from its start
  to its end, to the byte, by it (`Checker.rewind`);
- a checker that holds a job to the configuration's five guarantees:
  every lane's 64 bits, `trap == -1` and the retired count (the seeded
  checker's three); the bytes on fd 1, all of them, against
  `reference_job`'s stream (asked once before the window, with the
  lanes' results, from one pass over the ciphertext): `np.array_equal`
  over a memory map of fd 1, and where that is unequal by the
  permutation: each record (one `fd_write`'s bytes, contiguous) is
  matched to its (lane, call) by its first 16 bytes, compared whole,
  every (lane, call) must be there once, and a lane's calls must come in
  the lane's order, so that another order of blocks is not called
  wrong and a write dropped, doubled, torn or out of its lane's order
  is; no split and no fall-back; every call served by the tier-1
  vectorised `fd_write` (`hostcall_vectorized` = `hostcall_calls` =
  lanes x calls a job, `hostcall_rounds` = calls);
- `check_s` in the notes: the seconds a job's check took (it runs
  inside the window, after `eng.run`, as every batch driver's does);
- the engine counters of the seeded driver, `simd_ops`, and the four
  of the hostcall serve: `hostcall_rounds`, `hostcall_calls`,
  `hostcall_vectorized`, `hostcall_out_bytes`.  A program that lacks a
  counter leaves it out, and the metric that reads it is left out of
  the line.
"""

import mmap
import os
import time

import numpy as np

import harness

seeded = harness.load_module("drivers", "batch_seeded")

ENGINE_COUNTERS = seeded.ENGINE_COUNTERS + (
    "simd_ops", "hostcall_rounds", "hostcall_calls",
    "hostcall_vectorized", "hostcall_out_bytes")
KEY_BYTES = 16      # a record is matched by its first bytes
COMPARE_BYTES = 8 << 20     # the streams are compared a piece at a time,
#                             so that no stream-sized temporary is made


def build_engine(config, builder, out_fd):
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.host.wasi import WasiModule
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf = Configure()
    for key, value in config["geometry"].items():
        setattr(conf.batch, key, value)
    wasm = builder(**config["guest"].get("args", {}))
    mod = Validator(conf).validate(Loader(conf).parse_module(wasm))
    store = StoreManager()
    wasi = WasiModule()
    wasi.init_wasi()
    wasi.env.fds[1].os_fd = out_fd
    ex = Executor(conf)
    ex.register_import_object(store, wasi)
    inst = ex.instantiate(store, mod)
    return UniformBatchEngine(inst, store=store, conf=conf,
                              lanes=config["lanes"])


def sizes_of(config):
    """The guest's sizes, and `calls`, the `fd_write`s a lane makes."""
    sizes = dict(config["guest"].get("args", {}))
    sizes["calls"] = sizes["blocks"] // sizes["chunk_blocks"]
    return sizes


class Checker:
    """A job against the five guarantees; see the module's text."""

    def __init__(self, run, func, args, out_fd):
        config = run.config
        self.sizes = sizes_of(config)
        self.lanes = len(args)
        self.out_fd = out_fd
        cells, stream = run.reference().reference_job(
            func, args, **config["guest"].get("args", {}))
        self.expect = np.asarray(cells).astype(np.uint64)
        self.stream = stream
        self.record = 64 * self.sizes["chunk_blocks"]
        expected = run.workload.get("expected", {})
        self.retired = seeded.formula(expected["retired_formula"],
                                      self.sizes)
        if not run.rehearse:
            for key, got in (
                    ("retired_per_lane", self.retired),
                    ("hostcalls_per_lane", self.sizes["calls"]),
                    ("out_bytes_per_lane",
                     self.record * self.sizes["calls"])):
                if got != expected[key]:
                    raise RuntimeError(
                        f"expected.{key} is {expected[key]}, the sizes "
                        f"{self.sizes} give {got}")
        self.check_s = []
        self._keys = None
        self._map = None

    def rewind(self):
        """fd 1 at 0, before a job.  The file keeps its pages: a job
        overwrites them from the start, one `os.write` after another,
        so the offset after it says how much of the file is this job's
        (`_written` holds it to all of it).  Truncating instead would
        have the kernel free and allocate 196,608 pages a job inside
        the window, which is the host's noisiest work and no part of
        the system under test."""
        os.lseek(self.out_fd, 0, os.SEEK_SET)

    def _reset(self):
        """fd 1 empty and unmapped: after a job that left it wrong."""
        if self._map is not None:
            self._map.close()
            self._map = None
        os.ftruncate(self.out_fd, 0)

    def _written(self):
        """The bytes on fd 1, all of them this job's, or None."""
        size = os.fstat(self.out_fd).st_size
        if os.lseek(self.out_fd, 0, os.SEEK_CUR) != size \
                or size != self.stream.size or size == 0:
            return None
        if self._map is None:
            # every page mapped by the one call: a fault a page as the
            # comparison reaches it costs ten times the comparison
            self._map = mmap.mmap(
                self.out_fd, size, prot=mmap.PROT_READ,
                flags=mmap.MAP_SHARED | getattr(mmap, "MAP_POPULATE", 0))
        return np.frombuffer(self._map, np.uint8)

    def bad_stream_lanes(self):
        """Lanes whose writes are not on fd 1 once, whole, bit-exact and
        in the lane's order (every lane, where a record is no lane's)."""
        got = self._written()
        want = self.stream
        if got is None:     # short, long, or not written from the start
            self._reset()
            return self.lanes
        if all(np.array_equal(got[i:i + COMPARE_BYTES],
                              want[i:i + COMPARE_BYTES])
               for i in range(0, want.size, COMPARE_BYTES)):
            return 0
        # another order of records may be right: decide by permutation
        rec, lanes = self.record, self.lanes
        got = got.reshape(-1, rec)
        want = want.reshape(-1, rec)        # record r * lanes + lane
        if self._keys is None:
            self._keys = {bytes(k): i
                          for i, k in enumerate(want[:, :KEY_BYTES])}
            if len(self._keys) != len(want):
                raise RuntimeError("two records share their first "
                                   f"{KEY_BYTES} bytes: choose other seeds")
        index = np.array([self._keys.get(bytes(k), -1)
                          for k in got[:, :KEY_BYTES]], np.int64)
        if (index < 0).any() or len(set(index.tolist())) != len(index):
            return lanes    # a record that is no lane's, or one twice
        bad = np.zeros(lanes, bool)
        lane, call = index % lanes, index // lanes
        torn = (got != want[index]).any(axis=1)
        bad[lane[torn]] = True
        order = np.argsort(lane, kind="stable")   # stream order a lane
        calls = call[order].reshape(lanes, -1)
        bad[(np.diff(calls, axis=1) <= 0).any(axis=1)] = True
        return int(bad.sum())

    def bad_lanes(self, res, eng):
        """-> (bad lanes, retired instructions, one lane's retired)"""
        t0 = time.monotonic()
        got = np.asarray(res.results[0]).astype(np.uint64)
        retired = np.asarray(res.retired).astype(np.int64)
        bad = int(((np.asarray(res.trap) != -1) | (got != self.expect)
                   | (retired != self.retired)).sum())
        bad = max(bad, self.bad_stream_lanes())
        calls = self.lanes * self.sizes["calls"]
        pallas = eng.pallas
        if (pallas.splits, pallas.hostcall_rounds, pallas.hostcall_calls,
                pallas.hostcall_vectorized, pallas.hostcall_out_bytes) != \
                (0, self.sizes["calls"], calls, calls, self.stream.size):
            bad = self.lanes    # not the deployment the cell lists
        self.check_s.append(time.monotonic() - t0)
        return bad, int(retired.sum()), int(retired[0])


def run(run):
    builder = seeded.guest_builder(run.config)
    import jax

    device = run.device()
    config, traffic = run.config, run.traffic
    out_fd = os.memfd_create("batch-wasi-fd1")
    eng = build_engine(config, builder, out_fd)
    if eng.pallas is None or not eng.pallas.eligible:
        raise RuntimeError("the batch engine took no Pallas kernel: "
                           f"{getattr(eng.pallas, 'ineligible_reason', '')}")
    if eng.pallas._interpret() != run.rehearse:
        raise RuntimeError("the Pallas kernel is in interpret mode"
                           if not run.rehearse else
                           "a rehearsal runs the kernel in interpret mode")
    func = traffic["func"]
    lanes = config["lanes"]
    args = seeded.lane_args(traffic["args"], lanes, run.seed)
    t_ref = time.monotonic()
    checker = Checker(run, func, args, out_fd)
    run.note(reference_s=time.monotonic() - t_ref,
             stream_bytes=int(checker.stream.size))
    span = jax.profiler.TraceAnnotation   # costs nothing while no trace runs

    def job():
        """-> (start, end, retired instructions, bad lanes, steps,
        {counter: this job's})"""
        checker.rewind()
        t0 = time.monotonic()
        with span("bench/job"):
            res = eng.run(func, [args], max_steps=traffic["max_steps"])
        t1 = time.monotonic()
        if eng.fell_back_to_simt:
            raise RuntimeError("the job fell back to the SIMT engine")
        with span("bench/check"):
            bad, retired, lane_steps = checker.bad_lanes(res, eng)
        counts = {name: int(getattr(eng.pallas, name))
                  for name in ENGINE_COUNTERS
                  if getattr(eng.pallas, name, None) is not None}
        counts["lane_steps"] = lane_steps
        return (t0, t1, retired, bad, int(res.steps), counts)

    warm = job()    # compiles, or loads from the cache: set-up
    run.note(warm_up_s=warm[1] - warm[0], bad_lanes=warm[3], device=device,
             mem_static=getattr(eng.pallas, "mem_static", None), **warm[5])

    t0 = run.start_window()
    jobs = [job()]
    while time.monotonic() - t0 < run.seconds:
        jobs.append(job())
    t_end = jobs[-1][1]
    window_s = t_end - t0
    compiled = run.compiles_between(t0, t_end)
    counters = run.obs["counters"]
    counters.update(jobs=len(jobs), lanes=lanes, window_s=window_s,
                    compiles=len(compiled))
    for name in warm[5]:
        counters[name] = sum(j[5][name] for j in jobs)
    run.obs["samples"]["job_s"] = [j[1] - j[0] for j in jobs]
    check_s = checker.check_s[1:]   # the window's jobs

    traced = []
    if run.trace:   # a slice of its own, after the window
        with run.traced_slice():
            traced = [job() for _ in range(traffic["trace_jobs"])]
        counters.update(trace_jobs=len(traced),
                        trace_steps=sum(j[4] for j in traced))
        for name in ("window_fills", "window_writebacks"):
            if name in warm[5]:
                counters["trace_" + name] = sum(j[5][name] for j in traced)
        peaks = harness.load_json(harness.HERE, "peaks.json")["devices"]
        dma_bytes = seeded.window_dma_bytes(eng)
        if dma_bytes is not None and device["kind"] in peaks:
            counters.update(
                window_dma_bytes=dma_bytes,
                hbm_bytes_per_s=peaks[device["kind"]]["hbm_bytes_per_s"])

    run.attempted = lanes * (len(jobs) + len(traced))
    run.failed = sum(j[3] for j in [warm] + jobs + traced)
    run.correct = run.failed == 0
    retired = sum(j[2] for j in jobs)
    run.values["batch_ginstr_per_s"] = retired / window_s / 1e9
    job_s = run.obs["samples"]["job_s"]
    slow = 1.5 * sorted(job_s)[len(job_s) // 2]
    run.note(jobs=len(jobs), window_s=window_s,
             job_s_min=min(job_s), job_s_max=max(job_s),
             check_s=[min(check_s), sorted(check_s)[len(check_s) // 2],
                      max(check_s)],
             # a job far over the median is the machine's pause (PERF.md
             # section 6): which job, and how long it took
             slow_jobs=[[i, s] for i, s in enumerate(job_s) if s > slow],
             retired=retired, steps=jobs[0][4],
             counters_a_job={name: sorted({j[5][name] for j in jobs})
                             for name in warm[5]},
             compiled_in_window=compiled)
