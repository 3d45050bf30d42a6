"""drivers/batch_seeded.py: `drivers/batch.py`'s jobs, back to back on the
same engine build, for a guest whose lane argument is a seed: it changes
the data a lane computes on and never its control flow, every lane has
another, and the answer is 64 bits wide.

What it adds to `drivers/batch.py` (imported for the other argument
kinds; `build_engine` here is its steps with the guest's builder
arguments passed on):

- `guest.args` in the configuration: keyword arguments of the builder
  (the sizes of the guest), also handed to the reference;
- the lane-argument kind {kind: distinct, below}: one value a lane below
  `below`, drawn without replacement from the seed;
- a checker that asks the reference once for all lanes before the
  window (`reference_lanes(func, lane_args, **guest.args)`), compares
  all 64 bits of every lane, and holds every lane to
  `expected.retired_per_lane` and to `expected.retired_formula`
  (coefficients of the products of the guest's sizes, so it holds at
  the rehearsal's sizes too) and to `trap == -1`;
- counters summed job by job over the window, as `batch_split.py` sums
  its own: `splits`, `dispatches`, `window_fills`, `window_writebacks`,
  `window_accesses`, `softfloat_ops` off the engine, and `lane_steps`,
  the instructions one lane retired.  A program that lacks a counter
  (the parent of the PR that adds it) leaves it out, and the metric
  that reads it is left out of the line;
- over the traced slice, for `readers/window_hbm_share.py`: the traced
  jobs' own `trace_window_fills` and `trace_window_writebacks`, the
  bytes one of those DMAs moves (`window_dma_bytes`: the window's rows
  x the lane block x 4) and the device's `hbm_bytes_per_s` out of
  peaks.json.
"""

import math
import time

import numpy as np

import harness

batch = harness.load_module("drivers", "batch")

ENGINE_COUNTERS = ("splits", "dispatches", "window_fills",
                   "window_writebacks", "window_accesses", "softfloat_ops")


def guest_builder(config):
    """The guest's builder, before anything touches the device: a
    program that lacks it ends here, at once."""
    import wasmedge_tpu.models as models

    return getattr(models, config["guest"]["builder"])


def build_engine(config, builder):
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf = Configure()
    for key, value in config["geometry"].items():
        setattr(conf.batch, key, value)
    wasm = builder(**config["guest"].get("args", {}))
    mod = Validator(conf).validate(Loader(conf).parse_module(wasm))
    store = StoreManager()
    inst = Executor(conf).instantiate(store, mod)
    return UniformBatchEngine(inst, store=store, conf=conf,
                              lanes=config["lanes"])


def lane_args(spec, lanes, seed):
    if spec["kind"] != "distinct":
        return batch.lane_args(spec, lanes, seed)
    return np.random.default_rng(seed).choice(
        spec["below"], size=lanes, replace=False).astype(np.int64)


def formula(coefficients, sizes):
    """{"1": 24, "ni*nj": 60, ...} at the guest's sizes."""
    return sum(c * math.prod(sizes[k] for k in term.split("*") if k != "1")
               for term, c in coefficients.items())


class Checker:
    """Every lane's 64 bits against the plain reference, asked once for
    all lanes, and every lane's retired count against the constant."""

    def __init__(self, run, func, args):
        sizes = run.config["guest"].get("args", {})
        self.expect = np.asarray(run.reference().reference_lanes(
            func, args, **sizes)).astype(np.uint64)
        expected = run.workload.get("expected", {})
        self.retired = formula(expected["retired_formula"], sizes)
        if not run.rehearse and \
                self.retired != expected["retired_per_lane"]:
            raise RuntimeError(
                f"expected.retired_formula gives {self.retired} at "
                f"{sizes}, retired_per_lane {expected['retired_per_lane']}")

    def bad_lanes(self, res):
        got = np.asarray(res.results[0]).astype(np.uint64)
        retired = np.asarray(res.retired).astype(np.int64)
        bad = (np.asarray(res.trap) != -1) | (got != self.expect) \
            | (retired != self.retired)
        return int(bad.sum()), int(retired.sum()), int(retired[0])


def window_dma_bytes(eng):
    """The bytes one fill or write-back moves: the window's rows x the
    lane block x 4, read off the engine's own `mem_static`."""
    static = getattr(eng.pallas, "mem_static", None) or {}
    if static.get("mem_mode") != "hbm_window":
        return None
    rows = int(static["window"].split("x")[0])
    return rows * int(static["lane_block"]) * 4


def run(run):
    builder = guest_builder(run.config)
    import jax

    device = run.device()
    config, traffic = run.config, run.traffic
    eng = build_engine(config, builder)
    if eng.pallas is None or not eng.pallas.eligible:
        raise RuntimeError("the batch engine took no Pallas kernel: "
                           f"{getattr(eng.pallas, 'ineligible_reason', '')}")
    if eng.pallas._interpret() != run.rehearse:
        raise RuntimeError("the Pallas kernel is in interpret mode"
                           if not run.rehearse else
                           "a rehearsal runs the kernel in interpret mode")
    func = traffic["func"]
    lanes = config["lanes"]
    args = lane_args(traffic["args"], lanes, run.seed)
    t_ref = time.monotonic()
    checker = Checker(run, func, args)
    run.note(reference_s=time.monotonic() - t_ref)
    span = jax.profiler.TraceAnnotation   # costs nothing while no trace runs

    def job():
        """-> (start, end, retired instructions, bad lanes, steps,
        {counter: this job's})"""
        t0 = time.monotonic()
        with span("bench/job"):
            res = eng.run(func, [args], max_steps=traffic["max_steps"])
        t1 = time.monotonic()
        with span("bench/check"):
            bad, retired, lane_steps = checker.bad_lanes(res)
        if eng.fell_back_to_simt:
            raise RuntimeError("the job fell back to the SIMT engine")
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
        dma_bytes = window_dma_bytes(eng)
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
             # a job far over the median is the machine's pause (PERF.md
             # section 6): which job, and how long it took
             slow_jobs=[[i, s] for i, s in enumerate(job_s) if s > slow],
             retired=retired, steps=jobs[0][4],
             counters_a_job={name: sorted({j[5][name] for j in jobs})
                             for name in warm[5]},
             compiled_in_window=compiled)
