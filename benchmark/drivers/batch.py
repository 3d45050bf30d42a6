"""drivers/batch.py: offline batch jobs, back to back, on the engine that
`VM.execute_batch` builds (`UniformBatchEngine`), which must take the
Pallas kernel, compiled, with no fall-back to SIMT.

Configuration keys: guest {builder, export}, lanes, geometry
{value_stack_depth, call_stack_depth, steps_per_launch}, reference.
Traffic keys: func, args {kind: uniform, value} | {kind: range, lo, hi}
(one argument a lane; `range` deals lo..hi evenly over the lanes and
shuffles them from the seed, so every seed has the same set of sizes),
max_steps, trace_jobs.  `expected.retired_by_arg` in the cell's file holds
the scalar engine's instruction counts.

The geometry and the engine build are copied from chip_smoke.py
(`_bench_conf`, `_instantiate`, `_engine`): the program may change under
the yardstick, the yardstick may not.
"""

import time

import numpy as np


def build_engine(config):
    import wasmedge_tpu.models as models
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    conf = Configure()
    for key, value in config["geometry"].items():
        setattr(conf.batch, key, value)
    wasm = getattr(models, config["guest"]["builder"])()
    mod = Validator(conf).validate(Loader(conf).parse_module(wasm))
    store = StoreManager()
    inst = Executor(conf).instantiate(store, mod)
    return UniformBatchEngine(inst, store=store, conf=conf,
                              lanes=config["lanes"])


def lane_args(spec, lanes, seed):
    if spec["kind"] == "uniform":
        return np.full(lanes, spec["value"], np.int64)
    if spec["kind"] == "range":
        span = spec["hi"] - spec["lo"] + 1
        args = (spec["lo"] + np.arange(lanes) % span).astype(np.int64)
        np.random.default_rng(seed).shuffle(args)
        return args
    raise ValueError(f"unknown lane argument kind {spec['kind']!r}")


class Checker:
    """Every lane against the plain reference, and its retired count
    against the scalar engine's constant for its argument."""

    def __init__(self, run, func, args):
        ref = run.reference().reference
        self.args = args
        self.expect = np.zeros(args.shape, np.uint64)
        self.retired = np.full(args.shape, -1, np.int64)
        counts = run.workload.get("expected", {}).get("retired_by_arg", {})
        for arg in np.unique(args):
            lanes = args == arg
            self.expect[lanes] = ref(func, [int(arg)])[0]
            self.retired[lanes] = counts.get(str(int(arg)), -1)
        if run.rehearse:    # other sizes: only agreement between lanes
            self.retired[:] = -1

    def bad_lanes(self, res):
        got = np.asarray(res.results[0]).astype(np.uint64) \
            & np.uint64(0xFFFFFFFF)
        retired = np.asarray(res.retired).astype(np.int64)
        bad = (np.asarray(res.trap) != -1) | (got != self.expect)
        bad |= (self.retired >= 0) & (retired != self.retired)
        for arg in np.unique(self.args):    # same work, same count
            lanes = self.args == arg
            bad |= lanes & (retired != retired[lanes][0])
        return int(bad.sum()), int(retired.sum())


def run(run):
    import jax

    device = run.device()
    config, traffic = run.config, run.traffic
    eng = build_engine(config)
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
    checker = Checker(run, func, args)
    span = jax.profiler.TraceAnnotation   # costs nothing while no trace runs

    def job():
        """-> (start, end, retired instructions, bad lanes, steps)"""
        t0 = time.monotonic()
        with span("bench/job"):
            res = eng.run(func, [args], max_steps=traffic["max_steps"])
        t1 = time.monotonic()
        with span("bench/check"):
            bad, retired = checker.bad_lanes(res)
        if eng.fell_back_to_simt:
            raise RuntimeError("the job fell back to the SIMT engine")
        return (t0, t1, retired, bad, int(res.steps))

    warm = job()    # compiles, or loads from the cache: set-up
    run.note(warm_up_s=warm[1] - warm[0], bad_lanes=warm[3], device=device)

    splits0 = int(eng.pallas.splits)
    t0 = run.start_window()
    jobs = [job()]
    while time.monotonic() - t0 < run.seconds:
        jobs.append(job())
    t_end = jobs[-1][1]
    window_s = t_end - t0
    compiled = run.compiles_between(t0, t_end)
    counters = run.obs["counters"]
    counters.update(jobs=len(jobs), lanes=lanes, window_s=window_s,
                    splits=int(eng.pallas.splits) - splits0,
                    compiles=len(compiled))
    run.obs["samples"]["job_s"] = [j[1] - j[0] for j in jobs]

    traced = []
    if run.trace:   # a slice of its own, after the window
        with run.traced_slice():
            traced = [job() for _ in range(traffic["trace_jobs"])]
        counters.update(trace_jobs=len(traced),
                        trace_steps=sum(j[4] for j in traced))

    run.attempted = lanes * (len(jobs) + len(traced))
    run.failed = sum(j[3] for j in [warm] + jobs + traced)
    run.correct = run.failed == 0
    retired = sum(j[2] for j in jobs)
    run.values["batch_ginstr_per_s"] = retired / window_s / 1e9
    run.note(jobs=len(jobs), window_s=window_s,
             job_s_min=min(run.obs["samples"]["job_s"]),
             job_s_max=max(run.obs["samples"]["job_s"]),
             retired=retired, steps=jobs[0][4],
             compiled_in_window=compiled)
