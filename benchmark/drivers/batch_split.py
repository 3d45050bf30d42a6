"""drivers/batch_split.py: `drivers/batch.py`'s jobs, back to back on the
same engine build, with lane arguments that entry grouping cannot group,
so that one block splits in flight.

What it adds to `drivers/batch.py` (imported for `build_engine`, the
other argument kinds and the `Checker`):

- the lane-argument kind {kind: quarter_unique, lo, hi}: lane i with
  i % 4 == 0 gets n = -1 - i // 4, a value of its own below 2 (the guest
  answers n at once); the other three quarters get lo..hi dealt evenly
  and shuffled among themselves from the seed, so every seed has the
  same set of sizes;
- a checker that also holds every lane below 2 to
  `expected.retired_below_2`, at the rehearsal's sizes too (a leaf call
  retires the same at any size);
- counters summed job by job over the window: `splits`, `launches`,
  `rechecks`, `careful_steps`.  `eng.pallas.splits` is overwritten by
  every run, so with the same arguments in every job a difference
  between the window's end and its start reads 0 whatever happened.  A
  program that lacks a counter (the parent of the PR that adds it) leaves
  it out, and the metric that reads it is left out of the line.
"""

import time

import numpy as np

import harness

batch = harness.load_module("drivers", "batch")

ENGINE_COUNTERS = ("splits", "launches", "rechecks", "careful_steps")


def lane_args(spec, lanes, seed):
    if spec["kind"] != "quarter_unique":
        return batch.lane_args(spec, lanes, seed)
    lane = np.arange(lanes)
    unique = lane % 4 == 0
    span = spec["hi"] - spec["lo"] + 1
    rest = (spec["lo"] + np.arange(lanes - unique.sum()) % span) \
        .astype(np.int64)
    np.random.default_rng(seed).shuffle(rest)
    args = -1 - lane // 4
    args[~unique] = rest
    return args.astype(np.int64)


class Checker(batch.Checker):
    """`batch.Checker` with one constant for all the arguments below 2,
    and the agreement between lanes of one argument in one pass (there
    are over a thousand arguments here, and the check runs inside the
    window)."""

    def __init__(self, run, func, args):
        super().__init__(run, func, args)
        below = run.workload.get("expected", {}).get("retired_below_2")
        if below is not None:
            self.retired[args < 2] = below
        _, first, group = np.unique(args, return_index=True,
                                    return_inverse=True)
        self.first = first[group]   # a lane -> the first of its argument

    def bad_lanes(self, res):
        got = np.asarray(res.results[0]).astype(np.uint64) \
            & np.uint64(0xFFFFFFFF)
        retired = np.asarray(res.retired).astype(np.int64)
        bad = (np.asarray(res.trap) != -1) | (got != self.expect)
        bad |= (self.retired >= 0) & (retired != self.retired)
        bad |= retired != retired[self.first]   # same work, same count
        return int(bad.sum()), int(retired.sum())


def run(run):
    import jax

    device = run.device()
    config, traffic = run.config, run.traffic
    eng = batch.build_engine(config)
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
        """-> (start, end, retired instructions, bad lanes, steps,
        {engine counter: this job's})"""
        t0 = time.monotonic()
        with span("bench/job"):
            res = eng.run(func, [args], max_steps=traffic["max_steps"])
        t1 = time.monotonic()
        with span("bench/check"):
            bad, retired = checker.bad_lanes(res)
        if eng.fell_back_to_simt:
            raise RuntimeError("the job fell back to the SIMT engine")
        counts = {name: int(getattr(eng.pallas, name))
                  for name in ENGINE_COUNTERS if hasattr(eng.pallas, name)}
        return (t0, t1, retired, bad, int(res.steps), counts)

    warm = job()    # compiles, or loads from the cache: set-up
    run.note(warm_up_s=warm[1] - warm[0], bad_lanes=warm[3], device=device,
             **warm[5])

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

    run.attempted = lanes * (len(jobs) + len(traced))
    run.failed = sum(j[3] for j in [warm] + jobs + traced)
    run.correct = run.failed == 0
    retired = sum(j[2] for j in jobs)
    run.values["batch_ginstr_per_s"] = retired / window_s / 1e9
    run.note(jobs=len(jobs), window_s=window_s,
             job_s_min=min(run.obs["samples"]["job_s"]),
             job_s_max=max(run.obs["samples"]["job_s"]),
             retired=retired, steps=jobs[0][4],
             splits_a_job=sorted({j[5]["splits"] for j in jobs}),
             compiled_in_window=compiled)
