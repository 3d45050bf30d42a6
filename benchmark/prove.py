#!/usr/bin/env python3
"""prove.py: the proof of a cell, in one call to the chip.

    chiprun --timeout 3000 -- python3 benchmark/prove.py \
        --cells batch-fib30-uniform,gateway-fib-closed1024 --sets 1 --runs 6

For each cell in order: one cold run (the first in a checkout compiles),
then `--sets` sets of `--runs` runs with `--trace 0`, every run of a set
with another seed and every set with the same seeds, then one run with
`--trace 1`.  Each run is a new process started as BENCHMARK.json's
`command` says; this parent never touches jax, so it never holds the chip.
Every run's last line goes into `<out>/runs.jsonl` with its cell, set and
seed, its whole stdout and stderr into `<out>/<cell>/`, and the spreads the
contract asks for (the distance between the quartiles of
`statistics.quantiles(values, n=4)` as a share of the median, per set) are
printed at the end and written to `<out>/summary.json`.

`--checkout <dir>` runs from another copy of the tree, such as one unpacked
from `git archive $(git write-tree)`.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
COLD_TIMEOUT_S = 1200.0     # the first run in a checkout compiles
SEEDS = [2147483659, 1790468867, 987654321, 2025092801, 1357924680,
         2147480001, 31337, 1234567891, 424242, 2000000011]


def one_run(cmd, cwd, log, timeout_s):
    t0 = time.monotonic()
    try:
        proc = subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                              timeout=timeout_s)
    except subprocess.TimeoutExpired as e:
        proc = subprocess.CompletedProcess(
            cmd, 124, (e.stdout or b"").decode(errors="replace"),
            (e.stderr or b"").decode(errors="replace"))
    with open(log + ".out", "w") as f:
        f.write(proc.stdout)
    with open(log + ".err", "w") as f:
        f.write(proc.stderr)
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1]) if lines else {}
    except ValueError:
        last = {}
    return {"rc": proc.returncode, "wall_s": time.monotonic() - t0,
            "line": last}


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--no-cold", action="store_true")
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="pass --rehearse on: the script's own rehearsal")
    ap.add_argument("--checkout", default=os.path.dirname(HERE))
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(HERE), "chiprun_out", "prove"))
    ap.add_argument("--timeout", type=float, default=420.0,
                    help="seconds a run may take (the cold run: 1200)")
    opts = ap.parse_args(argv)

    with open(os.path.join(opts.checkout, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = opts.seconds if opts.seconds is not None \
        else bench["run_seconds"]
    os.makedirs(opts.out, exist_ok=True)
    runs_path = os.path.join(opts.out, "runs.jsonl")
    summary = {}
    for cell in opts.cells.split(","):
        os.makedirs(os.path.join(opts.out, cell), exist_ok=True)
        plan = [] if opts.no_cold else [("cold", 0, SEEDS[-1], 0)]
        plan += [(f"set{s + 1}", k, SEEDS[k], 0)
                 for s in range(opts.sets) for k in range(opts.runs)]
        if not opts.no_trace:
            plan.append(("trace", 0, SEEDS[0], 1))
        sets = {}
        for label, k, seed, trace in plan:
            cmd = bench["command"] + [
                "--workload", cell, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(trace)] \
                + (["--rehearse"] if opts.rehearse else [])
            rec = one_run(cmd, opts.checkout, os.path.join(
                opts.out, cell, f"{label}_{k}"),
                COLD_TIMEOUT_S if label == "cold" else opts.timeout)
            rec.update(cell=cell, set=label, seed=seed, trace=trace)
            with open(runs_path, "a") as f:
                f.write(json.dumps(rec) + "\n")
            line = rec["line"]
            print(cell, label, k, "rc", rec["rc"],
                  "wall", round(rec["wall_s"], 1),
                  "correct", line.get("correct"),
                  {m: v["value"] for m, v in
                   line.get("metrics", {}).items()}, flush=True)
            if label.startswith("set") and rec["rc"] == 0:
                for m, v in line.get("metrics", {}).items():
                    if v["value"] is not None:   # a rehearsal has none
                        sets.setdefault(label, {}).setdefault(
                            m, []).append(v["value"])
        summary[cell] = {
            label: {m: {"median": statistics.median(vs),
                        "spread": spread(vs) if len(vs) >= 2 else None,
                        "values": vs}
                    for m, vs in metrics.items()}
            for label, metrics in sets.items()}
    with open(os.path.join(opts.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for cell, by_set in summary.items():
        for label, metrics in by_set.items():
            for m, s in metrics.items():
                print(f"{cell} {label} {m}: median {s['median']:.6g} "
                      f"spread {100 * (s['spread'] or 0):.3f}% "
                      f"n={len(s['values'])}")


if __name__ == "__main__":
    main()
