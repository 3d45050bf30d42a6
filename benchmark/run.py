#!/usr/bin/env python3
"""run.py: one cell of BENCHMARK.json, once, in a new process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the contract's JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` `breakdown`).
Everything the cell needs is found by name: the cell in BENCHMARK.json and
`workloads/<cell>.json`, its configuration's `file`, the driver the
configuration names in `drivers/`, each per-layer metric in
`layer_metrics/<name>.json` and its reader in `readers/`.  Adding a cell,
a configuration, a driver or a layer metric adds files and entries; see
README.md.

A measurement refuses anything but a TPU.  `--rehearse` is the one
exception: the cell's `rehearse` sizes on the CPU, every code path, and a
last line whose metric values are all null.
"""

import time

T_START = time.perf_counter()   # set-up counts from here

import argparse                 # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window "
                         "(default: BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no metric value")
    opts = ap.parse_args(argv)
    if opts.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import harness

    run = harness.Run(opts, T_START)
    driver = harness.load_module("drivers", run.config["driver"])
    driver.run(run)
    run.emit()


if __name__ == "__main__":
    main()
