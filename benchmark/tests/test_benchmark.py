"""The benchmark's own checks, run by hand (`pytest benchmark/tests`, not
part of tier-1): the data files agree with BENCHMARK.json and with what the
driver accepts, both drivers pass a rehearsal, the load generator stays off
jax, and the trace reducer gives the expected numbers on a small recorded
trace (`data/fib30_two_jobs.xplane.pb`: two fib(30)x4096 jobs on a TPU v5e,
recorded by a discarded session of PR 24 under the span names
bench/trace_window, bench/job, bench/compare)."""

import ast
import glob
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import reduce_trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(ROOT, "BENCHMARK.json")


def test_every_data_file_loads():
    files = glob.glob(os.path.join(BENCH, "**", "*.json"), recursive=True)
    assert len(files) >= 16
    for path in files:
        load(path)
        rel = os.path.relpath(path, ROOT)
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for item in bench[group]:
            assert NAME.match(item["name"]), item["name"]
            names.append((group in ("end_to_end", "per_layer"), item["name"]))
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))


def test_cells_find_their_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        conf = load(ROOT, configs[w["config"]]["file"])
        cell = load(BENCH, "workloads", w["name"] + ".json")
        assert conf["name"] == w["config"] == cell["config"]
        assert conf["chips"] == w["chips"]
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           conf["driver"] + ".py"))
        assert os.path.exists(os.path.join(BENCH, "references",
                                           conf["reference"] + ".py"))
        assert sorted(conf["reduced"]) == \
            sorted(configs[w["config"]]["reduced"])
        assert "guarantees" in conf and "assumed" in conf
    assert {w["config"] for w in bench["workloads"]} == set(configs)


def test_moves_and_cells(bench):
    cells = [w["name"] for w in bench["workloads"]]

    def reported_in(metric):
        return metric.get("workloads", cells)

    drivers = {w["name"]: load(ROOT, c["file"])["driver"]
               for w in bench["workloads"] for c in bench["configs"]
               if c["name"] == w["config"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert reported_in(e2e["setup_s"]) == cells
    for cell in cells:      # setup_s, one more, and a per-layer metric
        assert sum(cell in reported_in(m) for m in e2e.values()) >= 2
        assert any(cell in reported_in(m) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        spec = load(BENCH, "layer_metrics", m["name"] + ".json")
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        # which cells report it is BENCHMARK.json's alone to say, so a new
        # cell edits no file here; the file names the families it fits
        assert "workloads" not in spec
        assert {drivers[c] for c in reported_in(m)} <= set(spec["drivers"])
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        assert m["moves"] in e2e, m
        for cell in reported_in(m):
            assert cell in cells
            assert cell in reported_in(e2e[m["moves"]]), (m["name"], cell)
    layers = {}
    for m in bench["per_layer"]:    # one layer, one spelling
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_peaks_table():
    peaks = load(BENCH, "peaks.json")
    assert peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_loadgen_stays_off_jax():
    with open(os.path.join(BENCH, "loadgen.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= set(sys.stdlib_module_names), imported
    code = ("import sys; sys.argv=['loadgen.py', '--help']\n"
            "import runpy\n"
            "try:\n runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit: pass\n"
            "assert 'jax' not in sys.modules\n"
            "assert 'wasmedge_tpu' not in sys.modules\n"
            % os.path.join(BENCH, "loadgen.py"))
    subprocess.run([sys.executable, "-c", code], check=True,
                   capture_output=True)


def run_cell(cell, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "2147483659", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in load(
    ROOT, "BENCHMARK.json")["workloads"]])      # every cell, so every driver
def test_rehearsal_prints_the_contracts_line(bench, cell, trace):
    proc = run_cell(cell, "--rehearse", "--trace", str(trace))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    group = bench["per_layer"] if trace else bench["end_to_end"]
    listed = {m["name"] for m in group if cell in m.get("workloads", [cell])}
    assert line["metrics"] and set(line["metrics"]) <= listed
    if not trace:
        assert set(line["metrics"]) == listed
    # a CPU number never stands under the name of a device metric
    assert all(v["value"] is None for v in line["metrics"].values())


def test_a_measurement_refuses_the_cpu():
    proc = run_cell("batch-fib30-uniform", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "not a TPU" in proc.stderr


def test_short_op_name():
    hlo = ("%run.1 = (s32[1,16]{1,0:T(1,128)}, s32[1,3,256]{2,1,0:T(4,128)"
           "S(1)}) custom-call(s32[16]{0:T(128)S(1)} %copy-done), "
           "custom_call_target=\"tpu_custom_call\"")
    assert reduce_trace.short_op_name(hlo) == "%run.1 custom-call"
    assert reduce_trace.short_op_name(
        "%copy.3 = s32[256,4096]{1,0:T(8,128)S(1)} copy(s32[256,4096]"
        "{1,0:T(8,128)} %args_0_.1)") == "%copy.3 copy"


def test_reducer_on_the_recorded_trace():
    trace = reduce_trace.load(
        os.path.join(HERE, "data", "fib30_two_jobs.xplane.pb"),
        slice_span="bench/trace_window")
    # the discarded session's own reduction of this file (call1.jsonl):
    # busy 2.076279461 s of a 2.230622982 s slice, idle 6.919 %, kernel
    # 36.714 ns a step over two jobs of 28,271,635 steps
    assert trace.window_s == pytest.approx(2.230622982, rel=1e-9)
    assert trace.busy_s == pytest.approx(2.076279461, rel=1e-6)
    kernel = trace.op_seconds(" custom-call$")
    assert kernel == pytest.approx(2.075926734, rel=1e-6)
    assert 1e9 * kernel / (2 * 28271635) == pytest.approx(36.7139, rel=1e-4)
    assert len(trace.spans["bench/job"]) == 2
    host_ms = [1000 * ((b - a) - trace.busy_in(a, b))
               for a, b in trace.spans["bench/job"]]
    assert host_ms == pytest.approx([77.2, 73.2], abs=0.1)
    out = trace.breakdown()
    assert out["device_ops"][0][0] == "%run.1 custom-call"
    assert all(len(name) < 100 for name, _s in out["device_ops"])
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["idle_gaps"][0][0] == "job"
    idle = sum(b - a for a, b in trace.gaps())
    assert idle == pytest.approx(trace.window_s - trace.busy_s, rel=1e-9)


def test_readers_on_the_recorded_trace():
    import harness

    trace = reduce_trace.load(
        os.path.join(HERE, "data", "fib30_two_jobs.xplane.pb"),
        slice_span="bench/trace_window")
    obs = {"trace": trace, "samples": {"probe_ms": [1.0, 3.0, 2.0]},
           "counters": {"trace_steps": 2 * 28271635, "splits": 0, "jobs": 9,
                        "compiles": 0, "window_s": 30.0, "rounds": 15}}

    def read(name):
        spec = load(BENCH, "layer_metrics", name + ".json")
        return harness.load_module("readers", spec["reader"]).read(
            obs, **spec.get("args", {}))

    assert read("kernel_ns_per_step.batch") == pytest.approx(36.7139,
                                                             rel=1e-4)
    assert read("idle_share.batch") == pytest.approx(6.9193, rel=1e-4)
    assert read("job_host_ms.batch") == pytest.approx(75.2, abs=0.1)
    assert read("splits_per_job.batch") == 0
    assert read("compiles_in_window.batch") == 0
    assert read("round_ms.serve") == pytest.approx(2000.0)
    assert read("http_probe_ms.serve") == 2.0
    # nothing to read: the reader returns nothing, the line leaves it out
    assert read("device_ms_per_round.serve") is None
    assert read("live_lane_share.serve") is None
    obs["trace"] = None
    assert read("idle_share.serve") is None
