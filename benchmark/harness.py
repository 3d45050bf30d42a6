"""harness.py: what every driver shares: the cell's data files, the device
check, the compile listener, the traced slice, the readers, and the one
line a run prints last.  A driver (`drivers/<name>.py`, `run(run)`) builds
the system under test, warms it, calls `run.start_window()`, does the
window's work, and leaves on the `Run`:

    run.correct, run.attempted, run.failed
    run.values      {end-to-end metric: number}, host clock
    run.obs         what the per-layer readers read (README.md, "obs")
"""

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """`<kind>/<name>.py` under benchmark/, found by the name a data file
    gives: a new driver, reader or reference is a new file."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry(items, name, what):
    for item in items:
        if item["name"] == name:
            return item
    sys.exit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


class Run:
    def __init__(self, opts, t_start):
        self.t_start = t_start
        self.bench = load_json(ROOT, "BENCHMARK.json")
        self.cell = entry(self.bench["workloads"], opts.workload, "cell")
        conf = entry(self.bench["configs"], self.cell["config"],
                     "configuration")
        self.config = load_json(ROOT, conf["file"])
        self.workload = load_json(HERE, "workloads",
                                  self.cell["name"] + ".json")
        self.traffic = dict(self.workload["traffic"])
        self.rehearse = opts.rehearse
        self.seed = opts.seed
        self.trace = bool(opts.trace)
        self.seconds = opts.seconds if opts.seconds is not None \
            else float(self.bench["run_seconds"])
        if self.rehearse:   # the tiny sizes are data too
            self.config.update(self.config.get("rehearse", {}))
            self.traffic.update(self.workload.get("rehearse", {}))
            self.seconds = float(self.traffic.get("seconds", 3))
        self.correct = False
        self.attempted = 0
        self.failed = 0
        self.values = {}
        self.obs = {"counters": {}, "samples": {}, "trace": None}
        self.compiles = []          # (monotonic time, program, seconds)
        self.setup_s = None
        self.t0 = None
        self._device = None

    # -- earlier lines ------------------------------------------------------
    def note(self, **record):
        print(json.dumps(record), flush=True)

    # -- the device ---------------------------------------------------------
    def device(self):
        """Create the backend; refuse anything but the chips the cell asks
        for (a rehearsal takes the CPU).  Compiled programs persist where
        JAX_COMPILATION_CACHE_DIR says, else in `<checkout>/.jax_cache`:
        a fixed path, so the second run of a cell there compiles nothing."""
        import jax
        from jax import monitoring

        jax.config.update(
            "jax_compilation_cache_dir",
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
        monitoring.register_event_duration_secs_listener(
            self._on_duration)
        devs = jax.devices()
        self._device = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}
        if self.rehearse:
            return self._device
        if self._device["platform"] != "tpu":
            sys.exit(f"benchmark: JAX reached {self._device['platform']!r}, "
                     f"not a TPU; only --rehearse runs without one")
        if len(devs) < self.cell["chips"]:
            sys.exit(f"benchmark: {len(devs)} chips, the cell needs "
                     f"{self.cell['chips']}")
        if self._device["kind"] not in load_json(HERE,
                                                 "peaks.json")["devices"]:
            sys.exit(f"benchmark: device kind {self._device['kind']!r} is "
                     f"not in peaks.json")
        return self._device

    def _on_duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.compiles.append((time.monotonic(),
                                  str(kw.get("fun_name")), duration))

    def compiles_between(self, a, b):
        return [[name, s] for t, name, s in self.compiles if a <= t <= b]

    def reference(self):
        return load_module("references", self.config["reference"])

    # -- the window ---------------------------------------------------------
    def start_window(self):
        """Set-up ends here: process start to now is `setup_s`."""
        self.setup_s = time.perf_counter() - self.t_start
        self.t0 = time.monotonic()
        return self.t0

    @contextlib.contextmanager
    def traced_slice(self):
        """Profile what runs inside, under the span `bench/slice`, reduce
        the trace on this machine and keep the numbers, not the file."""
        import jax

        where = tempfile.mkdtemp(prefix="bench-trace-")
        # the Python tracer hooks every call of every thread: with the
        # gateway's thousand handler threads it stalled the serving loop
        # and wrote 237 MB in 39 s (PR 25); spans and device events stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        try:
            jax.profiler.start_trace(where, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(reduce_trace.SLICE_SPAN):
                    yield
            finally:
                jax.profiler.stop_trace()
            found = glob.glob(os.path.join(
                where, "plugins", "profile", "*", "*.xplane.pb"))
            if found:
                self.note(trace_bytes=os.path.getsize(found[0]))
                self.obs["trace"] = reduce_trace.load(found[0])
        finally:
            shutil.rmtree(where, ignore_errors=True)

    # -- the last line ------------------------------------------------------
    def _per_layer(self):
        out = {}
        for m in self.bench["per_layer"]:
            if not applies(m, self.cell["name"]):
                continue
            spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
            reader = load_module("readers", spec["reader"])
            value = reader.read(self.obs, **spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def _end_to_end(self):
        values = dict(self.values, setup_s=self.setup_s)
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in self.bench["end_to_end"]
                if applies(m, self.cell["name"])}

    def emit(self):
        import jax

        metrics = self._per_layer() if self.trace else self._end_to_end()
        if self.rehearse:   # a CPU number never stands under a device name
            metrics = {k: {"value": None, "unit": v["unit"]}
                       for k, v in metrics.items()}
        stats = [d.memory_stats() or {} for d in jax.devices()]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        device = dict(self._device, memory_peak_bytes=peak or None)
        line = {"correct": bool(self.correct),
                "attempted": int(self.attempted),
                "failed": int(self.failed), "metrics": metrics,
                "device": device}
        trace = self.obs["trace"]
        if self.trace and trace is not None:
            device["busy_s"] = trace.busy_s
            device["window_s"] = trace.window_s
            line["breakdown"] = trace.breakdown()
        print(json.dumps(line), flush=True)
