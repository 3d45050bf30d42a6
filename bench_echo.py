"""BASELINE config 4 shape: batched WASI outcalls (echo workload).

4096 lanes each call wasi fd_write twice per iteration (message +
nwritten bookkeeping to a sink fd), interleaved with compute, for ITERS
iterations — the serverless request-handler shape.  Measures wall time
and aggregate host-call service rate through the batch engines' three-
tier hostcall pipeline (batch/hostcall.py):

  tier 0  pure calls retired in-kernel (zero device<->host round trips)
  tier 1  parked lanes drained by SoA-vectorized WASI implementations
  tier 2  CPU drain overlapped with device compute (block scheduler)

Prints ONE JSON line and records it to ECHO_r06.json (BENCH_ARTIFACT
overrides the path; =off disables the file)."""

import os
import sys
import time

import numpy as np

LANES = int(os.environ.get("ECHO_LANES", 4096))
ITERS = int(os.environ.get("ECHO_ITERS", 4))


def build_module():
    from wasmedge_tpu.utils.builder import ModuleBuilder

    b = ModuleBuilder()
    b.import_func("wasi_snapshot_preview1", "fd_write",
                  ["i32", "i32", "i32", "i32"], ["i32"])
    b.add_memory(1, 1)
    # iovec at 64 -> "hello wasi echo\n" at 128 (16 bytes)
    body = [
        ("i32.const", 64), ("i32.const", 128), ("i32.store", 2, 0),
        ("i32.const", 68), ("i32.const", 16), ("i32.store", 2, 0),
    ]
    msg = b"hello wasi echo\n"
    for i, ch in enumerate(msg):
        body += [("i32.const", 128 + i), ("i32.const", ch),
                 ("i32.store8", 0, 0)]
    body += [
        ("block", None), ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        # write the message
        ("i32.const", 1), ("i32.const", 64), ("i32.const", 1),
        ("i32.const", 32), ("call", 0), ("local.set", 2),
        # write again (second syscall per iteration)
        ("i32.const", 1), ("i32.const", 64), ("i32.const", 1),
        ("i32.const", 32), ("call", 0), ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0), "end", "end",
        ("local.get", 2),
    ]
    b.add_function(["i32"], ["i32"], ["i32", "i32"], body, export="echo")
    return b.build()


def _backend():
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unknown"


def hostcall_stats(eng):
    """Aggregate pipeline counters from whichever engines actually ran."""
    from wasmedge_tpu.batch.engine import new_hostcall_stats

    out = new_hostcall_stats()
    seen = set()
    for e in (eng, getattr(eng, "simt", None),
              getattr(getattr(eng, "pallas", None), "simt", None)):
        if e is None or id(e) in seen:
            continue
        seen.add(id(e))
        st = getattr(e, "hostcall_stats", None)
        if st:
            for k, v in st.items():
                out[k] = out.get(k, 0) + v
    return out


def main():
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.host.wasi import WasiModule
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.utils.bench_artifact import emit
    from wasmedge_tpu.validator import Validator

    data = build_module()
    conf = Configure()
    conf.batch.steps_per_launch = 100_000
    # Size the per-lane stacks to the workload (bench.py precedent):
    # the echo handler needs ~16 value slots / 2 frames; smaller state
    # planes mean cheaper per-step updates everywhere.
    conf.batch.value_stack_depth = 64
    conf.batch.call_stack_depth = 16
    wasi = WasiModule()
    wasi.init_wasi(dirs=[], prog_name="echo")
    # route fd 1 to a sink so the bench doesn't spam stdout
    sink = os.open(os.devnull, os.O_WRONLY)
    wasi.env.fds[1].os_fd = sink
    mod = Validator(conf).validate(Loader(conf).parse_module(data))
    store = StoreManager()
    ex = Executor(conf)
    ex.register_import_object(store, wasi)
    inst = ex.instantiate(store, mod)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    eng.run("echo", [np.full(LANES, 1, np.int64)], max_steps=100_000)

    t0 = time.perf_counter()
    res = eng.run("echo", [np.full(LANES, ITERS, np.int64)],
                  max_steps=10_000_000)
    dt = time.perf_counter() - t0
    os.close(sink)

    ok = bool(res.completed.all())
    ncalls = LANES * ITERS * 2
    tiers = hostcall_stats(eng)
    out = {
        "metric": f"wasi_echo_hostcalls_per_sec_x{LANES}",
        "value": round(ncalls / dt, 1),
        "unit": "hostcalls/s",
        "ok": ok,
        "calls": ncalls,
        "wall_s": round(dt, 3),
        "per_lane_calls_per_sec": round(ncalls / dt / LANES, 3),
        "lanes": LANES,
        "iters": ITERS,
        "tier0_calls": tiers["tier0_calls"],
        "tier0_fd_write": tiers["tier0_fd_write"],
        "tier1_calls": tiers["tier1_calls"],
        "tier1_vectorized": tiers["tier1_vectorized"],
        "serve_rounds": tiers["serve_rounds"],
        # tier-0 calls complete in-kernel: zero device<->host round
        # trips is witnessed by serve_rounds == 0
        "zero_roundtrip": bool(tiers["tier0_calls"] >= ncalls
                               and tiers["serve_rounds"] == 0),
        "backend": _backend(),
    }
    if LANES == 4096 and ITERS == 4:
        # recorded context, NOT measured by this run: r5's number came
        # from 1x TPU v5e; the seed numbers are the
        # unmodified seed bench on the r6 build container (CPU, 2 vCPU).
        # The seed ran with default stack geometry (1024/512); the r6
        # pipeline measured 2,793 calls/s under that SAME geometry
        # (pipeline-only gain: 5.2x) before the workload-sized stacks
        # above were applied on top.
        out["reference"] = {
            "note": "hardcoded prior measurements for comparison",
            "r5_tpu_calls_per_sec": 1935.0,
            "seed_same_container_cpu_calls_per_sec": 533.6,
            "r6_same_container_default_geometry_calls_per_sec": 2793.0,
        }
    emit(out, "ECHO_r06.json")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
