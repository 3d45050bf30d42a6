#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the main path still starts on
the chip.  Run it from the repo root on a machine with a TPU:

    python chip_smoke.py                # one chip (what the driver runs)
    python chip_smoke.py --four-chips   # the --devices 4 path, four chips

One process owns a chip at a time, so this parent never creates a JAX
backend: it starts one child after the other, each with JAX_PLATFORMS=tpu
in its environment (a chip that will not initialise is an error, never a
quiet CPU run), passes the child's JSON lines through as they arrive and
exits non-zero the moment a phase fails.

  child A  `chip_smoke.py --child pallas`: the library surface
           Loader -> Validator -> Executor.instantiate ->
           UniformBatchEngine(lanes=4096), i.e. what VM.execute_batch
           builds; phases device, pallas_parity, pallas_flagship,
           pallas_memory, each checked against the scalar engine.
  child B  the server itself: `python -m wasmedge_tpu.cli gateway
           fib.wasm --port 0 --lanes 4096`; the parent talks real HTTP
           to it (phase gateway) and stops it with SIGINT.

The last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
There is no mode in which a run without the chip prints it.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PLATFORM = "tpu"
LANES = 4096
FLAGSHIP_N = 30             # fib(30) in every lane (BASELINE.json configs[0])
MEMORY_WORDS = 2048         # mem_checksum(n) in every lane
GATEWAY_REQUESTS = 64
GATEWAY_TIMEOUT_S = 900     # one HTTP answer, first-launch compile included
FOUR_CHIP_TIMEOUT_S = 3000


def emit(**record):
    print(json.dumps(record), flush=True)


def fib(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def check(cond, what):
    if not cond:
        raise AssertionError(what)


# -- child A: the Pallas batch path ------------------------------------------
def _bench_conf(depth, call_depth):
    """The benchmark's batch geometry (benchmark/configs/): stacks
    sized to the workload, one long launch."""
    from wasmedge_tpu.common.configure import Configure

    conf = Configure()
    conf.batch.steps_per_launch = 50_000_000
    conf.batch.value_stack_depth = depth
    conf.batch.call_stack_depth = call_depth
    return conf


def _instantiate(wasm, conf):
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.validator import Validator

    mod = Validator(conf).validate(Loader(conf).parse_module(wasm))
    store = StoreManager()
    ex = Executor(conf)
    return ex, store, ex.instantiate(store, mod)


def _engine(wasm, conf):
    """(engine, scalar oracle) over one module: the engine is what
    VM.execute_batch builds, the oracle runs Executor.invoke_raw on a
    fresh instance and memoizes by argument."""
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.errors import TrapError

    _ex, store, inst = _instantiate(wasm, conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    check(eng.pallas is not None, "the batch engine took no Pallas kernel")
    check(eng.pallas.eligible, eng.pallas.ineligible_reason)
    check(eng.pallas._interpret() is False,
          "the Pallas kernel is in interpret mode")
    memo = {}

    def oracle(func, arg):
        if arg not in memo:
            ex, s_store, s_inst = _instantiate(wasm, conf)
            try:
                memo[arg] = ("ok", ex.invoke_raw(
                    s_store, s_inst.find_func(func),
                    [arg & ((1 << 64) - 1)]))
            except TrapError as te:
                memo[arg] = ("trap", int(te.code))
        return memo[arg]

    return eng, oracle


def _compare(res, func, args, oracle):
    """Bit-for-bit against the scalar engine, every lane."""
    import numpy as np

    for arg in np.unique(args):
        lanes = np.flatnonzero(args == arg)
        kind, expect = oracle(func, int(arg))
        if kind == "trap":
            check((res.trap[lanes] == expect).all(),
                  f"{func}({arg}): trap differs from the scalar engine")
            continue
        check((res.trap[lanes] == -1).all(),
              f"{func}({arg}): lanes did not complete: "
              f"{np.unique(res.trap[lanes])}")
        for i, v in enumerate(expect):
            got = np.asarray(res.results[i][lanes]).astype(np.uint64)
            check((got == np.uint64(v)).all(),
                  f"{func}({arg}): result {i} differs from the scalar "
                  f"engine ({np.unique(got)[:4]} != {v})")


def _run(eng, func, args, max_steps):
    """One engine run -> (result, what the engine says it did).  The
    recheck counter lives on the per-geometry kernel engines the block
    scheduler caches and only ever grows, so report this run's share."""
    cache = getattr(eng.pallas.simt, "_sched_cache", {})
    before = sum(e.recheck_rounds for e in cache.values())
    t0 = time.perf_counter()
    res = eng.run(func, [args], max_steps=max_steps)
    wall = time.perf_counter() - t0
    cache = eng.pallas.simt._sched_cache
    return res, {
        "interpret": eng.pallas._interpret(),
        "fell_back_to_simt": bool(eng.fell_back_to_simt),
        "splits": int(eng.pallas.splits),
        "recheck_rounds":
            sum(e.recheck_rounds for e in cache.values()) - before,
        "lane_blocks": sorted({int(lblk) for (_lanes, lblk) in cache}),
        "wall_s": round(wall, 3)}


def phase_device():
    import importlib.metadata as md

    import jax

    from wasmedge_tpu.batch import ensure_jax_backend

    ensure_jax_backend()   # what every engine build calls first
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    check(device["platform"] == PLATFORM,
          f"JAX reached {device['platform']!r}, not {PLATFORM!r}")
    emit(phase="device", ok=True, device=device, jax=jax.__version__,
         jaxlib=md.version("jaxlib"), libtpu=md.version("libtpu"),
         compile_cache_dir=jax.config.jax_compilation_cache_dir)


def phase_pallas_parity(eng, oracle):
    import numpy as np

    lane = np.arange(LANES)
    # sixteen argument groups: the block scheduler packs each group
    # into its own lane blocks (batch/scheduler.py entry grouping)
    args = (5 + lane % 16).astype(np.int64)
    res, grouped = _run(eng, "fib", args, 50_000_000)   # compiles
    _compare(res, "fib", args, oracle)
    counts = []
    for n in range(5, 21):
        r = np.unique(res.retired[args == n])
        check(r.size == 1, f"fib({n}): lanes retired differing counts {r}")
        counts.append(int(r[0]))
    check(all(a < b for a, b in zip(counts, counts[1:])),
          f"retired counts do not rise with n: {counts}")
    check(not grouped["fell_back_to_simt"], "grouped run fell back to SIMT")

    # Arguments that defeat entry grouping (every fourth lane a value of
    # its own, all below 2 so fib returns at once): the batch enters as
    # one 4096-lane block and diverges in flight, so THIS run compiles
    # and runs the careful kernel and the block splitter.
    args = np.where(lane % 4 == 0, -1 - lane // 4,
                    5 + lane % 8).astype(np.int64)
    res, divergent = _run(eng, "fib", args, 50_000_000)  # compiles
    _compare(res, "fib", args, oracle)
    check(divergent["splits"] > 0 and divergent["recheck_rounds"] > 0,
          f"the divergent run never split a block: {divergent}")
    emit(phase="pallas_parity", ok=True, lanes=LANES, grouped=grouped,
         divergent=divergent, retired_fib5_to_fib20=counts)


def phase_pallas_flagship(eng):
    import numpy as np

    args = np.full(LANES, FLAGSHIP_N, np.int64)
    res, flags = _run(eng, "fib", args, 2_000_000_000)
    check(bool(res.completed.all()), "not every lane completed")
    check((np.asarray(res.results[0]) == fib(FLAGSHIP_N)).all(),
          f"a lane's fib({FLAGSHIP_N}) is not {fib(FLAGSHIP_N)}")
    check(not flags["fell_back_to_simt"], "flagship fell back to SIMT")
    retired = np.unique(res.retired)
    check(retired.size == 1, f"lanes retired differing counts {retired}")
    total = float(retired[0]) * LANES
    emit(phase="pallas_flagship", ok=True, lanes=LANES, n=FLAGSHIP_N,
         steps=int(res.steps), retired_per_lane=int(retired[0]),
         info_retired_instr_per_s=round(total / flags["wall_s"], 1),
         **flags)


def phase_pallas_memory():
    import numpy as np

    from wasmedge_tpu.models import build_memory_workload

    eng, oracle = _engine(build_memory_workload(), _bench_conf(128, 64))
    args = np.full(LANES, MEMORY_WORDS, np.int64)
    res, flags = _run(eng, "mem_checksum", args, 200_000_000)  # compiles
    _compare(res, "mem_checksum", args, oracle)
    check(not flags["fell_back_to_simt"], "memory workload fell back to SIMT")
    emit(phase="pallas_memory", ok=True, lanes=LANES, words=MEMORY_WORDS,
         mem_mode="hbm_window" if eng.pallas._mem_mode() else "resident",
         **flags)


def child_pallas():
    from wasmedge_tpu.models import build_fib

    phase_device()
    eng, oracle = _engine(build_fib(), _bench_conf(256, 256))
    phase_pallas_parity(eng, oracle)
    phase_pallas_flagship(eng)
    phase_pallas_memory()


# -- child (four chips): the --devices N shard drive --------------------------
def child_four():
    import jax
    import numpy as np

    import wasmedge_tpu.parallel.mesh as pmesh
    from wasmedge_tpu.batch.engine import BatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.models import build_fib
    from wasmedge_tpu.vm import VM

    phase_device()
    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")

    # where the run's own placement call puts a lane plane
    placed = []
    place = pmesh.shard_batch_state

    def spy(state, mesh):
        out = place(state, mesh)
        placed.append([(str(s.device), tuple(s.data.shape))
                       for s in out.trap.addressable_shards])
        return out

    pmesh.shard_batch_state = spy

    args = (5 + np.arange(LANES) % 11).astype(np.int64)
    vm = VM(Configure())
    vm.load_wasm(build_fib())
    vm.validate()
    vm.instantiate()
    t0 = time.perf_counter()
    res = vm.execute_batch("fib", [args], lanes=LANES, devices=4,
                           max_steps=50_000_000)
    wall4 = time.perf_counter() - t0
    check(len(placed) == 1, f"state placed {len(placed)} times")
    shards = placed[0]
    check(len(shards) == 4 and len({d for d, _ in shards}) == 4,
          f"lane plane is not on four distinct devices: {shards}")
    check(all(shape == (LANES // 4,) for _, shape in shards),
          f"uneven lane shards: {shards}")

    conf = Configure()
    _ex, store, inst = _instantiate(build_fib(), conf)
    one = BatchEngine(inst, store=store, conf=conf, lanes=LANES)
    t0 = time.perf_counter()
    ref = one.run("fib", [args], max_steps=50_000_000)
    wall1 = time.perf_counter() - t0
    check(bool(ref.completed.all()), "one-device run did not complete")
    check((np.asarray(ref.results[0]) ==
           np.array([fib(int(n)) for n in args])).all(),
          "one-device run is wrong")
    for name, a, b in (("results", res.results[0], ref.results[0]),
                       ("trap", res.trap, ref.trap),
                       ("retired", res.retired, ref.retired)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"four-device {name} differ from the one-device run")
    emit(phase="four_chips", ok=True, lanes=LANES, shard_devices=shards,
         bit_identical=True, wall_four_s=round(wall4, 3),
         wall_one_s=round(wall1, 3))


# -- parent -------------------------------------------------------------------
def _child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = PLATFORM
    return env


def run_child(kind, timeout_s):
    """Run `chip_smoke.py --child kind` to its end, passing its lines
    through; returns its `device` phase record.  A child that fails
    ends the run."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", kind],
        cwd=HERE, env=_child_env(), stdout=subprocess.PIPE, text=True)
    try:
        lines = _Lines(proc.stdout)
        rc = proc.wait(timeout=timeout_s)
        if rc != 0:
            sys.exit(f"chip_smoke: child {kind!r} failed (exit code {rc})")
        return lines.next_json("compile_cache_dir", 10)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


class _Lines:
    """Lines of a child's stdout, read on a thread so a wait can time
    out; every line is passed through as it arrives."""

    def __init__(self, stream):
        self._cond = threading.Condition()
        self._lines = []
        self._eof = False
        self._next = 0
        threading.Thread(target=self._pump, args=(stream,),
                         daemon=True).start()

    def _pump(self, stream):
        for line in stream:
            sys.stdout.write(line)
            sys.stdout.flush()
            with self._cond:
                self._lines.append(line)
                self._cond.notify_all()
        with self._cond:
            self._eof = True
            self._cond.notify_all()

    def next_json(self, key, timeout_s):
        """The next JSON line that has `key`."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while True:
                while self._next < len(self._lines):
                    line = self._lines[self._next]
                    self._next += 1
                    if line.startswith("{"):
                        rec = json.loads(line)
                        if key in rec:
                            return rec
                left = deadline - time.monotonic()
                if self._eof or left <= 0:
                    raise AssertionError(
                        f"no {key!r} line from the child "
                        f"({'exited' if self._eof else 'timed out'})")
                self._cond.wait(left)


def _http(port, method, path, body=None):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=GATEWAY_TIMEOUT_S)
    try:
        data = None if body is None else json.dumps(body).encode()
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, (json.loads(raw) if raw[:1] in (b"{", b"[")
                             else raw)
    finally:
        conn.close()


def _invoke(port, n, is_async):
    """One fib(n) through POST /v1/invoke, checked against the host's
    fib.  An async request (and a sync one that outlasted the gateway's
    60 s sync cap, as the first may while the step compiles) is polled
    through GET /v1/requests/<id>."""
    status, body = _http(port, "POST", "/v1/invoke",
                         {"module": "main", "func": "fib", "args": [n],
                          "async": is_async})
    check(status == 202 if is_async else status in (200, 202),
          f"invoke answered {status}: {body}")
    deadline = time.monotonic() + GATEWAY_TIMEOUT_S
    while body.get("status") == "pending":
        check(time.monotonic() < deadline,
              f"request {body['request_id']} never resolved")
        time.sleep(0.2)
        status, body = _http(port, "GET",
                             f"/v1/requests/{body['request_id']}")
    check(status == 200 and body.get("ok") is True,
          f"fib({n}) answered {status}: {body}")
    check(body["result"] == [fib(n)],
          f"fib({n}) = {body['result']}, expected {fib(n)}")


def phase_gateway(device):
    from concurrent.futures import ThreadPoolExecutor

    from wasmedge_tpu.models import build_fib

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        wasm = os.path.join(tmp, "fib.wasm")
        with open(wasm, "wb") as f:
            f.write(build_fib())
        proc = subprocess.Popen(
            [sys.executable, "-m", "wasmedge_tpu.cli", "gateway", wasm,
             "--port", "0", "--lanes", str(LANES)],
            cwd=HERE, env=_child_env(), stdout=subprocess.PIPE, text=True)
        try:
            lines = _Lines(proc.stdout)
            hello = lines.next_json("listening", GATEWAY_TIMEOUT_S)
            t_listen = time.perf_counter()
            check(hello["device"]["platform"] == PLATFORM,
                  f"the gateway serves from {hello['device']}")
            check(hello["device"]["kind"] == device["kind"],
                  f"gateway device {hello['device']} != {device}")
            check(hello["lanes"] == LANES, f"lanes {hello['lanes']}")
            port = int(hello["listening"].rsplit(":", 1)[1])

            # the first answer pays the SIMT step's compile on the chip
            _invoke(port, 10, False)
            first_answer_s = time.perf_counter() - t_listen
            t0 = time.perf_counter()
            jobs = [(10 + k % 6, k % 2 == 1)
                    for k in range(1, GATEWAY_REQUESTS)]
            with ThreadPoolExecutor(max_workers=16) as pool:
                futs = [pool.submit(_invoke, port, n, a) for n, a in jobs]
                for fut in futs:
                    fut.result()
            rest_s = time.perf_counter() - t0

            status, st = _http(port, "GET", "/v1/status")
            check(status == 200, f"/v1/status answered {status}")
            check(st["gateway"]["completed"] == GATEWAY_REQUESTS
                  and st["gateway"]["failed"] == 0
                  and st["gateway"]["received"] == GATEWAY_REQUESTS,
                  f"/v1/status does not reconcile: {st['gateway']}")
            check(st["device"]["platform"] == PLATFORM
                  and st["device"]["count"] == 1,
                  f"/v1/status device {st['device']}")
            status, _body = _http(port, "GET", "/healthz")
            check(status == 200, f"/healthz answered {status}")

            proc.send_signal(signal.SIGINT)
            bye = lines.next_json("metric", 120)
            check(bye["metric"] == "gateway_exit"
                  and bye["completed"] == GATEWAY_REQUESTS
                  and bye["failed"] == 0, f"gateway_exit line: {bye}")
            rc = proc.wait(timeout=120)
            check(rc == 0, f"the gateway exited with code {rc}")
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    emit(phase="gateway", ok=True, lanes=LANES, device=hello["device"],
         requests=GATEWAY_REQUESTS, sync=GATEWAY_REQUESTS // 2,
         asynchronous=GATEWAY_REQUESTS // 2,
         listening_to_first_answer_s=round(first_answer_s, 3),
         other_63_answers_s=round(rest_s, 3),
         serve=st.get("serve"))


def _cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _d, _s, files in os.walk(path))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the --devices 4 shard-drive path and "
                         "its one-device comparison (needs four chips)")
    ap.add_argument("--child", choices=("pallas", "four"),
                    help=argparse.SUPPRESS)
    opts = ap.parse_args(argv)
    if opts.child == "pallas":
        return child_pallas()
    if opts.child == "four":
        return child_four()

    # nothing of JAX in this process: wasm bytes only
    import wasmedge_tpu.models  # noqa: F401  (fails outside the repo)

    t0 = time.perf_counter()
    if opts.four_chips:
        first = run_child("four", FOUR_CHIP_TIMEOUT_S)
        check(first["device"]["count"] == 4, f"device {first['device']}")
    else:
        first = run_child("pallas", GATEWAY_TIMEOUT_S)
        phase_gateway(first["device"])
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        check(not xla_bridge.backends_are_initialized(),
              "the parent created a JAX backend")
    cache = first["compile_cache_dir"]
    emit(phase="summary", ok=True, wall_s=round(time.perf_counter() - t0, 1),
         compile_cache_dir=cache, compile_cache_entries=_cache_entries(cache))
    emit(ok=True, device=first["device"])


if __name__ == "__main__":
    main()
