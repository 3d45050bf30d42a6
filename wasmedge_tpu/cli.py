"""CLI: the `wasmedge` / `wasmedgec` tool analogs.

Mirrors /root/reference/tools/wasmedge/wasmedger.cpp:22-360 (runner:
command mode runs _start with WASI exit code; reactor mode calls an
exported function with typed argv) and wasmedgec.cpp:20-200 (compiler:
load -> validate -> emit universal artifact). TPU additions: `--batch N`
runs the export over N SIMT device lanes, `--engine` picks the execution
engine.

Usage:
  python -m wasmedge_tpu.cli run [options] app.wasm [args...]
  python -m wasmedge_tpu.cli compile [options] in.wasm out.twasm
  python -m wasmedge_tpu.cli app.wasm [args...]        # implicit run
"""

from __future__ import annotations

import sys
from typing import List, Optional

from wasmedge_tpu.common.configure import (
    Configure,
    EngineKind,
    HostRegistration,
    Proposal,
)
from wasmedge_tpu.common.errors import WasmError
from wasmedge_tpu.common.types import ValType
from wasmedge_tpu.host.wasi.environ import WasiExit
from wasmedge_tpu.utils.po import ArgumentParser, ListOpt, Option, Toggle


def _runner_parser() -> ArgumentParser:
    p = ArgumentParser("wasmedge-tpu run",
                       "run a WebAssembly file (command or reactor mode)")
    p.add_option("reactor", Toggle("enable reactor mode: call an exported fn "
                                   "with typed argv"))
    p.add_option("dir", ListOpt("bind guest:host directory (preopen)",
                                "guest_path:host_path"))
    p.add_option("env", ListOpt("environment variable NAME=VALUE", "env"))
    p.add_option(["enable-instruction-count"],
                 Toggle("enable instruction counting statistics"))
    p.add_option(["enable-gas-measuring"], Toggle("enable gas metering"))
    p.add_option(["enable-time-measuring"], Toggle("enable time measuring"))
    p.add_option(["enable-all-statistics"], Toggle("enable all statistics"))
    p.add_option(["gas-limit"], Option("gas limit (cost units)", "n", typ=int))
    p.add_option(["memory-page-limit"],
                 Option("page limit of linear memory", "n", typ=int))
    p.add_option(["time-limit"],
                 Option("time limit in milliseconds (async+cancel)", "ms",
                        typ=int))
    p.add_option(["allow-command"],
                 ListOpt("allow a command for wasmedge_process", "cmd"))
    p.add_option(["allow-command-all"],
                 Toggle("allow all commands for wasmedge_process"))
    p.add_option(["disable-bulk-memory"], Toggle("disable bulk-memory ops"))
    p.add_option(["disable-reference-types"], Toggle("disable ref types"))
    p.add_option(["disable-simd"], Toggle("disable 128-bit SIMD"))
    p.add_option(["disable-sign-extension"], Toggle("disable sign-ext ops"))
    p.add_option(["enable-tail-call"], Toggle("enable tail-call proposal"))
    p.add_option(["enable-multi-memory"], Toggle("enable multi memories"))
    p.add_option(["batch"],
                 Option("run over N SIMT device lanes (tpu_batch engine)",
                        "lanes", typ=int))
    p.add_option(["engine"],
                 Option("execution engine: scalar|native|tpu_batch|auto",
                        "kind", default="auto"))
    p.add_option(["devices"],
                 Option("shard --batch lanes across N devices (mesh "
                        "drive; with --supervised adds device "
                        "quarantine, lane migration, and coordinated "
                        "mesh checkpoints)", "n", typ=int))
    p.add_option(["mesh-drive"],
                 Option("mesh drive for --devices: shard (default; one "
                        "jitted program over the lane-sharded named "
                        "mesh) | threaded (per-device engines, the "
                        "degradation-ladder rung)", "kind"))
    p.add_option(["compact"],
                 Toggle("divergence-aware lane compaction for --batch "
                        "runs: PC-sorted lane regrouping + live-prefix "
                        "packing at launch boundaries "
                        "(batch/compact.py)"))
    p.add_option(["supervised"],
                 Toggle("supervise --batch runs: auto-checkpoint, "
                        "retry-with-backoff, engine-degradation ladder"))
    p.add_option(["checkpoint-dir"],
                 Option("checkpoint directory for supervised runs",
                        "dir"))
    p.add_option(["checkpoint-every"],
                 Option("checkpoint every N retired steps (supervised; "
                        "default 1000000)", "n", typ=int))
    p.add_option(["max-retries"],
                 Option("retry budget per engine tier (supervised)",
                        "n", typ=int))
    p.add_option(["resume"],
                 Toggle("adopt an existing --checkpoint-dir lineage at "
                        "startup (cross-process resume; implies "
                        "--supervised)"))
    p.add_option(["trace-out"],
                 Option("write a Chrome trace_event JSON of the batch "
                        "run (open in Perfetto / chrome://tracing)",
                        "path"))
    p.add_option(["metrics-out"],
                 Option("write a Prometheus text-format metrics "
                        "snapshot after the batch run", "path"))
    p.add_positional("wasm_file", "WebAssembly file to run")
    return p


def _build_conf(p: ArgumentParser) -> Configure:
    conf = Configure()
    conf.host_registrations.add(HostRegistration.Wasi)
    if p._opts["allow-command"].value or p._opts["allow-command-all"].value:
        conf.host_registrations.add(HostRegistration.WasmEdgeProcess)
    if p._opts["disable-bulk-memory"].value:
        conf.remove_proposal(Proposal.BulkMemoryOperations)
    if p._opts["disable-reference-types"].value:
        conf.remove_proposal(Proposal.ReferenceTypes)
    if p._opts["disable-simd"].value:
        conf.remove_proposal(Proposal.SIMD)
    if p._opts["disable-sign-extension"].value:
        conf.remove_proposal(Proposal.SignExtensionOperators)
    if p._opts["enable-tail-call"].value:
        conf.add_proposal(Proposal.TailCall)
    if p._opts["enable-multi-memory"].value:
        conf.add_proposal(Proposal.MultiMemories)
    st = conf.statistics
    if p._opts["enable-all-statistics"].value:
        st.instr_counting = st.cost_measuring = st.time_measuring = True
    if p._opts["enable-instruction-count"].value:
        st.instr_counting = True
    if p._opts["enable-gas-measuring"].value:
        st.cost_measuring = True
    if p._opts["enable-time-measuring"].value:
        st.time_measuring = True
    if p._opts["gas-limit"].seen:
        st.cost_measuring = True
        st.cost_limit = p._opts["gas-limit"].value
    if p._opts["memory-page-limit"].seen:
        conf.runtime.max_memory_pages = p._opts["memory-page-limit"].value
    if p._opts["compact"].value:
        conf.batch.compact = True
    if p._opts["checkpoint-dir"].seen:
        conf.supervisor.checkpoint_dir = p._opts["checkpoint-dir"].value
    if p._opts["checkpoint-every"].seen:
        conf.supervisor.checkpoint_every_steps = \
            p._opts["checkpoint-every"].value
    if p._opts["max-retries"].seen:
        conf.supervisor.max_retries = p._opts["max-retries"].value
    if p._opts["resume"].value:
        conf.supervisor.resume = True
    if p._opts["trace-out"].seen:
        conf.obs.enabled = True
        conf.obs.trace_out = p._opts["trace-out"].value
    if p._opts["metrics-out"].seen:
        conf.obs.enabled = True
        conf.obs.metrics_out = p._opts["metrics-out"].value
    if (p._opts["supervised"].value or p._opts["resume"].value) and not (
            conf.supervisor.checkpoint_every_steps
            or conf.supervisor.checkpoint_every_s):
        # --supervised promises auto-checkpointing: without an explicit
        # cadence every retry would silently restart from step 0
        conf.supervisor.checkpoint_every_steps = 1_000_000
    try:
        conf.engine = EngineKind(p._opts["engine"].value)
    except ValueError:
        raise ValueError(
            f"invalid --engine {p._opts['engine'].value!r} "
            f"(choose from {[e.value for e in EngineKind]})")
    return conf


def _parse_typed_args(functype, raw: List[str]) -> list:
    out = []
    for t, s in zip(functype.params, raw):
        if t in (ValType.I32, ValType.I64):
            out.append(int(s, 0))
        elif t in (ValType.F32, ValType.F64):
            out.append(float(s))
        else:
            out.append(int(s, 0))
    return out


def run_command(argv: List[str], out=None, err=None) -> int:
    out = out or sys.stdout
    err = err or sys.stderr
    p = _runner_parser()
    try:
        if not p.parse(argv, out):
            return 0
        conf = _build_conf(p)
    except ValueError as e:
        err.write(f"wasmedge-tpu: {e}\n")
        return 2
    path = p.positional_values[0]
    prog_args = p.rest

    from wasmedge_tpu.vm import VM

    vm = VM(conf)
    if vm.wasi_module is not None:
        vm.wasi_module.init_wasi(dirs=p._opts["dir"].value, prog_name=path,
                                 args=prog_args, envs=p._opts["env"].value)
    proc = vm.get_import_module(HostRegistration.WasmEdgeProcess)
    if proc is not None:
        proc.env.allowed_cmds = set(p._opts["allow-command"].value)
        proc.env.allowed_all = p._opts["allow-command-all"].value

    reactor = p._opts["reactor"].value
    batch_lanes = p._opts["batch"].value
    time_limit_ms = p._opts["time-limit"].value

    try:
        vm.load_wasm(path)
        vm.validate()
        vm.instantiate()
    except WasmError as e:
        err.write(f"wasmedge-tpu: load failed: {e.formatted()}\n")
        return 1
    except OSError as e:
        err.write(f"wasmedge-tpu: cannot read {path}: {e}\n")
        return 1

    def invoke(fn_name: str, args: list) -> Optional[list]:
        if time_limit_ms is not None:
            h = vm.async_execute(fn_name, args)
            if not h.wait_for(time_limit_ms / 1000.0):
                h.cancel()
            return h.get()
        return vm.execute(fn_name, args)

    try:
        if reactor:
            # reactor mode (wasmedger.cpp:239-359): _initialize then func
            if not prog_args:
                err.write("wasmedge-tpu: reactor mode needs a function name\n")
                return 2
            fn_name, fn_args = prog_args[0], prog_args[1:]
            if vm.active_module.find_func("_initialize") is not None:
                vm.execute("_initialize")
            fi = vm.active_module.find_func(fn_name)
            if fi is None:
                err.write(f"wasmedge-tpu: function {fn_name!r} not found\n")
                return 1
            if batch_lanes:
                import numpy as np

                res = vm.execute_batch(
                    fn_name,
                    [np.full(batch_lanes, int(a, 0), np.int64)
                     for a in fn_args], lanes=batch_lanes,
                    devices=p._opts["devices"].value,
                    mesh_drive=p._opts["mesh-drive"].value,
                    supervised=p._opts["supervised"].value
                    or p._opts["resume"].value,
                    resume=p._opts["resume"].value)
                out.write(f"{[int(r[0]) for r in res.results]}"
                          f" ({int(res.completed.sum())}/{batch_lanes} lanes"
                          f" completed, {int(res.retired.sum())} instrs)\n")
            else:
                rets = invoke(fn_name, _parse_typed_args(fi.functype, fn_args))
                out.write(f"{rets}\n" if rets else "[]\n")
        else:
            # command mode: run _start, exit code from WASI
            invoke("_start", [])
        code = vm.wasi_module.exit_code if vm.wasi_module else 0
    except WasiExit as e:
        code = e.code
    except WasmError as e:
        err.write(f"wasmedge-tpu: {e}\n")
        return 1
    finally:
        stat = vm.statistics()
        if stat.instr_counting or stat.cost_measuring or stat.time_measuring:
            err.write(f"statistics: {stat.dump()}\n")
    return code


def _serve_parser() -> ArgumentParser:
    p = ArgumentParser("wasmedge-tpu serve",
                       "continuous-batching serving over device lanes: "
                       "queue requests, recycle retired lanes, report "
                       "latency/occupancy")
    p.add_option(["lanes"], Option("device lanes to serve on", "n",
                                   typ=int, default=64))
    p.add_option(["requests"], Option("seeded request count", "n",
                                      typ=int, default=256))
    p.add_option(["arg-min"], Option("minimum argument value", "n",
                                     typ=int, default=8))
    p.add_option(["arg-max"], Option("maximum argument value", "n",
                                     typ=int, default=20))
    p.add_option(["seed"], Option("request schedule seed", "n",
                                  typ=int, default=0))
    p.add_option(["tenants"], Option("spread requests over N tenants",
                                     "n", typ=int, default=1))
    p.add_option(["deadline-ms"],
                 Option("per-request deadline in milliseconds", "ms",
                        typ=int))
    p.add_option(["queue-capacity"],
                 Option("bounded queue capacity (backpressure)", "n",
                        typ=int))
    p.add_option(["autotune"],
                 Toggle("auto-tune steps_per_launch from the hostcall "
                        "drain-latency histograms"))
    p.add_option(["max-virtual-lanes"],
                 Option("oversubscribe: admit up to N concurrent "
                        "requests (resident + host-swapped virtual "
                        "lanes; default = --lanes, no "
                        "oversubscription)", "n", typ=int))
    p.add_option(["resident-budget-bytes"],
                 Option("cap device-resident lane bytes: admission "
                        "installs floor(budget/lane-bytes) physical "
                        "lanes, the rest wait as virtual lanes", "b",
                        typ=int))
    p.add_option(["swap-dir"],
                 Option("spill swapped lane state to this directory "
                        "(default: host memory only)", "dir"))
    p.add_option(["compact"],
                 Toggle("divergence-aware lane compaction: PC-sorted "
                        "lane regrouping at launch boundaries "
                        "(bindings follow their lane)"))
    p.add_option(["checkpoint-dir"],
                 Option("serving-state checkpoint directory", "dir"))
    p.add_option(["checkpoint-every"],
                 Option("checkpoint every N serving rounds", "n",
                        typ=int))
    p.add_option(["resume"],
                 Toggle("adopt an existing --checkpoint-dir serving "
                        "lineage (in-flight requests come back)"))
    p.add_option(["trace-out"],
                 Option("write a Chrome trace_event JSON of the serving "
                        "run", "path"))
    p.add_option(["metrics-out"],
                 Option("write a Prometheus metrics snapshot after the "
                        "serving run", "path"))
    p.add_positional("wasm_file", "WebAssembly file to serve")
    p.add_positional("func", "exported function handling each request")
    return p


def _percentile(sorted_vals, q):
    """Nearest-rank percentile over an ascending sequence (None when
    empty): rank ceil(n*q), 1-based."""
    import math

    if not sorted_vals:
        return None
    i = min(max(math.ceil(len(sorted_vals) * q) - 1, 0),
            len(sorted_vals) - 1)
    return sorted_vals[i]


def serve_command(argv: List[str], out=None, err=None) -> int:
    """`wasmedge-tpu serve app.wasm func [options]`: drive a seeded
    request stream through the continuous-batching BatchServer and
    print one JSON summary line (req/s, latency percentiles, occupancy,
    recycled lanes)."""
    import json

    out = out or sys.stdout
    err = err or sys.stderr
    p = _serve_parser()
    try:
        if not p.parse(argv, out):
            return 0
        # the shared parser stops option processing at the last
        # positional (`run`'s trailing args are guest argv payload);
        # serve has no payload, so `serve app.wasm func --lanes 4`
        # must keep parsing options instead of dropping them
        if p.rest:
            trailing, p.rest = p.rest, []
            if not p.parse(trailing, out):
                return 0
            if p.rest:
                raise ValueError(
                    f"unexpected argument {p.rest[0]!r}")
    except ValueError as e:
        err.write(f"wasmedge-tpu: {e}\n")
        return 2
    conf = Configure()
    conf.host_registrations.add(HostRegistration.Wasi)
    if p._opts["queue-capacity"].seen:
        conf.serve.queue_capacity = p._opts["queue-capacity"].value
    if p._opts["autotune"].value:
        conf.serve.autotune = True
        conf.obs.enabled = True   # the tuner reads the drain histograms
    if p._opts["checkpoint-every"].seen:
        conf.serve.checkpoint_every_rounds = p._opts["checkpoint-every"].value
    if p._opts["max-virtual-lanes"].seen:
        conf.hv.max_virtual_lanes = p._opts["max-virtual-lanes"].value
    if p._opts["resident-budget-bytes"].seen:
        conf.hv.resident_budget_bytes = \
            p._opts["resident-budget-bytes"].value
    if p._opts["swap-dir"].seen:
        conf.hv.swap_dir = p._opts["swap-dir"].value
    if p._opts["compact"].value:
        conf.batch.compact = True
    if p._opts["trace-out"].seen or p._opts["metrics-out"].seen:
        conf.obs.enabled = True

    from wasmedge_tpu.vm import VM

    path, func = p.positional_values[0], p.positional_values[1]
    vm = VM(conf)
    if vm.wasi_module is not None:
        vm.wasi_module.init_wasi(dirs=[], prog_name=path)
    try:
        vm.load_wasm(path)
        vm.validate()
        vm.instantiate()
    except WasmError as e:
        err.write(f"wasmedge-tpu: load failed: {e.formatted()}\n")
        return 1
    except OSError as e:
        err.write(f"wasmedge-tpu: cannot read {path}: {e}\n")
        return 1

    import time as _time

    import numpy as np

    server = vm.serve(lanes=p._opts["lanes"].value,
                      checkpoint_dir=p._opts["checkpoint-dir"].value,
                      resume=p._opts["resume"].value)
    # adopted in-flight requests complete alongside the fresh stream and
    # land in the same counters — the exit check must expect them too
    nadopted = len(server.adopted)
    try:
        # fail like run_command's "function not found", not a traceback
        server.recycler.func_idx(func)
    except (KeyError, ValueError) as e:
        err.write(f"wasmedge-tpu: {e.args[0] if e.args else e}\n")
        return 1
    rng = np.random.RandomState(p._opts["seed"].value)
    nreq = p._opts["requests"].value
    ntenants = max(p._opts["tenants"].value, 1)
    lo_a = p._opts["arg-min"].value
    hi_a = max(p._opts["arg-max"].value, lo_a)
    deadline_ms = p._opts["deadline-ms"].value

    futures = []
    t0 = _time.monotonic()
    try:
        for i in range(nreq):
            args = [int(rng.randint(lo_a, hi_a + 1))]
            while True:
                try:
                    futures.append(server.submit(
                        func, args, tenant=f"tenant{i % ntenants}",
                        deadline_s=deadline_ms / 1000.0
                        if deadline_ms is not None else None))
                    break
                except WasmError as e:
                    # the structured rejection contract: only a
                    # retryable rejection (backpressure) is worth a
                    # retry — permanent conditions re-raise unchanged
                    if not e.retryable:
                        raise
                    # backpressure: serve a round to free queue space
                    if not server.step():
                        if server.failed is not None:
                            # surface the terminal engine failure, not
                            # the stale backpressure signal it caused
                            raise server.failed from None
                        raise
        server.run_until_idle()
    except WasmError as e:
        err.write(f"wasmedge-tpu: serve failed: {e}\n")
        return 1
    wall = _time.monotonic() - t0
    lat = sorted(f.t_done - t0 for f in futures if f.t_done is not None)
    c = server.counters
    # true utilization: retired instructions over device step-lanes
    occupancy = (c["retired_instructions"]
                 / max(server.total * server.lanes, 1))
    summary = {
        "metric": "serve_cli",
        "requests": nreq,
        "adopted": nadopted,
        "completed": c["completed"],
        "trapped": c["trapped"],
        "expired": c["expired"],
        "killed": c["killed"],
        "recycled_lanes": c["recycled_lanes"],
        "rounds": c["rounds"],
        "occupancy": round(occupancy, 4),
        "wall_s": round(wall, 3),
        "req_per_s": round(nreq / wall, 1) if wall > 0 else 0.0,
        "p50_latency_s": round(_percentile(lat, 0.5), 4) if lat else None,
        "p99_latency_s": round(_percentile(lat, 0.99), 4) if lat else None,
    }
    hv = server.hv_stats()
    if hv is not None:
        summary["swaps_in"] = hv["swaps_in"]
        summary["swaps_out"] = hv["swaps_out"]
        summary["peak_admitted"] = hv["peak_admitted"]
        summary["resident_cap"] = hv["resident_cap"]
    out.write(json.dumps(summary) + "\n")
    if conf.obs.enabled:
        rec = server.obs
        if p._opts["trace-out"].seen:
            from wasmedge_tpu.obs.trace import export_chrome_trace

            export_chrome_trace(rec, p._opts["trace-out"].value)
        if p._opts["metrics-out"].seen:
            from wasmedge_tpu.obs.metrics import export_prometheus

            export_prometheus(p._opts["metrics-out"].value, recorder=rec,
                              stats=vm.statistics(),
                              hostcall_stats=server.engine.hostcall_stats,
                              hv_stats=hv)
    return 0 if c["completed"] + c["trapped"] + c["expired"] \
        + c["killed"] == nreq + nadopted else 1


def _gateway_parser() -> ArgumentParser:
    p = ArgumentParser("wasmedge-tpu gateway",
                       "network-facing multi-tenant serving gateway: "
                       "HTTP invoke/poll, runtime module registration, "
                       "per-tenant auth/rate/quota")
    p.add_option(["host"], Option("bind address", "addr",
                                  default="127.0.0.1"))
    p.add_option(["port"], Option("bind port (0 = ephemeral; the bound "
                                  "port is printed)", "n", typ=int,
                                  default=8080))
    p.add_option(["lanes"], Option("device lanes per serving generation",
                                   "n", typ=int, default=64))
    p.add_option(["devices"],
                 Option("serve over N devices (single-program mesh "
                        "drive, lane-sharded serving pool; lanes round "
                        "up to a device multiple)", "n", typ=int))
    p.add_option(["module"],
                 ListOpt("preload a guest module as NAME=PATH "
                         "(repeatable; more can be registered at "
                         "runtime via POST /v1/modules)", "name=path"))
    p.add_option(["tenants"],
                 Option("tenant policy file (JSON or .toml): api keys, "
                        "weights, quotas, rate limits", "file"))
    p.add_option(["queue-capacity"],
                 Option("bounded request queue capacity "
                        "(backpressure -> 429)", "n", typ=int))
    p.add_option(["max-virtual-lanes"],
                 Option("oversubscribe each serving generation: admit "
                        "up to N concurrent requests (resident + "
                        "host-swapped virtual lanes; default = "
                        "--lanes)", "n", typ=int))
    p.add_option(["resident-budget-bytes"],
                 Option("cap device-resident lane bytes per "
                        "generation (admission counts the budget "
                        "instead of the raw free-lane count)", "b",
                        typ=int))
    p.add_option(["compact"],
                 Toggle("divergence-aware lane compaction on every "
                        "serving generation: PC-sorted lane regrouping "
                        "at launch boundaries"))
    p.add_option(["suspend"],
                 Toggle("guest suspend/resume via effect handlers: "
                        "blocking hostcalls (poll_oneoff sleeps, "
                        "wasmedge.await_event) park the session at "
                        "zero resident cost until POST "
                        "/v1/requests/<id>/wake or its timer"))
    p.add_option(["audit"],
                 Toggle("shadow-audit lanes: re-execute a seeded lane "
                        "sample at launch boundaries and compare "
                        "bit-exact; divergence rolls back, masks, and "
                        "feeds the device-quarantine ladder"))
    p.add_option(["scrub"],
                 Option("at-rest integrity scrubbing every N seconds: "
                        "re-verify swap blobs / checkpoint members / "
                        "compile-cache entries, repair from mirror or "
                        "fleet peer, else evict (0 = off)", "s",
                        typ=float))
    p.add_option(["obs"],
                 Toggle("enable the flight recorder (gateway/<tenant> "
                        "spans, drain histograms; served at /metrics)"))
    p.add_option(["state-dir"],
                 Option("durable gateway state directory: registered "
                        "module store + async-request journal + serve "
                        "checkpoints (crash/restart survivable)",
                        "dir"))
    p.add_option(["resume"],
                 Toggle("adopt an existing --state-dir at startup: "
                        "re-register the stored module set, restore "
                        "the serving checkpoint lineage, re-queue "
                        "journaled unresolved request ids"))
    p.add_option(["build-timeout"],
                 Option("generation build timeout in seconds; a build "
                        "exceeding it rolls back with a retryable 503 "
                        "(default 120)", "s", typ=float))
    p.add_option(["result-cache"],
                 Option("resolved async requests kept pollable (and "
                        "durably replayable) before pruning "
                        "(default 4096)", "n", typ=int))
    p.add_option(["duration"],
                 Option("serve for N seconds then drain and exit "
                        "(default: until SIGINT)", "s", typ=float))
    p.add_option(["peer"],
                 ListOpt("federate with the gateway at HOST:PORT "
                         "(repeatable; wasmedge_tpu/fleet/: peer-"
                         "replicated module store, rendezvous request "
                         "routing, journal-replicated failover, "
                         "cross-host lane migration)", "host:port"))
    p.add_option(["fleet-heartbeat"],
                 Option("peer heartbeat interval in seconds "
                        "(default 0.25; drives the suspect->dead "
                        "liveness state machine)", "s", typ=float))
    p.add_positional("wasm_file", "guest module registered as 'main'",
                     required=False)
    return p


def gateway_command(argv: List[str], out=None, err=None) -> int:
    """`wasmedge-tpu gateway [app.wasm] [options]`: serve the gateway
    until SIGINT (or --duration), printing one JSON line with the
    bound address at startup and one summary line at shutdown."""
    import json
    import time as _time

    out = out or sys.stdout
    err = err or sys.stderr
    p = _gateway_parser()
    try:
        if not p.parse(argv, out):
            return 0
        if p.rest:   # same trailing-options idiom as serve_command
            trailing, p.rest = p.rest, []
            if not p.parse(trailing, out):
                return 0
            if p.rest:
                raise ValueError(f"unexpected argument {p.rest[0]!r}")
    except ValueError as e:
        err.write(f"wasmedge-tpu: {e}\n")
        return 2
    conf = Configure()
    conf.host_registrations.add(HostRegistration.Wasi)
    if p._opts["queue-capacity"].seen:
        conf.serve.queue_capacity = p._opts["queue-capacity"].value
    if p._opts["max-virtual-lanes"].seen:
        conf.hv.max_virtual_lanes = p._opts["max-virtual-lanes"].value
    if p._opts["resident-budget-bytes"].seen:
        conf.hv.resident_budget_bytes = \
            p._opts["resident-budget-bytes"].value
    if p._opts["compact"].value:
        conf.batch.compact = True
    if p._opts["suspend"].value:
        conf.effects.suspend = True
    if p._opts["audit"].value:
        conf.integrity.audit = True
    if p._opts["scrub"].seen and p._opts["scrub"].value > 0:
        conf.integrity.scrub = True
        conf.integrity.scrub_interval_s = p._opts["scrub"].value
    if p._opts["obs"].value:
        conf.obs.enabled = True

    from wasmedge_tpu.gateway import Gateway, GatewayService, \
        GatewayTenants

    tenants = None
    if p._opts["tenants"].seen:
        try:
            tenants = GatewayTenants.from_file(p._opts["tenants"].value)
        except (OSError, ValueError, KeyError) as e:
            err.write(f"wasmedge-tpu: bad tenants file: {e}\n")
            return 2
    if p._opts["resume"].value and not p._opts["state-dir"].seen:
        err.write("wasmedge-tpu: --resume requires --state-dir\n")
        return 2
    # the fleet controller is ALWAYS on for the CLI gateway (a no-peer
    # FleetConfig is inert and pinned bit-identical to a non-federated
    # gateway): the /v1/fleet/* routes must answer even on a gateway
    # started without --peer, or a peer that lists THIS address could
    # never introduce itself and one-directional configs would never
    # converge
    from wasmedge_tpu.fleet import FleetConfig

    fleet = FleetConfig(
        peers=p._opts["peer"].value,
        heartbeat_s=p._opts["fleet-heartbeat"].value
        if p._opts["fleet-heartbeat"].seen else 0.25)
    try:
        svc = GatewayService(
            conf=conf, lanes=p._opts["lanes"].value, tenants=tenants,
            devices=p._opts["devices"].value,
            state_dir=p._opts["state-dir"].value,
            resume=p._opts["resume"].value,
            build_timeout_s=p._opts["build-timeout"].value
            if p._opts["build-timeout"].seen else 120.0,
            result_cache=p._opts["result-cache"].value
            if p._opts["result-cache"].seen else 4096,
            fleet=fleet)
    except (WasmError, ValueError, OSError) as e:
        err.write(f"wasmedge-tpu: gateway resume failed: {e}\n")
        return 1
    boot = []
    if p.positional_values:
        boot.append(("main", p.positional_values[0]))
    for spec in p._opts["module"].value:
        name, sep, path = spec.partition("=")
        if not sep or not name or not path:
            err.write(f"wasmedge-tpu: bad --module {spec!r} "
                      f"(want NAME=PATH)\n")
            return 2
        boot.append((name, path))
    entries = []
    for name, path in boot:
        try:
            with open(path, "rb") as f:
                entries.append((name, f.read()))
        except OSError as e:
            err.write(f"wasmedge-tpu: cannot read {path}: {e}\n")
            return 1
    if p._opts["resume"].value:
        # a restart reuses the SAME command line (systemd et al.): boot
        # modules the manifest already restored must not re-register
        # and collide with themselves
        restored = set(svc.registry.names)
        entries = [(n, b) for n, b in entries if n not in restored]
    if entries:
        try:
            # ONE generation for the whole boot set — not a build-and-
            # drain per module
            svc.preload(entries)
        except (WasmError, ValueError) as e:
            err.write(f"wasmedge-tpu: boot module rejected: {e}\n")
            return 1
    # truthful-health boot gate: a dead driver thread or a terminally
    # failed boot generation must fail the command, not silently serve
    # 503s until someone notices (the /healthz fix's CLI half)
    health = svc.health()
    if health["status"] == "unhealthy":
        bad = "; ".join(c["detail"] for c in health["checks"].values()
                        if not c["ok"])
        err.write(f"wasmedge-tpu: gateway unhealthy after boot: "
                  f"{bad}\n")
        svc.shutdown(drain=False)
        return 1
    try:
        gw = Gateway(svc, host=p._opts["host"].value,
                     port=p._opts["port"].value).start()
    except OSError as e:
        err.write(f"wasmedge-tpu: cannot bind: {e}\n")
        svc.shutdown(drain=False)
        return 1
    out.write(json.dumps({
        "listening": f"http://{gw.host}:{gw.port}",
        "modules": svc.registry.names,
        "lanes": svc.lanes,
        "device": svc.device_info(),
        "tenants": sorted(svc.tenants.policies),
        "health": health["status"],
        "durable": svc.durable is not None,
        "restarts": svc.counters["restarts"],
        "resumed_requests": svc.counters["resumed"],
        "fleet_peers": sorted(svc.fleet.peers)
        if svc.fleet is not None else None,
    }) + "\n")
    out.flush()
    duration = p._opts["duration"].value
    try:
        if duration is not None:
            _time.sleep(duration)
        else:
            while True:
                _time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        gw.shutdown(drain=True)
    st = svc.status()
    out.write(json.dumps({"metric": "gateway_exit",
                          **st["gateway"], "http": st["http"]}) + "\n")
    return 0


def _analyze_parser() -> ArgumentParser:
    p = ArgumentParser("wasmedge-tpu analyze",
                       "static bytecode analysis over the lowered "
                       "image: per-function CFG, cost/gas bounds, "
                       "loop/recursion verdicts, hostcall inventory, "
                       "divergence scores, footprint bounds")
    p.add_option("disasm",
                 Toggle("include the block-annotated disassembly in "
                        "the report (\"disasm\" key)"))
    p.add_option(["out"],
                 Option("write the JSON report to a file instead of "
                        "stdout", "path"))
    p.add_option(["compact"],
                 Toggle("one-line JSON (default pretty-prints)"))
    p.add_positional("wasm_file", "WebAssembly file to analyze")
    return p


def analyze_command(argv: List[str], out=None, err=None) -> int:
    """`wasmedge-tpu analyze app.wasm [--disasm] [--out report.json]`:
    load + validate (no instantiation — unlinkable imports still
    analyze), run the static analyzer over the lowered image, and emit
    the JSON report (wasmedge-tpu/analysis/v1 schema)."""
    import json

    out = out or sys.stdout
    err = err or sys.stderr
    p = _analyze_parser()
    try:
        if not p.parse(argv, out):
            return 0
        if p.rest:   # same trailing-options idiom as serve_command
            trailing, p.rest = p.rest, []
            if not p.parse(trailing, out):
                return 0
            if p.rest:
                raise ValueError(f"unexpected argument {p.rest[0]!r}")
    except ValueError as e:
        err.write(f"wasmedge-tpu: {e}\n")
        return 2
    path = p.positional_values[0]
    conf = Configure()
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as e:
        err.write(f"wasmedge-tpu: cannot read {path}: {e}\n")
        return 1
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.validator import Validator

    try:
        mod = Validator(conf).validate(Loader(conf).parse_module(data))
    except WasmError as e:
        err.write(f"wasmedge-tpu: load failed: {e.formatted()}\n")
        return 1
    from wasmedge_tpu.analysis import analyze_validated

    analysis = analyze_validated(mod)
    report = analysis.to_dict()
    report["file"] = path
    # superinstruction translation view (batch/fuse.py): plan the fused
    # dispatch cells the batch engine would realize, so the report
    # shows planned-vs-realized per candidate.  numpy-only (no jax);
    # a planner failure degrades to a report without the section.
    fusion = None
    try:
        from wasmedge_tpu.batch.fuse import plan_fusion
        from wasmedge_tpu.batch.image import build_device_image

        img = build_device_image(mod.lowered, mod=mod)
        fusion = plan_fusion(img, conf.batch, analysis=analysis)
        report["fusion"] = fusion
    except Exception as e:  # advisory section, never a CLI failure
        err.write(f"wasmedge-tpu: fusion planning skipped: {e!r}\n")
    if p._opts["disasm"].value:
        report["disasm"] = analysis.annotated_disasm(mod.lowered,
                                                     fusion=fusion)
    text = json.dumps(report,
                      indent=None if p._opts["compact"].value else 2)
    if p._opts["out"].seen:
        from wasmedge_tpu.utils.fsio import atomic_write_bytes

        atomic_write_bytes(p._opts["out"].value, (text + "\n").encode())
        out.write(f"written: {p._opts['out'].value}\n")
    else:
        out.write(text + "\n")
    return 0


def compile_command(argv: List[str], out=None, err=None) -> int:
    out = out or sys.stdout
    err = err or sys.stderr
    p = ArgumentParser("wasmedge-tpu compile",
                       "precompile wasm to a universal twasm artifact")
    p.add_option("dump", Toggle("dump the lowered image disassembly"))
    p.add_option(["no-cache"], Toggle("bypass the content-addressed cache"))
    p.add_positional("in_wasm", "input wasm file")
    p.add_positional("out_wasm", "output artifact", required=False)
    try:
        if not p.parse(argv, out):
            return 0
    except ValueError as e:
        err.write(f"wasmedge-tpu: {e}\n")
        return 2

    from wasmedge_tpu import aot

    with open(p.positional_values[0], "rb") as f:
        data = f.read()
    try:
        artifact = (aot.compile_module(data) if p._opts["no-cache"].value
                    else aot.compile_cached(data))
    except WasmError as e:
        err.write(f"wasmedge-tpu: compile failed: {e}\n")
        return 1
    if p._opts["dump"].value:
        from wasmedge_tpu.loader.loader import Loader
        from wasmedge_tpu.validator.validator import Validator

        mod = Validator().validate(Loader().parse_module(artifact))
        out.write(mod.lowered.disasm() + "\n")
    if len(p.positional_values) > 1:
        with open(p.positional_values[1], "wb") as f:
            f.write(artifact)
        out.write(f"written: {p.positional_values[1]} "
                  f"({len(artifact)} bytes)\n")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        sys.stdout.write(
            "usage: wasmedge-tpu [run|serve|gateway|analyze|compile|"
            "version] ...\n"
            "  run      run a wasm file (default when first arg is a file)\n"
            "  serve    continuous-batching serving over device lanes\n"
            "  gateway  HTTP multi-tenant serving gateway (runtime module\n"
            "           registration, per-tenant auth/rate/quota)\n"
            "  analyze  static bytecode analysis: CFG/cost/divergence\n"
            "           JSON report over the lowered image\n"
            "  compile  precompile to a universal twasm artifact\n"
            "  version  print version\n")
        return 0
    cmd, rest = argv[0], argv[1:]
    if cmd == "run":
        return run_command(rest)
    if cmd == "serve":
        return serve_command(rest)
    if cmd == "gateway":
        return gateway_command(rest)
    if cmd == "analyze":
        return analyze_command(rest)
    if cmd == "compile":
        return compile_command(rest)
    if cmd == "version":
        import wasmedge_tpu

        sys.stdout.write(f"wasmedge-tpu {wasmedge_tpu.__version__}\n")
        return 0
    return run_command(argv)  # implicit run: wasmedge-tpu app.wasm ...


if __name__ == "__main__":
    sys.exit(main())
