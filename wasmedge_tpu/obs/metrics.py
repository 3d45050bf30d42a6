"""Prometheus text-format export for batch observability.

One scrape-shaped snapshot aggregating everything a long-lived batch
server wants on a dashboard:

  - common/statistics.py counters (instructions, gas, wasm/host time)
  - per-kind hostcall drain latency histograms (flight recorder)
  - engine-tier residency seconds (supervisor ladder)
  - failure-taxonomy counts (FailureRecords by fault_class)
  - hostcall pipeline counters (tier-0/tier-1/serve rounds)
  - per-opcode retired counts when the device histogram plane was on

Rendering follows the Prometheus exposition format v0.0.4 (HELP/TYPE
comment lines, histogram `_bucket{le=...}` cumulative counts + `_sum` +
`_count`, escaped label values), so the output is scrapeable as-is by a
real Prometheus — and parseable by the test suite's strict parser.
"""

from __future__ import annotations

from typing import Optional


def _esc(v) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt(v) -> str:
    if isinstance(v, float):
        return repr(v)
    return str(v)


class _Writer:
    def __init__(self):
        self.lines = []
        self._typed = set()

    def head(self, name: str, typ: str, help_: str):
        if name in self._typed:
            return
        self._typed.add(name)
        self.lines.append(f"# HELP {name} {help_}")
        self.lines.append(f"# TYPE {name} {typ}")

    def sample(self, name: str, labels: Optional[dict], value):
        lab = ""
        if labels:
            inner = ",".join(f'{k}="{_esc(v)}"'
                             for k, v in sorted(labels.items()))
            lab = "{" + inner + "}"
        self.lines.append(f"{name}{lab} {_fmt(value)}")

    def render(self) -> str:
        return "\n".join(self.lines) + "\n"


def render_prometheus(recorder=None, stats=None, hostcall_stats=None,
                      failures=None, http_requests=None,
                      analysis_counts=None, gateway_counts=None,
                      shed_counts=None, hv_stats=None,
                      fleet_stats=None, reshard_counts=None,
                      autoscale_actions=None,
                      compile_cache_counts=None,
                      snapshot_counts=None,
                      session_stats=None,
                      integrity_stats=None) -> str:
    """Render one metrics snapshot.  All sources optional: `recorder` a
    FlightRecorder, `stats` a common.statistics.Statistics, `hostcall_stats`
    an engine's pipeline counter dict, `failures` extra FailureRecords
    (e.g. statistics.recent_failures()) merged into the taxonomy counts,
    `http_requests` the gateway's {status_code: count} edge tally,
    `analysis_counts` the gateway's static-analysis admission summary
    ({"bounded": n, "unbounded": n, "policy_rejected": n}),
    `gateway_counts` the gateway's durability/robustness counters
    ({"restarts": n, "rollbacks": n}), `shed_counts` the per-tenant
    degraded-mode shed tally, `hv_stats` a BatchServer.hv_stats()
    lane-virtualization snapshot (wasmedge_tpu/hv/), `fleet_stats` a
    FleetController.stats() federation snapshot (wasmedge_tpu/fleet/),
    `reshard_counts` the gateway's {direction: count} live-reshard
    tally (emitted only when a reshard has happened), and
    `autoscale_actions` the AutoscaleController's {action: count}
    tally (emitted only when the controller is constructed) — both
    r21; a gateway without them renders bit-identically to r16.
    `compile_cache_counts` the registry compile cache's counter dict
    and `snapshot_counts` the imagestore snapshot tally — both r22,
    passed only when Configure.imagestore is active, so a gateway
    without the subsystem renders bit-identically to r21.
    `session_stats` an EffectsRuntime.stats() suspend/resume snapshot
    (wasmedge_tpu/effects/) — r23, passed only when Configure.effects
    is active, so a gateway without it renders bit-identically to
    r22.  `integrity_stats` a GatewayService.integrity_stats() block
    ({"audit": ShadowAuditor.stats, "quarantine":
    DeviceQuarantine.snapshot(), "scrub": Scrubber.snapshot()}, each
    key optional) — r24, passed only when Configure.integrity is
    active, so a gateway without it renders bit-identically to r23."""
    w = _Writer()

    if compile_cache_counts:
        w.head("wasmedge_compile_cache_hits_total", "counter",
               "Content-addressed compile-cache hits by tier: probe = "
               "in-process parked-engine adoption, disk = persistent "
               "cross-process image payload (imagestore/compilecache).")
        w.sample("wasmedge_compile_cache_hits_total", {"tier": "probe"},
                 int(compile_cache_counts.get("probe_hits", 0)))
        w.sample("wasmedge_compile_cache_hits_total", {"tier": "disk"},
                 int(compile_cache_counts.get("disk_hits", 0)))
        w.head("wasmedge_compile_cache_misses_total", "counter",
               "Registrations that lowered fresh: no cache entry, a "
               "corrupt/mismatched entry, or a faulted read (the last "
               "two also count in their own kinds below).")
        w.sample("wasmedge_compile_cache_misses_total", None,
                 int(compile_cache_counts.get("misses", 0)))
        if compile_cache_counts.get("corrupt") or \
                compile_cache_counts.get("read_faults"):
            w.head("wasmedge_compile_cache_errors_total", "counter",
                   "Cache entries rejected (corrupt = integrity/decode "
                   "failure, read_fault = injected/IO read fault); "
                   "every one fell back to a fresh lower.")
            for kind in ("corrupt", "read_faults"):
                if compile_cache_counts.get(kind):
                    w.sample("wasmedge_compile_cache_errors_total",
                             {"kind": kind},
                             int(compile_cache_counts[kind]))

    if snapshot_counts:
        w.head("wasmedge_snapshot_installs_total", "counter",
               "Lanes admitted through a pre-initialized snapshot "
               "overlay instead of init replay (imagestore/snapshot).")
        w.sample("wasmedge_snapshot_installs_total", None,
                 int(snapshot_counts.get("installs", 0)))
        w.head("wasmedge_snapshot_captures_total", "counter",
               "Registration-time snapshot captures by outcome "
               "(skipped = no init export / init parked or trapped).")
        for kind in ("captured", "skipped"):
            if snapshot_counts.get(kind):
                w.sample("wasmedge_snapshot_captures_total",
                         {"outcome": kind},
                         int(snapshot_counts[kind]))
        if snapshot_counts.get("install_faults") or \
                snapshot_counts.get("corrupt"):
            w.head("wasmedge_snapshot_errors_total", "counter",
                   "Snapshot overlays rejected at generation build "
                   "(faulted install, corrupt store entry); the "
                   "generation fell back to template init replay.")
            for kind in ("install_faults", "corrupt"):
                if snapshot_counts.get(kind):
                    w.sample("wasmedge_snapshot_errors_total",
                             {"kind": kind},
                             int(snapshot_counts[kind]))

    if fleet_stats:
        w.head("wasmedge_fleet_peers", "gauge",
               "Fleet peers by liveness state (wasmedge_tpu/fleet/: "
               "heartbeat-driven suspect->dead state machine).")
        peers = fleet_stats.get("peers", {})
        for state in ("alive", "suspect", "dead"):
            w.sample("wasmedge_fleet_peers", {"state": state},
                     int(peers.get(state, 0)))
        w.head("wasmedge_fleet_migrations_total", "counter",
               "Cross-host lane migrations (out = parked vlane "
               "shipped to a peer, in = adopted from one; SwapStore "
               "payloads hash-verified end to end).")
        w.sample("wasmedge_fleet_migrations_total", {"direction": "out"},
                 int(fleet_stats.get("migrations_out", 0)))
        w.sample("wasmedge_fleet_migrations_total", {"direction": "in"},
                 int(fleet_stats.get("migrations_in", 0)))
        w.head("wasmedge_fleet_adoptions_total", "counter",
               "Unresolved requests adopted from dead peers' "
               "replicated journals (re-queued at-least-once under "
               "their original ids).")
        w.sample("wasmedge_fleet_adoptions_total", None,
                 int(fleet_stats.get("adoptions", 0)))
        w.head("wasmedge_fleet_membership_epoch", "gauge",
               "Gossip membership view epoch (wasmedge_tpu/fleet/"
               "membership.py: bumps on join/leave origin events; a "
               "static fleet stays at 0).")
        w.sample("wasmedge_fleet_membership_epoch", None,
                 int(fleet_stats.get("membership_epoch", 0)))

    if reshard_counts:
        w.head("wasmedge_reshards_total", "counter",
               "Live reshards of the running generation by direction "
               "(serve/server.py reshard: device-set change at a "
               "launch boundary, resident lanes ride through).")
        for direction in sorted(reshard_counts):
            w.sample("wasmedge_reshards_total",
                     {"direction": str(direction)},
                     int(reshard_counts[direction]))

    if autoscale_actions is not None:
        w.head("wasmedge_autoscale_actions_total", "counter",
               "Autoscale controller actions by kind (gateway/"
               "autoscale.py: deterministic spike/calm ladder).")
        for action in sorted(autoscale_actions):
            w.sample("wasmedge_autoscale_actions_total",
                     {"action": str(action)},
                     int(autoscale_actions[action]))

    if hv_stats:
        w.head("wasmedge_hv_swaps_total", "counter",
               "Virtual-lane swaps by direction (wasmedge_tpu/hv/: "
               "out = lane state parked host-side, in = reinstalled "
               "onto a physical lane).")
        w.sample("wasmedge_hv_swaps_total", {"direction": "out"},
                 int(hv_stats.get("swaps_out", 0)))
        w.sample("wasmedge_hv_swaps_total", {"direction": "in"},
                 int(hv_stats.get("swaps_in", 0)))
        w.head("wasmedge_hv_resident_lanes", "gauge",
               "Physical lanes currently holding a request.")
        w.sample("wasmedge_hv_resident_lanes", None,
                 int(hv_stats.get("resident", 0)))
        w.head("wasmedge_hv_virtual_lanes", "gauge",
               "Admitted requests currently off-device (fresh + "
               "swapped virtual lanes).")
        w.sample("wasmedge_hv_virtual_lanes", None,
                 int(hv_stats.get("virtual", 0)))
        w.head("wasmedge_hv_resident_lane_cap", "gauge",
               "Physical lanes the resident-bytes budget admits.")
        w.sample("wasmedge_hv_resident_lane_cap", None,
                 int(hv_stats.get("resident_cap", 0)))
        w.head("wasmedge_hv_swap_store_bytes", "gauge",
               "Host bytes held by the swap store.")
        w.sample("wasmedge_hv_swap_store_bytes", None,
                 int(hv_stats.get("store_bytes", 0)))
        if hv_stats.get("swap_out_faults") or \
                hv_stats.get("swap_in_faults") or \
                hv_stats.get("swap_corrupt"):
            w.head("wasmedge_hv_swap_faults_total", "counter",
                   "Swap operations that failed (faulted swap-out/"
                   "swap-in retried; corrupt entries rejected).")
            for kind in ("swap_out_faults", "swap_in_faults",
                         "swap_corrupt"):
                if hv_stats.get(kind):
                    w.sample("wasmedge_hv_swap_faults_total",
                             {"kind": kind}, int(hv_stats[kind]))

    if session_stats:
        w.head("wasmedge_sessions_parked", "gauge",
               "Guest sessions suspended off-device awaiting an "
               "external wake or a timer (wasmedge_tpu/effects/: "
               "parked through the SwapStore, zero resident lanes).")
        w.sample("wasmedge_sessions_parked", None,
                 int(session_stats.get("parked", 0)))
        w.head("wasmedge_session_wakes_total", "counter",
               "Parked-session wakes by source (http = POST "
               "/v1/requests/<id>/wake payload delivery, timer = "
               "deterministic timer-wheel expiry).")
        w.sample("wasmedge_session_wakes_total", {"source": "http"},
                 int(session_stats.get("wakes_http", 0)))
        w.sample("wasmedge_session_wakes_total", {"source": "timer"},
                 int(session_stats.get("wakes_timer", 0)))
        w.head("wasmedge_session_parks_total", "counter",
               "Suspend transitions completed (lane serialized, "
               "journaled, and freed at a launch boundary).")
        w.sample("wasmedge_session_parks_total", None,
                 int(session_stats.get("parks", 0)))
        w.head("wasmedge_session_resumes_total", "counter",
               "Woken sessions reinstalled onto a physical lane.")
        w.sample("wasmedge_session_resumes_total", None,
                 int(session_stats.get("resumes", 0)))
        hist = session_stats.get("park_seconds")
        if hist is not None:
            w.head("wasmedge_session_park_seconds", "histogram",
                   "Wall seconds each completed park spent suspended "
                   "(park boundary to lane reinstall).")
            cum = 0
            for ub in sorted(hist.get("buckets", {}),
                             key=lambda k: float(k)):
                cum += int(hist["buckets"][ub])
                w.sample("wasmedge_session_park_seconds_bucket",
                         {"le": ub}, cum)
            w.sample("wasmedge_session_park_seconds_bucket",
                     {"le": "+Inf"}, int(hist.get("count", 0)))
            w.sample("wasmedge_session_park_seconds_sum", None,
                     float(hist.get("sum", 0.0)))
            w.sample("wasmedge_session_park_seconds_count", None,
                     int(hist.get("count", 0)))
        if session_stats.get("park_faults") or \
                session_stats.get("wake_faults") or \
                session_stats.get("corrupt"):
            w.head("wasmedge_session_faults_total", "counter",
                   "Suspend-path operations that failed (faulted park "
                   "left the lane resident and retried; faulted wake "
                   "re-queued; corrupt store entries rejected).")
            for kind in ("park_faults", "wake_faults", "corrupt"):
                if session_stats.get(kind):
                    w.sample("wasmedge_session_faults_total",
                             {"kind": kind},
                             int(session_stats[kind]))

    if integrity_stats:
        audit = integrity_stats.get("audit")
        if audit is not None:
            w.head("wasmedge_integrity_audits_total", "counter",
                   "Shadow-audit verdicts at launch boundaries "
                   "(wasmedge_tpu/integrity: a seeded lane subset "
                   "re-executed on the reference tier and compared "
                   "bit-exact; divergence = silent data corruption "
                   "detected, rolled back, and re-executed).")
            for verdict in ("match", "divergence", "skipped_rng",
                            "error"):
                w.sample("wasmedge_integrity_audits_total",
                         {"verdict": verdict},
                         int(audit.get(verdict, 0)))
        quar = integrity_stats.get("quarantine")
        if quar is not None:
            w.head("wasmedge_integrity_quarantined_devices", "gauge",
                   "Devices ejected from the serving mesh after "
                   "repeated audit-divergence attribution (integrity/"
                   "quarantine.py ladder, ejection via live reshard).")
            w.sample("wasmedge_integrity_quarantined_devices", None,
                     len(quar.get("ejected", ())))
        scrub = integrity_stats.get("scrub")
        if scrub is not None:
            w.head("wasmedge_integrity_scrub_entries_total", "counter",
                   "At-rest scrub outcomes over content-addressed "
                   "state (swap blobs, checkpoint members, compile-"
                   "cache entries): entries walked, corruption found, "
                   "repairs (mirror or fleet replica), evictions, "
                   "unrepairable counts (integrity/scrub.py).")
            for kind in ("entries", "corrupt", "repaired", "evicted",
                         "unrepairable", "read_faults",
                         "quarantined_members"):
                w.sample("wasmedge_integrity_scrub_entries_total",
                         {"kind": kind}, int(scrub.get(kind, 0)))
            w.head("wasmedge_integrity_scrub_passes_total", "counter",
                   "Completed at-rest scrub walks.")
            w.sample("wasmedge_integrity_scrub_passes_total", None,
                     int(scrub.get("scans", 0)))
            w.head("wasmedge_integrity_scrub_last_seconds", "gauge",
                   "Wall seconds the most recent scrub pass took.")
            w.sample("wasmedge_integrity_scrub_last_seconds", None,
                     float(scrub.get("last_seconds", 0.0)))

    if gateway_counts is not None:
        w.head("wasmedge_gateway_restarts_total", "counter",
               "Gateway crash/restart resumes over this state dir "
               "(durable count, gateway/durable.py manifest).")
        w.sample("wasmedge_gateway_restarts_total", None,
                 int(gateway_counts.get("restarts", 0)))
        w.head("wasmedge_generation_rollbacks_total", "counter",
               "Serving-generation builds/swaps that failed or timed "
               "out and rolled back atomically (gateway/service.py).")
        w.sample("wasmedge_generation_rollbacks_total", None,
                 int(gateway_counts.get("rollbacks", 0)))

    if shed_counts:
        w.head("wasmedge_gateway_shed_total", "counter",
               "Submissions shed at the edge while the gateway was "
               "degraded, by tenant (gateway/health.py ShedLoad).")
        for tenant in sorted(shed_counts):
            w.sample("wasmedge_gateway_shed_total",
                     {"tenant": str(tenant)},
                     int(shed_counts[tenant]))

    if analysis_counts and any(analysis_counts.values()):
        w.head("wasmedge_analysis_modules_total", "counter",
               "Modules vetted by the static analyzer at registration, "
               "by cost verdict (wasmedge_tpu/analysis/).")
        for verdict in ("bounded", "unbounded"):
            if analysis_counts.get(verdict):
                w.sample("wasmedge_analysis_modules_total",
                         {"verdict": verdict},
                         int(analysis_counts[verdict]))
        w.head("wasmedge_analysis_policy_rejections_total", "counter",
               "Registrations rejected by a static admission policy "
               "(analysis/policy.py AnalysisPolicy).")
        w.sample("wasmedge_analysis_policy_rejections_total", None,
                 int(analysis_counts.get("policy_rejected", 0)))

    if http_requests:
        w.head("wasmedge_gateway_http_requests_total", "counter",
               "Gateway HTTP responses by status code "
               "(wasmedge_tpu/gateway/).")
        for code in sorted(http_requests):
            w.sample("wasmedge_gateway_http_requests_total",
                     {"code": str(code)}, int(http_requests[code]))

    if stats is not None:
        w.head("wasmedge_instructions_total", "counter",
               "Instructions retired (Statistics.instr_count).")
        w.sample("wasmedge_instructions_total", None,
                 int(stats.instr_count))
        w.head("wasmedge_gas_cost_total", "counter",
               "Weighted gas cost consumed (Statistics.total_cost).")
        w.sample("wasmedge_gas_cost_total", None, int(stats.total_cost))
        w.head("wasmedge_exec_seconds_total", "counter",
               "Execution wall seconds split by where they were spent.")
        w.sample("wasmedge_exec_seconds_total", {"where": "wasm"},
                 stats.wasm_ns / 1e9)
        w.sample("wasmedge_exec_seconds_total", {"where": "host"},
                 stats.host_ns / 1e9)

    # Failure taxonomy: the SAME FailureRecord is mirrored into the
    # recorder, the run's Statistics, and the process-wide log, so
    # summing sources would double-count every incident.  Each source
    # individually counts the incidents it saw — merge by max per
    # class (covers classes only one source observed).
    counts = {}
    if recorder is not None:
        for fc, n in recorder.failure_counts.items():
            counts[fc] = max(counts.get(fc, 0), int(n))
    for src in ((stats.failures if stats is not None else []),
                (failures or [])):
        seen = {}
        for rec in src:
            fc = getattr(rec, "fault_class", "unknown")
            seen[fc] = seen.get(fc, 0) + 1
        for fc, n in seen.items():
            counts[fc] = max(counts.get(fc, 0), n)
    if counts:
        w.head("wasmedge_failures_total", "counter",
               "Supervised-execution incidents by fault class "
               "(FailureRecord taxonomy).")
        for fc in sorted(counts):
            w.sample("wasmedge_failures_total", {"fault_class": fc},
                     counts[fc])

    if recorder is not None:
        if recorder.hostcalls:
            name = "wasmedge_hostcall_drain_latency_seconds"
            w.head(name, "histogram",
                   "Tier-1 hostcall drain latency per WASI call kind "
                   "(one observation per drained group).")
            for kind in sorted(recorder.hostcalls):
                h = recorder.hostcalls[kind]
                for le, acc in h.cumulative():
                    w.sample(f"{name}_bucket",
                             {"kind": kind, "le": repr(float(le))}, acc)
                w.sample(f"{name}_bucket",
                         {"kind": kind, "le": "+Inf"}, h.count)
                w.sample(f"{name}_sum", {"kind": kind}, h.sum_s)
                w.sample(f"{name}_count", {"kind": kind}, h.count)
            w.head("wasmedge_hostcall_drained_lanes_total", "counter",
                   "Lanes served through the tier-1 drain per call kind.")
            for kind in sorted(recorder.hostcalls):
                w.sample("wasmedge_hostcall_drained_lanes_total",
                         {"kind": kind}, recorder.hostcalls[kind].lanes)
        hv_swaps = getattr(recorder, "hv_swaps", None)
        if hv_swaps:
            name = "wasmedge_hv_swap_latency_seconds"
            w.head(name, "histogram",
                   "Lane-virtualization swap latency by direction "
                   "(serialize+store for out, fetch+install for in).")
            for direction in sorted(hv_swaps):
                h = hv_swaps[direction]
                for le, acc in h.cumulative():
                    w.sample(f"{name}_bucket",
                             {"direction": direction,
                              "le": repr(float(le))}, acc)
                w.sample(f"{name}_bucket",
                         {"direction": direction, "le": "+Inf"},
                         h.count)
                w.sample(f"{name}_sum", {"direction": direction},
                         h.sum_s)
                w.sample(f"{name}_count", {"direction": direction},
                         h.count)
        admission = getattr(recorder, "admission", None)
        if admission is not None and admission.count:
            name = "wasmedge_serve_admission_latency_seconds"
            w.head(name, "histogram",
                   "Serving-layer admission latency: request submit() "
                   "to lane install (wasmedge_tpu/serve/).")
            for le, acc in admission.cumulative():
                w.sample(f"{name}_bucket", {"le": repr(float(le))}, acc)
            w.sample(f"{name}_bucket", {"le": "+Inf"}, admission.count)
            w.sample(f"{name}_sum", None, admission.sum_s)
            w.sample(f"{name}_count", None, admission.count)
        if recorder.tier_seconds:
            w.head("wasmedge_tier_residency_seconds", "counter",
                   "Wall seconds the batch spent on each engine tier "
                   "(supervisor degradation ladder).")
            for tier in sorted(recorder.tier_seconds):
                w.sample("wasmedge_tier_residency_seconds",
                         {"tier": tier}, recorder.tier_seconds[tier])
        conv = getattr(recorder, "convergence", None)
        if conv and conv.get("rounds"):
            w.head("wasmedge_convergence_unique_pcs", "gauge",
                   "Distinct active pcs among live lanes at the last "
                   "launch boundary (batch/compact.py divergence "
                   "estimate; 1 = fully convergent).")
            w.sample("wasmedge_convergence_unique_pcs", None,
                     int(conv.get("unique_pcs", 0)))
            w.head("wasmedge_convergence_largest_group_fraction",
                   "gauge",
                   "Largest convergent lane group as a fraction of "
                   "live lanes at the last launch boundary.")
            w.sample("wasmedge_convergence_largest_group_fraction",
                     None, round(float(conv.get("largest_group", 1.0)),
                                 6))
        n_compact = int(getattr(recorder, "compactions_total", 0))
        if n_compact:
            w.head("wasmedge_compactions_total", "counter",
                   "Lane compactions fired at launch boundaries "
                   "(PC-sorted regrouping, batch/compact.py).")
            w.sample("wasmedge_compactions_total", None, n_compact)
            h = recorder.compaction
            name = "wasmedge_compaction_latency_seconds"
            w.head(name, "histogram",
                   "Host-side latency of one fired lane compaction "
                   "(permutation build + dispatch).")
            for le, acc in h.cumulative():
                w.sample(f"{name}_bucket", {"le": repr(float(le))}, acc)
            w.sample(f"{name}_bucket", {"le": "+Inf"}, h.count)
            w.sample(f"{name}_sum", None, h.sum_s)
            w.sample(f"{name}_count", None, h.count)
        fused = getattr(recorder, "fused_counts", None)
        if fused and fused.get("retired_total"):
            w.head("wasmedge_fused_dispatches_total", "counter",
                   "Fused superinstruction dispatch cells executed on "
                   "the SIMT tier (each retires a whole straight-line "
                   "run in one dispatch, batch/fuse.py).")
            w.sample("wasmedge_fused_dispatches_total", None,
                     int(fused.get("dispatches", 0)))
            w.head("wasmedge_retired_by_path_total", "counter",
                   "Instructions retired by dispatch path: fused "
                   "superinstruction cells vs per-op dispatch.")
            rf = int(fused.get("retired_fused", 0))
            rt = int(fused.get("retired_total", 0))
            w.sample("wasmedge_retired_by_path_total",
                     {"path": "fused"}, rf)
            w.sample("wasmedge_retired_by_path_total",
                     {"path": "unfused"}, max(rt - rf, 0))
        tier = getattr(recorder, "tierup_counts", None)
        if tier and tier.get("retired_total"):
            w.head("wasmedge_tierup_dispatches_total", "counter",
                   "Compiled-function tier bodies dispatched (each "
                   "retires a whole function call in one dispatch, "
                   "batch/tierup.py).")
            w.sample("wasmedge_tierup_dispatches_total", None,
                     int(tier.get("dispatches", 0)))
            w.head("wasmedge_tierup_retired_total", "counter",
                   "Instructions retired by tier: compiled-function "
                   "bodies vs the interpreted SIMT path.")
            rc = int(tier.get("retired_comp", 0))
            rt = int(tier.get("retired_total", 0))
            w.sample("wasmedge_tierup_retired_total",
                     {"tier": "compiled"}, rc)
            w.sample("wasmedge_tierup_retired_total",
                     {"tier": "interpreted"}, max(rt - rc, 0))
        tus = getattr(recorder, "tierup_static", None)
        if tus:
            w.head("wasmedge_tierup_functions", "gauge",
                   "Whole functions promoted to the compiled tier "
                   "(batch/tierup.py plan_tierup) and counted loops "
                   "licensed as bounded device loops inside them.")
            w.sample("wasmedge_tierup_functions",
                     {"kind": "promoted"},
                     len(tus.get("promoted", ())))
            w.sample("wasmedge_tierup_functions",
                     {"kind": "device_loops"},
                     sum(int(p.get("device_loops", 0))
                         for p in tus.get("promoted", ())))
        mfs = getattr(recorder, "memfuse_static", None)
        if mfs:
            w.head("wasmedge_memfuse_runs", "gauge",
                   "Fused memory runs by license verdict: realized "
                   "(every load/store absint-licensed trap-free) vs "
                   "reverted load/store sites the license refused — "
                   "those stay on the per-op path (batch/fuse.py).")
            w.sample("wasmedge_memfuse_runs",
                     {"verdict": "licensed"},
                     int(mfs.get("mem_runs", 0)))
            w.sample("wasmedge_memfuse_runs",
                     {"verdict": "reverted_sites"},
                     int(mfs.get("unlicensed_sites", 0)))
        dps = getattr(recorder, "dispatch_static", None)
        if dps:
            w.head("wasmedge_dispatch_depth", "gauge",
                   "Branches one dispatch walks in the newest Pallas "
                   "kernel's dispatch tree: expected over the static "
                   "entry-slot weights, and the deepest handler "
                   "(batch/pallas_engine.py plan_dispatch_tree).")
            w.sample("wasmedge_dispatch_depth", {"stat": "expected"},
                     dps["expected"])
            w.sample("wasmedge_dispatch_depth", {"stat": "max"},
                     dps["max"])
        sbs = getattr(recorder, "superblock_static", None)
        if sbs is not None:
            w.head("wasmedge_superblock_edges", "gauge",
                   "Forward edges the newest Pallas kernel's fused "
                   "blocks run through instead of ending at: `br`s "
                   "absorbed as jumps, and guards whose taken side "
                   "runs the target's block as a tail "
                   "(batch/pallas_engine.py fuse_blocks).")
            for kind in ("jump", "guard_tail"):
                w.sample("wasmedge_superblock_edges", {"kind": kind},
                         int(sbs.get(kind, 0)))
        bss = getattr(recorder, "block_shapes_static", None)
        if bss is not None:
            w.head("wasmedge_block_shapes", "gauge",
                   "Block shapes of the newest Pallas kernel: `kept`, "
                   "those its plane holds, and `wanted`, the distinct "
                   "shapes its image's heads want; more wanted than "
                   "kept where MAX_BLOCK_SHAPES made fuse_blocks keep "
                   "those of the deepest loops "
                   "(batch/pallas_engine.py fuse_blocks_counted).")
            for kind in ("kept", "wanted"):
                w.sample("wasmedge_block_shapes", {"kind": kind},
                         int(bss.get(kind, 0)))
        shs = getattr(recorder, "shuffle_static", None)
        if shs is not None:
            w.head("wasmedge_shuffle_sites", "gauge",
                   "The `i8x16.shuffle` slots of the newest Pallas "
                   "kernel's image by lowering: `word` where the mask "
                   "moves whole 32-bit lanes and a fused block "
                   "re-orders rows at build time, `dynamic` where the "
                   "mask is fetched at run time "
                   "(batch/pallas_engine.py shuffle_sites).")
            for kind in ("word", "dynamic"):
                w.sample("wasmedge_shuffle_sites", {"kind": kind},
                         int(shs.get(kind, 0)))
        dnp = getattr(recorder, "donated_planes", None)
        if dnp is not None:
            w.head("wasmedge_kernel_donated_planes", "gauge",
                   "Plane arguments the newest Pallas kernel's launch "
                   "donates, so that the kernel writes them in place and "
                   "XLA copies none around it "
                   "(batch/pallas_engine.py donated_planes).")
            w.sample("wasmedge_kernel_donated_planes", None, dnp)
        pdc = getattr(recorder, "pallas_dispatches", 0)
        if pdc:
            w.head("wasmedge_pallas_dispatches_total", "counter",
                   "Handlers the Pallas kernels' loops dispatched, "
                   "summed over lane blocks and launches (a fused "
                   "block is one; a commit is none).")
            w.sample("wasmedge_pallas_dispatches_total", None, pdc)
        sfo = getattr(recorder, "softfloat_ops", 0)
        if sfo:
            w.head("wasmedge_softfloat_ops_total", "counter",
                   "Binary64 routines of batch/softfloat.py the Pallas "
                   "kernels ran (f64 arithmetic, comparisons and "
                   "conversions; a reinterpret is none), a lane-block "
                   "step each, summed over lane blocks and launches.")
            w.sample("wasmedge_softfloat_ops_total", None, sfo)
        sdo = getattr(recorder, "simd_ops", 0)
        if sdo:
            w.head("wasmedge_simd_ops_total", "counter",
                   "Instructions of a v128 class (v128.const through "
                   "v128.store) the Pallas kernels ran, a lane-block "
                   "step each, summed over lane blocks and launches.")
            w.sample("wasmedge_simd_ops_total", None, sdo)
        hcc = getattr(recorder, "hostcall_counts", None)
        if hcc and hcc["rounds"]:   # there once a run parked
            w.head("wasmedge_hostcall_rounds_total", "counter",
                   "Rounds of park, drain and re-arm the Pallas block "
                   "serve made: a kernel exit, the host's drain of the "
                   "parked blocks and a relaunch each.")
            w.sample("wasmedge_hostcall_rounds_total", None, hcc["rounds"])
            w.head("wasmedge_hostcall_calls_total", "counter",
                   "Host calls those rounds drained, a lane each, by the "
                   "path that served them: a tier-1 vectorised "
                   "implementation, or the per-lane loop.")
            w.sample("wasmedge_hostcall_calls_total",
                     {"path": "vectorized"}, hcc["vectorized"])
            w.sample("wasmedge_hostcall_calls_total",
                     {"path": "per_lane"},
                     hcc["calls"] - hcc["vectorized"])
            w.head("wasmedge_hostcall_out_bytes_total", "counter",
                   "Bytes those calls handed to an fd (fd_write, "
                   "fd_pwrite), as the WASI environ counts them.")
            w.sample("wasmedge_hostcall_out_bytes_total", None,
                     hcc["out_bytes"])
        sc = getattr(recorder, "split_counts", None)
        if sc and sc["launches"]:   # stays once the scheduler ran
            for key, name, text in (
                    ("launches", "wasmedge_kernel_launches_total",
                     "Launches of the optimistic Pallas kernel by the "
                     "block scheduler (batch/scheduler.py), one for "
                     "every round with a runnable block."),
                    ("splits", "wasmedge_block_splits_total",
                     "Lane blocks the scheduler split at an instruction "
                     "whose lanes disagreed, or handed to the per-step "
                     "engine there."),
                    ("rechecks", "wasmedge_careful_rechecks_total",
                     "Rounds of the careful kernel: after a rollback it "
                     "runs a block from its snapshot to the instruction "
                     "that stops it."),
                    ("careful_steps", "wasmedge_careful_steps_total",
                     "Block-steps the careful kernel retired in those "
                     "rounds."),
                    ("surgery_programs",
                     "wasmedge_block_surgery_programs_total",
                     "Compiled programs of block surgery: one that "
                     "gathers a split's child out of the planes, one "
                     "that sets it into a free slot."),
                    ("snap_restored",
                     "wasmedge_snapshot_intervals_restored_total",
                     "Children of a split installed with the full "
                     "snapshot interval in place of the halved one "
                     "their parent's row carried."),
                    ("snap_commits", "wasmedge_snapshot_commits_total",
                     "Periodic commits of the optimistic kernel, as "
                     "each launch's steps and snapshot interval imply "
                     "them (computed at the sync, not counted by the "
                     "kernel).")):
                w.head(name, "counter", text)
                w.sample(name, None, sc[key])
            w.head("wasmedge_batch_transfers_total", "counter",
                   "Transfers between host and device on the block "
                   "scheduler's path (batch/pallas_engine.py HostLink): "
                   "d2h blocks until the device has produced the array, "
                   "h2d uploads a host mirror or argument rows.")
            for way in ("d2h", "h2d"):
                w.sample("wasmedge_batch_transfers_total", {"dir": way},
                         sc[way + "_transfers"])
            w.head("wasmedge_batch_programs_enqueued_total", "counter",
                   "Calls of a compiled program by the block scheduler: "
                   "the optimistic and the careful kernel and the two "
                   "programs of block surgery.")
            w.sample("wasmedge_batch_programs_enqueued_total", None,
                     sc["programs_enqueued"])
        mst = getattr(recorder, "memory_static", None)
        if mst and "lane_block" in mst:     # a guest with a memory
            w.head("wasmedge_memory_lane_block", "gauge",
                   "Lane block of the newest Pallas kernel of a guest "
                   "with linear memory, labelled with how it holds "
                   "it: resident in VMEM, or hbm_window (window = "
                   "rows x ways; batch/pallas_engine.py _mem_mode).")
            w.sample("wasmedge_memory_lane_block",
                     {k: mst[k] for k in ("mem_mode", "window")
                      if k in mst}, mst["lane_block"])
        wc = getattr(recorder, "window_counts", None)
        if wc and wc["fills"]:      # stays once a window kernel ran
            w.head("wasmedge_hbm_window_fills_total", "counter",
                   "Window fills of the hbm_window kernel: DMAs "
                   "of window-rows x lane-block words from the "
                   "memory plane in HBM into a way in VMEM.")
            w.sample("wasmedge_hbm_window_fills_total", None,
                     wc["fills"])
            w.head("wasmedge_hbm_window_writebacks_total",
                   "counter",
                   "Dirty ways the hbm_window kernel wrote back "
                   "to the memory plane, same size as a fill.")
            w.sample("wasmedge_hbm_window_writebacks_total", None,
                     wc["writebacks"])
            w.head("wasmedge_hbm_window_accesses_total", "counter",
                   "Loads and stores the hbm_window kernel resolved "
                   "against the window, a lane block at a time; "
                   "all but the fills found their rows resident.")
            w.sample("wasmedge_hbm_window_accesses_total", None,
                     wc["accesses"])
        if recorder.opcode_counts is not None:
            from wasmedge_tpu.validator.image import lop_name

            w.head("wasmedge_opcode_retired_total", "counter",
                   "Instructions retired per opcode (device histogram "
                   "plane, Configure.obs.opcode_histogram).")
            for op_id, n in enumerate(recorder.opcode_counts):
                if n:
                    w.sample("wasmedge_opcode_retired_total",
                             {"op": lop_name(op_id)}, int(n))
        w.head("wasmedge_obs_events_total", "counter",
               "Flight-recorder events captured (ring occupancy).")
        w.sample("wasmedge_obs_events_total", None, len(recorder.events))
        w.head("wasmedge_obs_events_dropped_total", "counter",
               "Flight-recorder events dropped by the bounded ring.")
        w.sample("wasmedge_obs_events_dropped_total", None,
                 recorder.dropped)

    if hostcall_stats:
        w.head("wasmedge_hostcall_pipeline_total", "counter",
               "Three-tier hostcall pipeline counters "
               "(batch/engine.py new_hostcall_stats).")
        for key in sorted(hostcall_stats):
            w.sample("wasmedge_hostcall_pipeline_total",
                     {"counter": key}, int(hostcall_stats[key]))

    return w.render()


def export_prometheus(path, recorder=None, stats=None,
                      hostcall_stats=None, failures=None,
                      http_requests=None, analysis_counts=None,
                      gateway_counts=None, shed_counts=None,
                      hv_stats=None, fleet_stats=None,
                      reshard_counts=None,
                      autoscale_actions=None,
                      compile_cache_counts=None,
                      snapshot_counts=None,
                      session_stats=None,
                      integrity_stats=None) -> str:
    """Render and write a metrics snapshot to `path` (or file-like)."""
    text = render_prometheus(recorder=recorder, stats=stats,
                             hostcall_stats=hostcall_stats,
                             failures=failures,
                             http_requests=http_requests,
                             analysis_counts=analysis_counts,
                             gateway_counts=gateway_counts,
                             shed_counts=shed_counts,
                             hv_stats=hv_stats,
                             fleet_stats=fleet_stats,
                             reshard_counts=reshard_counts,
                             autoscale_actions=autoscale_actions,
                             compile_cache_counts=compile_cache_counts,
                             snapshot_counts=snapshot_counts,
                             session_stats=session_stats,
                             integrity_stats=integrity_stats)
    if hasattr(path, "write"):
        path.write(text)
    else:
        from wasmedge_tpu.utils.fsio import atomic_write_bytes

        atomic_write_bytes(path, text.encode())
    return text


def parse_prometheus(text: str) -> dict:
    """Strict-enough parser for the exposition format: returns
    {(name, frozenset(labels.items())): float}.  Used by the test suite
    to prove exports stay machine-readable, and handy for ad-hoc
    assertions on snapshots."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        if "{" in line:
            name, rest = line.split("{", 1)
            labpart, val = rest.rsplit("}", 1)
            labels = {}
            for item in _split_labels(labpart):
                k, v = item.split("=", 1)
                if not (v.startswith('"') and v.endswith('"')):
                    raise ValueError(f"unquoted label value: {line!r}")
                labels[k] = v[1:-1].replace('\\"', '"') \
                    .replace("\\n", "\n").replace("\\\\", "\\")
            out[(name, frozenset(labels.items()))] = float(val)
        else:
            name, val = line.rsplit(None, 1)
            out[(name, frozenset())] = float(val)
    return out


def _split_labels(s: str):
    """Split a label body on commas outside quotes."""
    items, cur, inq = [], "", False
    i = 0
    while i < len(s):
        ch = s[i]
        if ch == '"' and (i == 0 or s[i - 1] != "\\"):
            inq = not inq
        if ch == "," and not inq:
            if cur:
                items.append(cur)
            cur = ""
        else:
            cur += ch
        i += 1
    if cur:
        items.append(cur)
    return items
