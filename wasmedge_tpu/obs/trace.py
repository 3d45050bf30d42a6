"""Chrome trace_event JSON export for the flight recorder.

Emits the "JSON Object Format" of the trace_event spec (the format
Perfetto and chrome://tracing open directly): a `traceEvents` array of
phase records plus a `metadata` object.  Recorder tracks become trace
threads of one process — one per block/tier/component — with
thread_name metadata events so the UI labels them; counters ("C"
events: live-lane occupancy, hostcall queue depth) render as counter
tracks above the span rows.

`validate_chrome_trace` is the schema check the obs test suite
(tests/test_obs.py) runs against every emitted artifact: it proves the
required keys and types per phase, not merely that json.loads
succeeds.
"""

from __future__ import annotations

import json
from typing import List, Optional

_US = 1e6  # trace_event timestamps/durations are microseconds


def chrome_trace(recorder, metadata: Optional[dict] = None) -> dict:
    """Build the trace_event JSON object from a FlightRecorder."""
    tids = {}
    events = []

    def tid_of(track: str) -> int:
        t = tids.get(track)
        if t is None:
            t = tids[track] = len(tids) + 1
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": t, "args": {"name": track}})
        return t

    events.append({"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
                   "args": {"name": "wasmedge-tpu batch"}})
    for ev in recorder.events:
        rec = {
            "name": ev["name"],
            "cat": ev["cat"] or "batch",
            "ph": ev["ph"],
            "ts": ev["ts"] * _US,
            "pid": 1,
            "tid": tid_of(ev["track"]),
            "args": ev["args"],
        }
        if ev["ph"] == "X":
            rec["dur"] = ev["dur"] * _US
        elif ev["ph"] == "i":
            rec["s"] = "t"  # instant scope: thread
        events.append(rec)
    meta = {"recorder_capacity": recorder.capacity,
            "events_dropped": recorder.dropped}
    if recorder.tier_seconds:
        meta["tier_seconds"] = dict(recorder.tier_seconds)
    if recorder.failure_counts:
        meta["failure_counts"] = dict(recorder.failure_counts)
    if metadata:
        meta.update(metadata)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": meta}


def export_chrome_trace(recorder, path, metadata: Optional[dict] = None):
    """Write the trace object to `path` (or a file-like object)."""
    obj = chrome_trace(recorder, metadata)
    if hasattr(path, "write"):
        json.dump(obj, path)
    else:
        from wasmedge_tpu.utils.fsio import atomic_write_bytes

        atomic_write_bytes(path, json.dumps(obj).encode())
    return obj


_REQUIRED = {"name", "ph", "pid", "tid"}
_KNOWN_PH = {"X", "B", "E", "i", "I", "C", "M", "b", "e", "n", "s", "t",
             "f"}


def validate_chrome_trace(obj) -> List[str]:
    """Schema problems of a trace_event JSON object ([] = valid)."""
    probs = []
    if not isinstance(obj, dict):
        return ["top level is not an object"]
    evs = obj.get("traceEvents")
    if not isinstance(evs, list):
        return ["missing traceEvents array"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            probs.append(f"event {i}: not an object")
            continue
        missing = _REQUIRED - set(ev)
        if missing:
            probs.append(f"event {i} ({ev.get('name')!r}): missing "
                         f"{sorted(missing)}")
            continue
        ph = ev["ph"]
        if ph not in _KNOWN_PH:
            probs.append(f"event {i}: unknown phase {ph!r}")
            continue
        if ph != "M" and not isinstance(ev.get("ts"), (int, float)):
            probs.append(f"event {i} ({ev['name']!r}): non-numeric ts")
        if ph == "X" and not isinstance(ev.get("dur"), (int, float)):
            probs.append(f"event {i} ({ev['name']!r}): X without dur")
        if ph == "C" and not isinstance(ev.get("args"), dict):
            probs.append(f"event {i} ({ev['name']!r}): C without args")
    return probs
