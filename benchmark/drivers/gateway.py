"""drivers/gateway.py: the HTTP gateway under load from `loadgen.py`.

The gateway runs in THIS process, on the main thread, through the CLI's own
entry `wasmedge_tpu.cli.gateway_command([...], out=<lines>)`: only the
process that holds the chip can trace it, and nothing of the CLI's path is
copied.  A conductor thread reads the `listening` line, sends the warm-up
request, starts and reaps the load generator (a child that never imports
jax), marks the window, traces a slice in a traced run, and then sends the
process SIGINT, which drains the gateway as `chip_smoke.py` does from
outside.

Configuration keys: guest {builder}, argv (the CLI's options), lanes,
reference.  Traffic keys: see `loadgen.py`, and settle_s (clients run this
long before the window, at least as long as the longest request),
warm_arg, trace_rounds, trace_max_s.

Window accounting: an answer counts if it arrives inside the window,
whenever it was sent; what is outstanding at the window's end is neither
attempted nor failed.  A 429, a 5xx, a 202 from a sync call, a dropped
connection or a wrong value is a failure.
"""

import http.client
import json
import os
import queue
import signal
import statistics
import subprocess
import sys
import tempfile
import threading
import time

import harness

LISTEN_TIMEOUT_S = 900      # the first run of a checkout compiles
POLL_S = 0.1                # how finely the rounds are followed
STALL_S = 30.0              # no round ends for this long: stop waiting for one


class Lines:
    """What `gateway_command` writes, line by line, for another thread."""

    def __init__(self):
        self.q = queue.Queue()
        self._part = ""

    def write(self, text):
        self._part += text
        while "\n" in self._part:
            line, self._part = self._part.split("\n", 1)
            self.q.put(line)

    def flush(self):
        pass

    def next_json(self, key, timeout_s):
        deadline = time.monotonic() + timeout_s
        while True:
            line = self.q.get(timeout=max(deadline - time.monotonic(), 0.01))
            if line is None:
                raise RuntimeError(f"the gateway ended before its {key!r} "
                                   f"line")
            doc = json.loads(line) if line.startswith("{") else {}
            if key in doc:
                return doc


class Client:
    """The conductor's own kept-alive connection to the gateway."""

    def __init__(self, port):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=LISTEN_TIMEOUT_S)

    def call(self, method, path, body=None):
        data = None if body is None else json.dumps(body).encode()
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": "application/json"})
        resp = self.conn.getresponse()
        return resp.status, json.loads(resp.read())

    def status(self):
        code, doc = self.call("GET", "/v1/status")
        if code != 200:
            raise RuntimeError(f"/v1/status answered {code}")
        return doc


class Ticks:
    """Follows `serve.rounds` through /v1/status: answers leave in a burst
    after every round, so the window's borders are put in the middle of a
    round, where no burst is cut, and every window holds whole rounds."""

    def __init__(self, gw):
        self.gw = gw
        self.seen = gw.status()["serve"]["rounds"]
        self.times = []

    def wait(self, give_up):
        """The time at which the next round was seen to end, or None."""
        while time.monotonic() < give_up:
            rounds = self.gw.status()["serve"]["rounds"]
            if rounds > self.seen:
                self.seen = rounds
                self.times.append(time.monotonic())
                return self.times[-1]
            time.sleep(POLL_S)
        return None

    def mid_round(self, not_before):
        """Sleep to the middle of the first round that starts at or after
        `not_before`.  Where no round ends for STALL_S, or none has been
        timed, the border falls where the clock says."""
        tick = 0.0
        while tick is not None and tick < not_before:
            tick = self.wait(max(not_before, time.monotonic()) + STALL_S)
        if tick is not None and len(self.times) >= 2:
            half = (self.times[-1] - self.times[-2]) / 2
            time.sleep(max(tick + half - time.monotonic(), 0))
        return time.monotonic()


def lost(status):
    """Requests the gateway received and cannot account for."""
    g = status["gateway"]
    return g["received"] - g["completed"] - g["failed"] - g["deadline"] \
        - status.get("in_flight", 0) - status.get("queue_depth", 0)


def window_stats(records, t0, t1):
    """The end-to-end numbers over the answers that arrived in [t0, t1]."""
    reqs = [r for r in records if r[0] == "req" and t0 <= r[5] <= t1]
    good = sorted(r[5] - r[4] for r in reqs if r[7])
    out = {"attempted": len(reqs), "failed": len(reqs) - len(good),
           "statuses": {}}
    for r in reqs:
        out["statuses"][str(r[6])] = out["statuses"].get(str(r[6]), 0) + 1
    if len(good) >= 2:
        by_arg = {}
        for r in reqs:
            if r[7]:
                by_arg.setdefault(str(r[2]), []).append(r[5] - r[4])
        out["median_s_by_arg"] = {a: statistics.median(v)
                                  for a, v in sorted(by_arg.items())}
        out.update(served_req_per_s=len(good) / (t1 - t0),
                   served_p50_s=statistics.median(good),
                   served_p95_s=statistics.quantiles(
                       good, n=20, method="inclusive")[18],
                   longest_s=good[-1])
    return out


class Conductor(threading.Thread):
    def __init__(self, run, lines):
        super().__init__(name="bench-conductor", daemon=True)
        self.run_, self.lines = run, lines
        self.error = None
        self.gen = None
        self.up = False         # the gateway is listening

    def run(self):
        try:
            self.conduct()
        except BaseException as e:   # re-raised on the main thread
            self.error = e
        finally:
            if self.gen is not None and self.gen.poll() is None:
                self.gen.kill()
                self.gen.wait()
            if self.up:
                os.kill(os.getpid(), signal.SIGINT)     # drain the gateway

    def conduct(self):
        run = self.run_
        traffic, config = run.traffic, run.config
        ref = run.reference().reference
        hello = self.lines.next_json("listening", LISTEN_TIMEOUT_S)
        self.up = True
        if not run.rehearse and hello["device"]["platform"] != "tpu":
            raise RuntimeError(f"the gateway serves from {hello['device']}")
        if hello["lanes"] != config["lanes"]:
            raise RuntimeError(f"the gateway has {hello['lanes']} lanes")
        port = int(hello["listening"].rsplit(":", 1)[1])
        t_listen = time.monotonic()
        gw = Client(port)

        # the cell's own shapes: one request pays the served step's compile
        warm = traffic["warm_arg"]
        code, doc = gw.call("POST", "/v1/invoke", {
            "module": traffic["module"], "func": traffic["func"],
            "args": [warm], "async": False})
        while code == 202:      # a compile can outlast the sync cap
            time.sleep(0.5)
            code, doc = gw.call("GET", f"/v1/requests/{doc['request_id']}")
            code = 202 if doc.get("status") == "pending" else code
        if code != 200 or doc.get("result") != ref(traffic["func"], [warm]):
            raise RuntimeError(f"warm-up answered {code}: {doc}")
        run.note(listening_to_first_answer_s=time.monotonic() - t_listen,
                 device=hello["device"])

        with tempfile.TemporaryDirectory(prefix="bench-load-") as tmp:
            spec = dict(traffic, expected={
                str(arg): ref(traffic["func"], [arg])[0]
                for arg, _share in traffic["mix"]})
            with open(os.path.join(tmp, "spec.json"), "w") as f:
                json.dump(spec, f)
            out = os.path.join(tmp, "records.jsonl")
            self.gen = subprocess.Popen(
                [sys.executable, os.path.join(harness.HERE, "loadgen.py"),
                 "--port", str(port), "--spec", f.name,
                 "--seed", str(run.seed), "--out", out],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            ramped = json.loads(self.gen.stdout.readline() or "{}")
            if "ramped" not in ramped:
                raise RuntimeError("the load generator never ramped up")
            ticks = Ticks(gw)
            settled = time.monotonic() + traffic["settle_s"]
            while ticks.wait(settled) is not None:
                pass
            ticks.mid_round(settled)
            s0 = gw.status()
            t0 = run.start_window()
            half = (ticks.times[-1] - ticks.times[-2]) / 2 \
                if len(ticks.times) >= 2 else 0.0
            t1 = ticks.mid_round(t0 + run.seconds - half)
            s1 = gw.status()
            if run.trace:
                self.traced_slice(ticks)

            self.gen.stdin.close()      # send nothing new, collect the rest
            summary = json.loads(self.gen.stdout.readline() or "{}")
            if self.gen.wait(timeout=30) != 0 or not summary.get("done"):
                raise RuntimeError("the load generator failed")
            with open(out) as f:
                records = [json.loads(line) for line in f]
        s2 = gw.status()

        stats = window_stats(records, t0, t1)
        # shorter windows from the same run: how the numbers settle
        prefixes = {str(k): {m: v for m, v in window_stats(
            records, t0, t0 + k).items() if m.startswith("served_")}
            for k in range(10, int(t1 - t0), 10)}
        samples = [r for r in records if r[0] == "status" and t0 <= r[1] <= t1]
        probes = [r for r in records if r[0] == "probe" and t0 <= r[2] <= t1]
        compiled = run.compiles_between(t0, t1)
        run.obs["counters"].update(
            window_s=t1 - t0, lanes=config["lanes"], compiles=len(compiled),
            rounds=s1["serve"]["rounds"] - s0["serve"]["rounds"],
            retired=s1["serve"]["retired_instructions"]
            - s0["serve"]["retired_instructions"])
        run.obs["samples"].update(
            in_flight=[r[2]["in_flight"] for r in samples],
            probe_ms=[1000 * (r[2] - r[1]) for r in probes if r[3] == 200])
        # exact once the clients have stopped; under load a request can
        # sit between two counters while /v1/status is read
        self.lost = lost(s2)
        self.failed_by_gateway = s2["gateway"]["failed"] \
            + s2["gateway"]["deadline"]
        run.attempted = stats["attempted"]
        run.failed = stats["failed"]
        run.values.update({k: stats[k] for k in (
            "served_req_per_s", "served_p50_s", "served_p95_s")
            if k in stats})
        in_flight = run.obs["samples"]["in_flight"] or [0]
        run.note(window=stats, prefixes=prefixes,
                 in_flight_min_max=[min(in_flight), max(in_flight)],
                 p95_samples_beyond=int(0.05 * (stats["attempted"]
                                                - stats["failed"])),
                 loadgen=summary, in_flight_at_start=s0.get("in_flight"),
                 in_flight_at_end=s1.get("in_flight"),
                 queue_depth_at_end=s1.get("queue_depth"),
                 counters=run.obs["counters"], compiled_in_window=compiled,
                 unaccounted_at_start_end_after=[lost(s0), lost(s1),
                                                 self.lost])

    def traced_slice(self, ticks):
        """About `trace_rounds` serving rounds under the profiler, from the
        end of one round to the end of another; the window's counters are
        taken before it, so the profiler's own cost is in none of them."""
        run = self.run_
        give_up = time.monotonic() + run.traffic["trace_max_s"]
        with run.traced_slice():
            first = ticks.wait(give_up)
            seen = 0
            while first is not None and seen < run.traffic["trace_rounds"] \
                    and ticks.wait(give_up) is not None:
                seen += 1
        run.obs["counters"]["trace_rounds"] = seen


def run(run):
    import wasmedge_tpu.models as models
    from wasmedge_tpu.cli import gateway_command

    run.device()
    # a parent that ignores SIGINT (a shell's background job) hands that
    # on, and Python then installs no handler: the drain would never come
    signal.signal(signal.SIGINT, signal.default_int_handler)
    lines = Lines()
    conductor = Conductor(run, lines)
    with tempfile.TemporaryDirectory(prefix="bench-gw-") as tmp:
        wasm = os.path.join(tmp, "guest.wasm")
        with open(wasm, "wb") as f:
            f.write(getattr(models, run.config["guest"]["builder"])())
        conductor.start()
        rc = gateway_command([wasm] + run.config["argv"], out=lines)
        lines.q.put(None)
        conductor.join(timeout=120)
    if conductor.error is not None:
        raise conductor.error
    if rc != 0 or conductor.is_alive():
        raise RuntimeError(f"the gateway exited with code {rc}")
    bye = lines.next_json("metric", 1)
    drained = bye["received"] - bye["completed"]
    run.note(gateway_exit=bye)
    run.correct = (run.failed == 0 and run.attempted > 0
                   and conductor.lost == 0 and drained == 0
                   and conductor.failed_by_gateway == 0)
