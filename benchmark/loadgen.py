#!/usr/bin/env python3
"""loadgen.py: the general traffic generator of the serving cells, run as a
child process.  Standard library only: it imports neither jax nor the
program, so it can never hold a chip.

    python3 loadgen.py --port <p> --spec <traffic.json> --seed <n> --out <file>

One thread, one selector: every client is a kept-alive connection with a
timer.  A closed-loop client draws a think time, sleeps, sends one
synchronous `POST /v1/invoke`, waits for the answer, and starts over.  Two
more connections probe `GET /healthz` and sample `GET /v1/status` on a
fixed period.  The connections are opened over `ramp_s` seconds, a few at a
time (the gateway's listen backlog is 5).

Every seed gets the same set of sizes and think times in another order:
arguments are dealt from a deck that holds each `mix` entry in its exact
proportion, think times from a deck of `think_strata` evenly spaced
quantiles of the exponential; a deck is reshuffled from the seed when it
runs out.

It prints `{"ramped": ...}` once every client is connected, runs until its
stdin closes, then sends nothing new, waits up to `drain_s` for the answers
still outstanding, writes one JSON record per line to --out and prints a
summary line:

    ["req", client, arg, t_due, t_sent, t_answered, status, ok]
    ["probe", t_sent, t_answered, status]
    ["status", t_answered, {...the /v1/status body, cut to what is read...}]

Times are `time.monotonic()`, which one machine's processes share.
"""

import argparse
import heapq
import json
import math
import random
import selectors
import socket
import sys
import time

STATUS_KEYS = ("in_flight", "queue_depth", "serve", "gateway")


class Deck:
    """Deals `cards` in an order shuffled from `rng`, again and again."""

    def __init__(self, cards, rng):
        self.cards, self.rng, self.left = list(cards), rng, []

    def draw(self):
        if not self.left:
            self.left = list(self.cards)
            self.rng.shuffle(self.left)
        return self.left.pop()


class Conn:
    """One kept-alive HTTP/1.1 connection with at most one request out.
    It connects without blocking the loop: while the gateway is slow to
    accept (its listen backlog is 5), only this client waits."""

    def __init__(self, gen, kind, ident=None):
        self.gen, self.kind, self.ident = gen, kind, ident
        self.sock = None
        self.connected = False
        self.started = False    # a client's first connection has stood
        self.pending = b""      # a request waiting for the connection
        self.buf = b""
        self.sent_at = None     # set while a request is outstanding
        self.due_at = None
        self.arg = None

    def connect(self):
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setblocking(False)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.connect_ex(("127.0.0.1", self.gen.port))
        self.gen.sel.register(self.sock, selectors.EVENT_WRITE, self)

    def writable(self):
        """The connect has ended: True once the connection stands."""
        err = self.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
        if err:
            self.close()
            self.connect()      # the kernel has waited already: try again
            return False
        self.connected = True
        self.gen.sel.modify(self.sock, selectors.EVENT_READ, self)
        if self.pending:
            self.sock.sendall(self.pending)
            self.pending = b""
        return True

    def close(self):
        if self.sock is not None:
            self.gen.sel.unregister(self.sock)
            self.sock.close()
            self.sock = None
        self.connected = False
        self.buf = b""

    def send(self, method, path, body=None):
        data = b"" if body is None else json.dumps(body).encode()
        head = (f"{method} {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(data)}\r\n\r\n").encode()
        self.sent_at = time.monotonic()
        if self.connected:
            self.sock.sendall(head + data)
        else:                   # the time to connect is the client's too
            self.pending = head + data
            if self.sock is None:
                self.connect()

    def readable(self):
        """Bytes arrived: returns (status, body) once an answer is whole,
        (0, b"") if the server closed the connection, else None."""
        try:
            chunk = self.sock.recv(65536)
        except BlockingIOError:
            return None
        except OSError:
            chunk = b""
        if not chunk:
            self.close()
            return (0, b"") if self.sent_at is not None else None
        self.buf += chunk
        end = self.buf.find(b"\r\n\r\n")
        if end < 0:
            return None
        head = self.buf[:end].decode("latin-1")
        length = 0
        for line in head.split("\r\n")[1:]:
            key, _, value = line.partition(":")
            if key.strip().lower() == "content-length":
                length = int(value)
        if len(self.buf) < end + 4 + length:
            return None
        body = self.buf[end + 4:end + 4 + length]
        self.buf = self.buf[end + 4 + length:]
        return int(head.split(" ", 2)[1]), body


class LoadGen:
    def __init__(self, port, spec, seed):
        self.port, self.spec = port, spec
        self.sel = selectors.DefaultSelector()
        self.timers = []        # (due, sequence number, function, argument)
        self.seq = 0
        self.records = []
        self.late = []          # how late each think-time sleep woke, s
        self.stopping = False
        self.outstanding = 0
        rng = random.Random(seed)
        self.args = Deck([arg for arg, share in spec["mix"]
                          for _ in range(int(share))], rng)
        strata = int(spec["think_strata"])
        self.thinks = Deck(
            [-spec["think_mean_s"] * math.log(1 - (i + 0.5) / strata)
             for i in range(strata)], rng)
        self.expected = {int(k): v for k, v in spec["expected"].items()}

    def at(self, due, fn, arg=None):
        self.seq += 1
        heapq.heappush(self.timers, (due, self.seq, fn, arg))

    # -- closed-loop clients -------------------------------------------------
    def client_start(self, conn):
        conn.connect()

    def client_connected(self, conn):
        conn.started = True
        self.ramped += 1
        if self.ramped == self.spec["clients"]:
            print(json.dumps({"ramped": time.monotonic()}), flush=True)
        self.client_think(conn)

    def client_think(self, conn):
        if self.stopping:
            return
        conn.due_at = time.monotonic() + self.thinks.draw()
        self.at(conn.due_at, self.client_send, conn)

    def client_send(self, conn):
        if self.stopping:
            return
        conn.arg = self.args.draw()
        conn.send("POST", "/v1/invoke",
                  {"module": self.spec["module"], "func": self.spec["func"],
                   "args": [conn.arg], "async": False})
        self.late.append(conn.sent_at - conn.due_at)
        self.outstanding += 1

    def client_answer(self, conn, status, body):
        now = time.monotonic()
        ok = False
        if status == 200:
            try:
                doc = json.loads(body)
                ok = doc.get("ok") is True and \
                    doc.get("result") == [self.expected[conn.arg]]
            except ValueError:
                ok = False
        self.records.append(["req", conn.ident, conn.arg, conn.due_at,
                             conn.sent_at, now, status, ok])
        conn.sent_at = None
        self.outstanding -= 1
        self.client_think(conn)

    # -- the probe and the status sampler -----------------------------------
    def tick(self, conn):
        if self.stopping:
            return
        period = self.spec[conn.kind + "_every_s"]
        self.at(time.monotonic() + period, self.tick, conn)
        if conn.sent_at is None:    # never two out on one connection
            conn.send("GET", "/healthz" if conn.kind == "probe"
                      else "/v1/status")

    def tick_answer(self, conn, status, body):
        now = time.monotonic()
        if conn.kind == "probe":
            self.records.append(["probe", conn.sent_at, now, status])
        elif status == 200:
            doc = json.loads(body)
            self.records.append(["status", now,
                                 {k: doc.get(k) for k in STATUS_KEYS}])
        conn.sent_at = None

    # -- the loop -------------------------------------------------------------
    def sweep(self, _arg):
        """A request that outlasts `request_timeout_s` is a failure."""
        now = time.monotonic()
        self.at(now + 1.0, self.sweep)
        limit = self.spec["request_timeout_s"]
        for key in list(self.sel.get_map().values()):
            conn = key.data
            if conn is not None and conn.kind == "client" \
                    and conn.sent_at is not None \
                    and now - conn.sent_at > limit:
                conn.close()
                self.client_answer(conn, -1, b"")

    def run(self, stdin):
        spec = self.spec
        self.ramped = 0
        start = time.monotonic()
        for i in range(spec["clients"]):
            self.at(start + spec["ramp_s"] * i / spec["clients"],
                    self.client_start, Conn(self, "client", i))
        for kind in ("probe", "status"):
            self.at(start, self.tick, Conn(self, kind))
        self.at(start + 1.0, self.sweep)
        self.sel.register(stdin, selectors.EVENT_READ, None)
        deadline = None
        while True:
            now = time.monotonic()
            while self.timers and self.timers[0][0] <= now:
                _due, _seq, fn, arg = heapq.heappop(self.timers)
                fn(arg)
            if self.stopping and (self.outstanding == 0 or now > deadline):
                break
            wait = 0.5
            if self.timers:
                wait = min(wait, max(self.timers[0][0] - time.monotonic(), 0))
            for key, events in self.sel.select(wait):
                conn = key.data
                if conn is not None and events & selectors.EVENT_WRITE:
                    if conn.writable() and conn.kind == "client" \
                            and not conn.started:
                        self.client_connected(conn)
                    continue
                if conn is None:        # stdin closed: stop sending
                    if not stdin.buffer.read1(4096) and not self.stopping:
                        self.stopping = True
                        deadline = time.monotonic() + spec["drain_s"]
                        self.sel.unregister(stdin)
                    continue
                answer = conn.readable()
                if answer is None:
                    continue
                if conn.kind == "client":
                    self.client_answer(conn, *answer)
                else:
                    self.tick_answer(conn, *answer)
        return self.outstanding


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--spec", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    opts = ap.parse_args(argv)
    with open(opts.spec) as f:
        spec = json.load(f)
    gen = LoadGen(opts.port, spec, opts.seed)
    abandoned = gen.run(sys.stdin)
    with open(opts.out, "w") as f:
        for rec in gen.records:
            f.write(json.dumps(rec) + "\n")
    late = sorted(gen.late) or [0.0]
    print(json.dumps({"done": True, "records": len(gen.records),
                      "abandoned": abandoned,
                      "late_ms_median": 1000 * late[len(late) // 2],
                      "late_ms_max": 1000 * late[-1]}), flush=True)


if __name__ == "__main__":
    main()
