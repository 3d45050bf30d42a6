"""Guest suspend/resume via effect handlers (wasmedge_tpu/effects/,
marker `effects`).

Pins the r23 acceptance contract:
  - `wasmedge.await_event` with no pending payload PARKS the lane at
    the next launch boundary (serialized through the SwapStore, zero
    resident lanes) and an external wake re-enters it bit-identically
    to never having parked (results AND streamed stdout)
  - a pure-clock `poll_oneoff` parks with a deterministic timer and
    the timer wake delivers exactly the host-path event tail
  - the deadline clock PAUSES while a session waits on an explicit
    wake; timer sleeps keep their absolute deadline
  - fault seams: a faulted `session_park` leaves the lane resident and
    retries; a faulted `session_wake` re-queues the wake, never loses it
  - parked sessions survive a cross-process checkpoint/resume and wake
    exactly-once under their original ids
  - the effects-off configuration is inert: no `_effects` attribute,
    the `wasmedge` import falls back to Errno.AGAIN, wake() refuses

Speed discipline: tier-1 fast — tiny guest modules, lanes=2, chunk
128, and a module-scoped JAX compilation cache.
"""

import struct
import tempfile
import time

import pytest

from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import WasmError
from wasmedge_tpu.effects import StreamBuf, effects_import_object
from wasmedge_tpu.executor import Executor
from wasmedge_tpu.host.wasi import WasiModule
from wasmedge_tpu.loader import Loader
from wasmedge_tpu.runtime.store import StoreManager
from wasmedge_tpu.serve import BatchServer, DeadlineExceeded
from wasmedge_tpu.testing.faults import Fault, FaultInjector
from wasmedge_tpu.utils.builder import ModuleBuilder
from wasmedge_tpu.validator import Validator

pytestmark = pytest.mark.effects


@pytest.fixture(scope="module", autouse=True)
def _compile_cache():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    d = tempfile.mkdtemp(prefix="effects-jit-cache-")
    jax.config.update("jax_compilation_cache_dir", d)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _conf(effects=True, obs=False):
    conf = Configure()
    conf.batch.steps_per_launch = 128
    conf.batch.value_stack_depth = 64
    conf.batch.call_stack_depth = 16
    conf.obs.enabled = obs
    conf.effects.suspend = effects
    return conf


def _await_mod():
    """wait(n) -> await_event(buf=64, len=8, nwritten=32); returns
    first-payload-word + n (proves both delivery and that the guest's
    own state survived the park)."""
    b = ModuleBuilder()
    b.import_func("wasmedge", "await_event",
                  ["i32", "i32", "i32"], ["i32"])
    b.add_memory(1, 1)
    b.add_function(["i64"], ["i64"], [], [
        ("i32.const", 64), ("i32.const", 8), ("i32.const", 32),
        ("call", 0), "drop",
        ("i32.const", 64), ("i32.load", 2, 0), "i64.extend_i32_u",
        ("local.get", 0), "i64.add",
    ], export="wait")
    return b.build()


def _sleep_mod(ns):
    """nap(n) -> poll_oneoff over ONE monotonic-clock subscription of
    `ns` nanoseconds; returns n + nevents (= n + 1)."""
    sub = bytearray(48)
    sub[0:8] = (0xAB).to_bytes(8, "little")       # userdata
    sub[8] = 0                                    # tag CLOCK
    sub[16:20] = (1).to_bytes(4, "little")        # clockid MONOTONIC
    sub[24:32] = int(ns).to_bytes(8, "little")    # timeout
    b = ModuleBuilder()
    b.import_func("wasi_snapshot_preview1", "poll_oneoff",
                  ["i32", "i32", "i32", "i32"], ["i32"])
    b.add_memory(1, 1)
    b.add_active_data(0, [("i32.const", 64)], bytes(sub))
    b.add_function(["i64"], ["i64"], [], [
        ("i32.const", 64), ("i32.const", 128), ("i32.const", 1),
        ("i32.const", 192), ("call", 0), "drop",
        ("i32.const", 192), ("i32.load", 2, 0), "i64.extend_i32_u",
        ("local.get", 0), "i64.add",
    ], export="nap")
    return b.build()


def _echo_await_mod():
    """go(n): write "pre|", await_event, write the payload then "post";
    returns payload-length + n.  The stdout stream across a park must
    be byte-identical to a never-parked run."""
    b = ModuleBuilder()
    b.import_func("wasi_snapshot_preview1", "fd_write",
                  ["i32", "i32", "i32", "i32"], ["i32"])
    b.import_func("wasmedge", "await_event",
                  ["i32", "i32", "i32"], ["i32"])
    b.add_memory(1, 1)
    b.add_active_data(0, [("i32.const", 256)], b"pre|")
    b.add_active_data(0, [("i32.const", 264)], b"post")

    def write(buf_instrs, len_instrs):
        return [
            ("i32.const", 0), *buf_instrs, ("i32.store", 2, 0),
            ("i32.const", 4), *len_instrs, ("i32.store", 2, 0),
            ("i32.const", 1), ("i32.const", 0), ("i32.const", 1),
            ("i32.const", 32), ("call", 0), "drop",
        ]

    b.add_function(["i64"], ["i64"], [], [
        *write([("i32.const", 256)], [("i32.const", 4)]),
        ("i32.const", 64), ("i32.const", 16), ("i32.const", 40),
        ("call", 1), "drop",
        *write([("i32.const", 64)],
               [("i32.const", 40), ("i32.load", 2, 0)]),
        *write([("i32.const", 264)], [("i32.const", 4)]),
        ("i32.const", 40), ("i32.load", 2, 0), "i64.extend_i32_u",
        ("local.get", 0), "i64.add",
    ], export="go")
    return b.build()


def _server(wasm, conf=None, lanes=2, wasi=False, sink=None, **kw):
    conf = conf or _conf()
    mod = Validator(conf).validate(Loader(conf).parse_module(wasm))
    store = StoreManager()
    ex = Executor(conf)
    if wasi:
        w = WasiModule()
        w.init_wasi(dirs=[], prog_name="effects-test")
        if sink is not None:
            w.env.fds[1].os_fd = sink
        ex.register_import_object(store, w)
    ex.register_import_object(store, effects_import_object())
    inst = ex.instantiate(store, mod)
    return BatchServer(inst, store=store, conf=conf, lanes=lanes, **kw)


# ---------------------------------------------------------------------------
# StreamBuf unit semantics
# ---------------------------------------------------------------------------
def test_streambuf_dedupe_window_and_close():
    buf = StreamBuf(cap=8)
    buf.append(0, b"abcd")
    buf.append(2, b"cdef")        # crash-replay overlap: deduped
    chunk, off, closed = buf.read(0, timeout=0)
    assert (chunk, off, closed) == (b"abcdef", 6, False)
    assert buf.read(6, timeout=0) == (None, 6, False)   # bare timeout
    buf.append(6, b"ghijkl")      # 12 logical bytes > cap 8: window
    chunk, off, closed = buf.read(0, timeout=0)
    assert chunk == b"efghijkl" and off == 12           # snapped forward
    buf.close(error=None)
    assert buf.read(12, timeout=0) == (b"", 12, True)
    assert buf.read(3, timeout=0)[0] == b"efghijkl"     # late replay


# ---------------------------------------------------------------------------
# park -> external wake -> resolve
# ---------------------------------------------------------------------------
def test_await_event_parks_and_http_wake_resolves():
    srv = _server(_await_mod(), lanes=2)
    fut = srv.submit("wait", [5])
    srv.run_until_idle()
    # parked: zero resident lanes, the session holds no device capacity
    assert not fut.done
    assert srv.effects.in_flight() == 1
    assert not srv._bindings and len(srv._free) == 2
    st = srv.session_stats()
    assert st["parked"] == 1 and st["parks"] == 1
    rid = fut.request_id
    assert srv.wake(rid, struct.pack("<I", 41)) == "parked"
    srv.run_until_idle()
    assert fut.result(0)[0] == 41 + 5
    st = srv.session_stats()
    assert st["parked"] == 0 and st["resumes"] == 1
    assert st["wakes_http"] == 1 and st["delivered"] == 1
    # the server remains a normal server: a second request round-trips
    f2 = srv.submit("wait", [7])
    srv.run_until_idle()
    assert srv.wake(f2.request_id, struct.pack("<I", 1)) == "parked"
    srv.run_until_idle()
    assert f2.result(0)[0] == 8


def test_wake_before_park_delivers_without_parking():
    srv = _server(_await_mod(), lanes=2)
    fut = srv.submit("wait", [9])
    # the wake lands before the request ever reaches await_event: the
    # payload pre-delivers at the call and the session never parks
    assert srv.wake(fut.request_id, struct.pack("<I", 100)) \
        in ("pending", "unknown")
    srv.run_until_idle()
    assert fut.result(0)[0] == 109
    st = srv.session_stats()
    assert st["parks"] == 0 and st["delivered"] == 1


def test_timer_park_and_timer_wake():
    srv = _server(_sleep_mod(400_000_000), wasi=True, lanes=2)  # 0.4 s
    fut = srv.submit("nap", [10])
    srv.run_until_idle()
    assert srv.effects.in_flight() == 1 and not srv._bindings
    time.sleep(0.45)
    srv.run_until_idle()
    assert fut.result(0)[0] == 11    # n + the single clock event
    st = srv.session_stats()
    assert st["wakes_timer"] == 1 and st["parks"] == 1
    assert st["park_seconds"]["count"] == 1
    assert st["park_seconds"]["sum"] >= 0.05


# ---------------------------------------------------------------------------
# deadline semantics while parked
# ---------------------------------------------------------------------------
def test_timer_park_still_honors_deadline():
    srv = _server(_sleep_mod(10_000_000_000), wasi=True, lanes=2)
    fut = srv.submit("nap", [1], deadline_s=0.05)   # sleep 10s >> 50ms
    srv.run_until_idle()
    assert srv.effects.in_flight() == 1
    time.sleep(0.1)
    srv.run_until_idle()
    with pytest.raises(DeadlineExceeded):
        fut.result(0)
    assert srv.effects.in_flight() == 0
    assert srv.counters["killed"] >= 1


# ---------------------------------------------------------------------------
# streamed stdout: parked run byte-identical to never-parked run
# ---------------------------------------------------------------------------
def _echo_await_run(payload, park):
    import os

    sink = os.open(os.devnull, os.O_WRONLY)
    try:
        srv = _server(_echo_await_mod(), wasi=True, sink=sink, lanes=2)
        fut = srv.submit("go", [3])
        if park:
            srv.run_until_idle()
            assert srv.effects.in_flight() == 1
            # the pre-park output is already streaming
            chunk, _, closed = srv.stream_of(fut.request_id).read(
                0, timeout=0)
            assert chunk == b"pre|" and not closed
            srv.wake(fut.request_id, payload)
        else:
            srv.wake(fut.request_id, payload)   # pre-delivered
        srv.run_until_idle()
        assert fut.result(0)[0] == len(payload) + 3
        buf = srv.stream_of(fut.request_id)
        chunk, off, closed = buf.read(0, timeout=1.0)
        assert closed and buf.error is None
        return chunk
    finally:
        os.close(sink)


def test_stream_bytes_identical_across_park():
    payload = b"DATA1234"
    parked = _echo_await_run(payload, park=True)
    direct = _echo_await_run(payload, park=False)
    assert parked == b"pre|" + payload + b"post"
    assert parked == direct


# ---------------------------------------------------------------------------
# fault seams (testing/faults.py)
# ---------------------------------------------------------------------------
def test_faulted_park_leaves_lane_resident_and_retries():
    inj = FaultInjector([Fault(point="session_park", at=0)])
    srv = _server(_await_mod(), lanes=2, faults=inj)
    fut = srv.submit("wait", [4])
    srv.step()
    # first boundary: the park faulted -> the lane stays RESIDENT
    assert inj.fired == 1
    assert srv.effects.in_flight() == 0 and len(srv._bindings) == 1
    assert srv.session_stats()["park_faults"] == 1
    srv.run_until_idle()
    # retried at the next boundary: parked for real now
    assert srv.effects.in_flight() == 1 and not srv._bindings
    assert srv.session_stats()["parks"] == 1
    srv.wake(fut.request_id, struct.pack("<I", 2))
    srv.run_until_idle()
    assert fut.result(0)[0] == 6


def test_faulted_wake_requeues_not_lost():
    inj = FaultInjector([Fault(point="session_wake", at=0)])
    srv = _server(_await_mod(), lanes=2, faults=inj)
    fut = srv.submit("wait", [8])
    srv.run_until_idle()
    assert srv.effects.in_flight() == 1
    srv.wake(fut.request_id, struct.pack("<I", 30))
    srv.run_until_idle()
    # the faulted wake was re-queued and retried, never dropped
    assert inj.fired == 1
    assert fut.result(0)[0] == 38
    st = srv.session_stats()
    assert st["wake_faults"] == 1 and st["wakes_http"] == 1


# ---------------------------------------------------------------------------
# durability: parked sessions survive a cross-process resume
# ---------------------------------------------------------------------------
def test_parked_session_survives_cross_process_resume():
    with tempfile.TemporaryDirectory(prefix="effects-resume-") as d:
        srv = _server(_await_mod(), lanes=2, checkpoint_dir=d)
        fut = srv.submit("wait", [7])
        srv.run_until_idle()
        assert srv.effects.in_flight() == 1
        srv.checkpoint()
        rid = fut.request_id
        del srv, fut   # "process" dies with the session parked

        srv2 = _server(_await_mod(), lanes=2, checkpoint_dir=d,
                       resume=True)
        # adopted as a PARKED session (not requeued from scratch)
        assert list(srv2.adopted) == [rid]
        assert rid in srv2.effects.parked_ids()
        assert srv2.wake(rid, struct.pack("<I", 41)) == "parked"
        srv2.run_until_idle()
        assert srv2.adopted[rid].result(0)[0] == 41 + 7
        # exactly-once: fresh ids order after the adopted one
        f2 = srv2.submit("wait", [1])
        assert f2.request_id > rid


def test_wake_delivered_then_crash_is_not_lost():
    # a payload delivered to a PARKED session just before the crash
    # rides the journal (hex payloads) and still wakes the resume
    with tempfile.TemporaryDirectory(prefix="effects-resume2-") as d:
        srv = _server(_await_mod(), lanes=2, checkpoint_dir=d)
        fut = srv.submit("wait", [2])
        srv.run_until_idle()
        srv.wake(fut.request_id, struct.pack("<I", 9))
        srv.checkpoint()   # wake queued/journaled, not yet installed
        rid = fut.request_id
        del srv, fut

        srv2 = _server(_await_mod(), lanes=2, checkpoint_dir=d,
                       resume=True)
        srv2.run_until_idle()
        assert srv2.adopted[rid].result(0)[0] == 11


# ---------------------------------------------------------------------------
# effects off: bit-identical inert configuration
# ---------------------------------------------------------------------------
def test_effects_off_is_inert():
    srv = _server(_await_mod(), conf=_conf(effects=False), lanes=2)
    assert srv.effects is None
    assert not hasattr(srv.engine, "_effects")
    fut = srv.submit("wait", [9])
    srv.run_until_idle()
    # the fallback host body returns Errno.AGAIN with zero bytes: the
    # guest completes immediately with the untouched buffer (= 0 + n)
    assert fut.result(0)[0] == 9
    assert srv.session_stats() is None
    assert srv.stream_of(fut.request_id) is None
    with pytest.raises(WasmError):
        srv.wake(fut.request_id)


def test_effects_metrics_render_and_status_block():
    from wasmedge_tpu.obs.metrics import (
        parse_prometheus,
        render_prometheus,
    )

    srv = _server(_await_mod(), lanes=2)
    fut = srv.submit("wait", [1])
    srv.run_until_idle()
    m = parse_prometheus(render_prometheus(
        session_stats=srv.session_stats()))
    assert m[("wasmedge_sessions_parked", frozenset())] == 1
    assert m[("wasmedge_session_parks_total", frozenset())] == 1
    srv.wake(fut.request_id, b"\x01\x00\x00\x00")
    srv.run_until_idle()
    m = parse_prometheus(render_prometheus(
        session_stats=srv.session_stats()))
    assert m[("wasmedge_sessions_parked", frozenset())] == 0
    assert m[("wasmedge_session_wakes_total",
              frozenset({("source", "http")}))] == 1
    assert m[("wasmedge_session_park_seconds_count", frozenset())] == 1
    # obs-off/effects-off renders bit-identically to no kwarg at all
    assert render_prometheus(session_stats=None) == render_prometheus()
