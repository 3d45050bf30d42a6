"""Network-facing serving gateway (wasmedge_tpu/gateway/, marker `serve`).

Pins the r11 acceptance contract over REAL sockets (every HTTP
assertion here goes through a bound ephemeral port, never an
in-process shortcut):

  - runtime module registration: POST /v1/modules validates/compiles
    through the standard pipeline; register-then-invoke results are
    bit-identical to a solo execute_batch run of the same module on a
    cold-start multi-module image, while in-flight requests from the
    PREVIOUS generation finish on the old image, unperturbed
  - rejection taxonomy on the wire: unknown module/func -> 404, bad or
    unbatchable wasm -> 400, duplicate name -> 409, backpressure ->
    429 + Retry-After, deadline -> 504, auth -> 401/403
  - the machine-readable rejection contract (ErrCode + retryable flag,
    common/errors.rejection_info) both in-process and in HTTP bodies
  - per-tenant policy: API-key auth stub, token-bucket rate limiting,
    quota/weight wired into the FairQueue

Speed discipline: tier-1 fast.  Engine compiles dominate gateway
tests, so the suite shares ONE long-lived gateway (module fixture) for
everything that doesn't need special knobs, keeps every pool at the
same tiny geometry (so the module-scoped JAX persistent cache turns
repeat builds into deserializations), and registers exactly one module
at runtime across the whole file (each registration compiles a fresh
concatenated image — that is the feature, pay for it once).  Tests
against the shared gateway are order-independent: they read
generation/module state instead of assuming it.
"""

import base64
import json
import tempfile
import time
from http.client import HTTPConnection

import pytest

from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import ErrCode, WasmError, rejection_info
from wasmedge_tpu.gateway import Gateway, GatewayService, GatewayTenants
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.utils.builder import ModuleBuilder

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module", autouse=True)
def _compile_cache():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    d = tempfile.mkdtemp(prefix="gateway-jit-cache-")
    jax.config.update("jax_compilation_cache_dir", d)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _fib(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def build_dbl() -> bytes:
    """A second guest for runtime registration: dbl(n) = 2n + 7."""
    b = ModuleBuilder()
    b.add_function(["i64"], ["i64"], [],
                   [("local.get", 0), ("i64.const", 2), "i64.mul",
                    ("i64.const", 7), "i64.add"],
                   export="dbl")
    return b.build()


def build_unlinkable() -> bytes:
    """Imports a host function nothing provides: instantiation fails."""
    b = ModuleBuilder()
    b.import_func("env", "mystery", ["i32"], ["i32"])
    b.add_function(["i32"], ["i32"], [],
                   [("local.get", 0), ("call", 0)], export="f")
    return b.build()


def _conf(obs=False):
    conf = Configure()
    conf.batch.steps_per_launch = 256
    conf.batch.value_stack_depth = 128
    conf.batch.call_stack_depth = 64
    conf.obs.enabled = obs
    return conf


def _gateway(conf=None, lanes=2, tenants=None, fib=True):
    svc = GatewayService(conf=conf or _conf(), lanes=lanes,
                         tenants=tenants)
    if fib:
        svc.register_module("fib", wasm_bytes=build_fib(), source="boot")
    return Gateway(svc, port=0).start()


@pytest.fixture(scope="module")
def gw_main(_compile_cache):
    """The shared gateway: obs on, 2 lanes, fib preloaded.  Tests must
    stay order-independent against it (read state, don't assume it)."""
    gw = _gateway(conf=_conf(obs=True), lanes=2)
    yield gw
    gw.shutdown()


def rpc(gw, method, path, body=None, headers=None, timeout=120.0):
    c = HTTPConnection(gw.host, gw.port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if isinstance(body, dict) \
            else body
        c.request(method, path, body=data, headers=headers or {})
        r = c.getresponse()
        raw = r.read()
        hdrs = dict(r.getheaders())
    finally:
        c.close()
    try:
        doc = json.loads(raw)
    except (ValueError, UnicodeDecodeError):
        doc = raw.decode(errors="replace")
    return r.status, doc, hdrs


def _poll(gw, rid, timeout_s=120.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st, doc, _ = rpc(gw, "GET", f"/v1/requests/{rid}")
        if not (isinstance(doc, dict) and doc.get("status") == "pending"):
            return st, doc
        time.sleep(0.02)
    raise TimeoutError(f"request {rid} still pending")


# ---------------------------------------------------------------------------
# runtime registration: cold-start image parity + in-flight swap
# ---------------------------------------------------------------------------
def test_register_then_invoke_parity_and_generation_swap(gw_main):
    """The acceptance sentence in one flow: long requests go in flight
    on generation N, a module registers over HTTP (generation N+1,
    cold-start concatenated image), the NEW module serves bit-identical
    to a solo execute_batch run, the OLD generation's in-flight
    requests complete unperturbed on the old image, and the drained
    generation is reaped."""
    gw = gw_main
    st, doc, _ = rpc(gw, "GET", "/v1/status")
    gen0 = doc["generation"]

    # occupy generation N's two lanes with long requests (async so the
    # handler threads don't serialize them) + one queued behind
    ids = []
    for n in (17, 16, 15):
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"func": "fib", "args": [n], "async": True})
        assert st == 202, doc
        ids.append(doc["request_id"])

    # register mid-flight
    st, doc, _ = rpc(gw, "POST", "/v1/modules",
                     {"name": "dbl",
                      "wasm_b64": base64.b64encode(build_dbl()).decode()})
    assert st == 201, doc
    assert doc["generation"] == gen0 + 1
    assert doc["modules"][-1] == "dbl"
    assert doc["exports"] == ["dbl"]

    # the new module serves on the new generation immediately
    ds = [3, 1000, 7]
    got_dbl = []
    for n in ds:
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"module": "dbl", "func": "dbl", "args": [n]})
        assert st == 200 and doc["ok"], doc
        assert doc["generation"] == gen0 + 1
        got_dbl.append(doc["result"][0])
    # ... and the old module still serves (same pool, qualified route)
    st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                     {"module": "fib", "func": "fib", "args": [11]})
    assert st == 200 and doc["result"] == [89], doc

    # in-flight generation-N requests complete with correct results,
    # attributed to the OLD generation
    for rid, n in zip(ids, (17, 16, 15)):
        st, doc = _poll(gw, rid)
        assert st == 200 and doc["ok"], doc
        assert doc["result"] == [_fib(n)]
        assert doc["generation"] == gen0

    # the drained old generation is eventually reaped
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        st, doc, _ = rpc(gw, "GET", "/v1/status")
        if doc["draining_generations"] == 0:
            break
        time.sleep(0.05)
    assert doc["draining_generations"] == 0
    assert doc["generation"] == gen0 + 1

    # bit-identical to a solo execute_batch run of the runtime-
    # registered module alone (cold-start parity)
    import numpy as np

    from wasmedge_tpu.vm import VM

    vm = VM(_conf())
    vm.load_wasm(build_dbl())
    vm.validate()
    vm.instantiate()
    solo = vm.execute_batch("dbl", [np.asarray(ds, np.int64)],
                            lanes=len(ds))
    assert solo.completed.all()
    assert got_dbl == [int(x) for x in solo.results[0]]
    assert got_dbl == [2 * n + 7 for n in ds]


def test_wasi_guest_registers_over_http_and_serves(gw_main):
    """A guest that imports WASI (the echo guest: two fd_write calls an
    iteration) registers at runtime as a raw application/wasm body and
    answers; its stdout goes to the module's own sink."""
    from wasmedge_tpu.models import build_echo

    gw = gw_main
    st, doc, _ = rpc(gw, "POST", "/v1/modules?name=echo",
                     body=build_echo(),
                     headers={"Content-Type": "application/wasm"})
    assert st == 201, doc
    assert doc["exports"] == ["echo"]
    ids = []
    for _ in range(3):
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"module": "echo", "func": "echo", "args": [2],
                          "async": True})
        assert st == 202, doc
        ids.append(doc["request_id"])
    for rid in ids:
        st, doc = _poll(gw, rid)
        # echo returns fd_write's last errno
        assert st == 200 and doc["ok"] and doc["result"] == [0], doc


# ---------------------------------------------------------------------------
# rejection taxonomy on the wire
# ---------------------------------------------------------------------------
def test_unknown_module_bad_wasm_and_conflict_rejection(gw_main):
    gw = gw_main
    st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                     {"module": "nope", "func": "f", "args": []})
    assert st == 404 and not doc["ok"], doc
    st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                     {"module": "fib", "func": "nofunc"})
    assert st == 404, doc
    st, doc, _ = rpc(gw, "GET", "/v1/requests/999999")
    assert st == 404, doc

    # garbage bytes: LoadError taxonomy in the body
    st, doc, _ = rpc(gw, "POST", "/v1/modules",
                     {"name": "junk",
                      "wasm_b64":
                      base64.b64encode(b"not wasm at all").decode()})
    assert st == 400, doc
    assert doc["err"]["retryable"] is False
    assert "code" in doc["err"] and "name" in doc["err"]

    # well-formed but unlinkable (unknown import): still 400, and the
    # module must NOT have been registered
    st, doc, _ = rpc(gw, "POST", "/v1/modules",
                     {"name": "orphan",
                      "wasm_b64":
                      base64.b64encode(build_unlinkable()).decode()})
    assert st == 400, doc
    st, doc, _ = rpc(gw, "GET", "/v1/status")
    assert "orphan" not in doc["modules"]
    assert "junk" not in doc["modules"]

    # duplicate name -> 409
    st, doc, _ = rpc(gw, "POST", "/v1/modules",
                     {"name": "fib",
                      "wasm_b64": base64.b64encode(build_fib()).decode()})
    assert st == 409, doc
    assert doc["err"]["name"] == "ModuleNameConflict"

    # malformed requests -> 400
    st, doc, _ = rpc(gw, "POST", "/v1/invoke", b"{not json",
                     headers={"Content-Type": "application/json"})
    assert st == 400, doc
    st, doc, _ = rpc(gw, "POST", "/v1/invoke", {"args": [1]})
    assert st == 400, doc  # missing func


# ---------------------------------------------------------------------------
# r13 surface on the shared gateway: truthful /healthz + durability fields
# ---------------------------------------------------------------------------
def test_healthz_and_status_carry_machine_readable_health(gw_main):
    """/healthz is no longer a liveness stub: the body carries the
    per-check breakdown (driver/queue/checkpoint), /v1/status embeds
    the same health block plus the durability flag, and the restart/
    rollback counters always render in /metrics (zero-valued on a
    fresh non-durable gateway)."""
    gw = gw_main
    st, doc, _ = rpc(gw, "GET", "/healthz")
    assert st == 200, doc
    assert doc["ok"] is True
    assert doc["status"] in ("healthy", "degraded")
    for check in ("driver", "queue", "checkpoint"):
        assert check in doc["checks"]
        assert set(doc["checks"][check]) == {"ok", "level", "detail"}

    st, doc, _ = rpc(gw, "GET", "/v1/status")
    assert st == 200
    assert doc["health"]["status"] in ("healthy", "degraded")
    assert doc["durable"] is False   # no state_dir on the shared gw
    assert "rollbacks" in doc["gateway"]
    assert "restarts" in doc["gateway"]

    st, text, _ = rpc(gw, "GET", "/metrics")
    assert st == 200
    assert "wasmedge_gateway_restarts_total" in text
    assert "wasmedge_generation_rollbacks_total" in text


# ---------------------------------------------------------------------------
# observability: gateway spans + http_requests_total
# ---------------------------------------------------------------------------
def test_gateway_obs_spans_and_metrics(gw_main):
    from wasmedge_tpu.obs.metrics import parse_prometheus

    gw = gw_main
    svc = gw.service
    for n, tenant in ((9, "obs-a"), (6, "obs-b")):
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"func": "fib", "args": [n], "tenant": tenant})
        assert st == 200, doc
    names = [e["name"] for e in svc.obs.events]
    assert "gateway_receive" in names
    assert "gateway/obs-a" in names and "gateway/obs-b" in names
    spans = [e for e in svc.obs.events
             if e["name"] in ("gateway/obs-a", "gateway/obs-b")]
    assert all(e["track"] == "gateway" and e["args"]["ok"]
               for e in spans)

    st, text, _ = rpc(gw, "GET", "/metrics")
    assert st == 200
    parsed = parse_prometheus(text)
    key = ("wasmedge_gateway_http_requests_total",
           frozenset({("code", "200")}))
    assert parsed[key] >= 2.0


# ---------------------------------------------------------------------------
# auth + per-tenant policy enforcement
# ---------------------------------------------------------------------------
def test_auth_and_quota_enforcement(tmp_path):
    policy = {
        "require_auth": True,
        "tenants": {
            "alice": {"api_key": "sk-alice", "weight": 2.0, "quota": 2},
            "bob": {"api_key": "sk-bob", "can_register": False,
                    "rate_per_s": 1000.0, "burst": 3},
        },
    }
    pf = tmp_path / "tenants.json"
    pf.write_text(json.dumps(policy))
    tenants = GatewayTenants.from_file(str(pf))
    gw = _gateway(lanes=2, tenants=tenants)
    svc = gw.service
    try:
        # quota/weight made it onto the FairQueue admission substrate
        srv = svc.current.server
        assert srv.queue.quotas == {"alice": 2}
        assert srv.queue.weights == {"alice": 2.0, "bob": 1.0}

        # no key -> 401; unknown key -> 401; key/tenant mismatch -> 401
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"func": "fib", "args": [5]})
        assert st == 401, doc
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"func": "fib", "args": [5]},
                         headers={"Authorization": "Bearer sk-wrong"})
        assert st == 401, doc
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"func": "fib", "args": [5], "tenant": "bob"},
                         headers={"Authorization": "Bearer sk-alice"})
        assert st == 401, doc

        # a good key resolves the tenant (either header form)
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"func": "fib", "args": [10]},
                         headers={"Authorization": "Bearer sk-alice"})
        assert st == 200 and doc["result"] == [55], doc
        assert doc["tenant"] == "alice"
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"func": "fib", "args": [6]},
                         headers={"X-Api-Key": "sk-bob"})
        assert st == 200 and doc["tenant"] == "bob", doc

        # registration permission is per tenant (a 403 here must NOT
        # consume the name: alice's retry of the same name succeeds)
        wasm64 = base64.b64encode(build_dbl()).decode()
        st, doc, _ = rpc(gw, "POST", "/v1/modules",
                         {"name": "dbl", "wasm_b64": wasm64},
                         headers={"X-Api-Key": "sk-bob"})
        assert st == 403, doc
        st, doc, _ = rpc(gw, "POST", "/v1/modules",
                         {"name": "dbl", "wasm_b64": wasm64},
                         headers={"X-Api-Key": "sk-alice"})
        assert st == 201, doc

        # bob's token bucket enforced at the edge: stop refills, flood
        tenants._buckets["bob"].rate = 0.001
        saw_429 = None
        for _ in range(8):
            st, doc, hdrs = rpc(gw, "POST", "/v1/invoke",
                                {"func": "fib", "args": [4],
                                 "async": True},
                                headers={"X-Api-Key": "sk-bob"})
            if st == 429:
                saw_429 = (doc, hdrs)
                break
        assert saw_429 is not None
        doc, hdrs = saw_429
        assert doc["err"]["name"] == "RateLimited"
        assert doc["err"]["retryable"] is True
        assert "Retry-After" in hdrs
        assert svc.counters["rate_limited"] >= 1

        # obs is off by default here — yet the HTTP tally still lands
        # in the Prometheus text (bookkeeping, not tracing)
        assert svc.obs.enabled is False
        st, text, _ = rpc(gw, "GET", "/metrics")
        assert "wasmedge_gateway_http_requests_total" in text
    finally:
        gw.shutdown()


# ---------------------------------------------------------------------------
# deadline / backpressure status codes over a real socket
# ---------------------------------------------------------------------------
def test_deadline_and_backpressure_status_codes():
    conf = _conf()
    conf.serve.queue_capacity = 2
    gw = _gateway(conf=conf, lanes=1)
    try:
        # occupy the single lane, then fill the bounded queue: the
        # next submission must draw 429 + Retry-After (QueueSaturated
        # is the retryable class).  Admission runs on the driver
        # thread, so flood until the queue is provably full.
        st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                         {"func": "fib", "args": [17], "async": True})
        assert st == 202, doc
        long_id = doc["request_id"]
        saw_429 = None
        spill_ids = []
        for _ in range(12):
            st, doc, hdrs = rpc(gw, "POST", "/v1/invoke",
                                {"func": "fib", "args": [15],
                                 "async": True})
            if st == 429:
                saw_429 = (doc, hdrs)
                break
            spill_ids.append(doc["request_id"])
        assert saw_429 is not None, "queue never saturated"
        doc, hdrs = saw_429
        assert "Retry-After" in hdrs
        assert doc["err"]["retryable"] is True
        assert doc["err"]["code"] == int(ErrCode.CostLimitExceeded)

        # deadline: a queued request behind the long ones expires ->
        # 504 with the DeadlineExceeded taxonomy (non-retryable).  The
        # queue may still be saturated — honor the 429 contract and
        # retry until admitted (exactly what a well-behaved client
        # does with Retry-After)
        deadline = time.monotonic() + 60.0
        while True:
            st, doc, _ = rpc(gw, "POST", "/v1/invoke",
                             {"func": "fib", "args": [17],
                              "deadline_ms": 1})
            if st != 429:
                break
            assert time.monotonic() < deadline, "queue never drained"
            time.sleep(0.05)
        assert st == 504, doc
        assert doc["err"]["retryable"] is False
        assert doc["err"]["code"] == int(ErrCode.Terminated)

        # the occupying + spilled requests still complete correctly
        st, doc = _poll(gw, long_id)
        assert st == 200 and doc["result"] == [_fib(17)], doc
        for rid in spill_ids:
            st, doc = _poll(gw, rid)
            assert st == 200 and doc["result"] == [_fib(15)], doc
    finally:
        gw.shutdown()


# ---------------------------------------------------------------------------
# swap race: a submit that captured the old generation retries on the new
# ---------------------------------------------------------------------------
def test_submit_racing_a_generation_swap_lands_on_the_successor():
    """submit() resolves the current generation, then calls its server
    outside the gateway lock; a registration landing in that window
    makes the captured generation reject with a permanent 'draining'
    error.  That rejection belongs to the stale generation — the
    service must retry on the successor, never surface a non-retryable
    error for a servable request."""
    svc = GatewayService(conf=_conf(), lanes=2)
    svc.register_module("fib", wasm_bytes=build_fib(), source="boot")
    gen1_server = svc.current.server
    orig_submit = gen1_server.submit
    fired = {}

    def racing_submit(*a, **kw):
        if not fired:
            # the swap happens "between" the service's current-read and
            # the server call: generation 2 installs, generation 1
            # starts draining and rejects
            fired["yes"] = True
            svc.register_module("dbl", wasm_bytes=build_dbl(),
                                source="boot")
            raise WasmError(ErrCode.Terminated,
                            "server is draining; submissions closed")
        return orig_submit(*a, **kw)

    gen1_server.submit = racing_submit
    try:
        req = svc.submit("fib", [10], module="fib")
        assert req.gen_id == 2          # routed to the successor
        assert svc.wait(req, timeout_s=120.0)
        assert req.future.result(0) == [55]
        assert svc.counters["rejected"] == 0
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# the machine-readable rejection contract (in-process half)
# ---------------------------------------------------------------------------
def test_keyed_tenant_requires_its_key_even_without_require_auth():
    """A tenant with an api_key configured cannot be claimed keyless
    just because global require_auth is off — the key would otherwise
    silently protect nothing (weight/quota/can_register hijack)."""
    from wasmedge_tpu.gateway.tenants import AuthError

    t = GatewayTenants.from_dict({"tenants": {
        "keyed": {"api_key": "sk-k", "weight": 3.0},
        "open": {},
    }})
    assert t.require_auth is False
    assert t.authenticate("sk-k", None) == "keyed"
    assert t.authenticate(None, "open") == "open"
    assert t.authenticate(None, None) == "default"
    with pytest.raises(AuthError):
        t.authenticate(None, "keyed")


def test_gateway_closed_maps_to_503():
    """Lifecycle-terminated (gateway shutting down) is 503, never the
    admission-block 403 — a client must keep retrying a restarting
    gateway."""
    from wasmedge_tpu.gateway.http import submit_status_of
    from wasmedge_tpu.gateway.service import GatewayClosed

    assert submit_status_of(GatewayClosed()) == 503
    # the admission block (same ErrCode) stays 403
    assert submit_status_of(WasmError(ErrCode.Terminated)) == 403
    svc = GatewayService(conf=_conf(), lanes=2)
    svc.shutdown()
    with pytest.raises(GatewayClosed):
        svc.submit("fib", [1])
    with pytest.raises(GatewayClosed):
        svc.register_module("m", wasm_bytes=build_fib())


def test_structured_rejection_contract():
    from wasmedge_tpu.serve.queue import DeadlineExceeded, QueueSaturated

    qs = QueueSaturated(retry_after_s=0.25)
    assert qs.retryable is True
    info = rejection_info(qs)
    assert info["code"] == int(ErrCode.CostLimitExceeded)
    assert info["name"] == "CostLimitExceeded"
    assert info["retryable"] is True
    assert info["retry_after_s"] == 0.25

    dl = DeadlineExceeded()
    assert dl.retryable is False
    assert rejection_info(dl)["retryable"] is False

    # plain WasmErrors (permanent conditions) default non-retryable
    assert WasmError(ErrCode.Terminated).retryable is False
    # non-WasmError exceptions normalize into the same shape
    info = rejection_info(RuntimeError("boom"))
    assert info["retryable"] is False
    assert info["code"] == int(ErrCode.ExecutionFailed)

    # lifecycle rejections (guest never ran) are 503 at resolution,
    # never presented as a guest trap (200 ok:false)
    from types import SimpleNamespace

    from wasmedge_tpu.gateway.http import result_response
    from wasmedge_tpu.serve.queue import ServeRejected

    fake = SimpleNamespace(id=1, func="f", tenant="t", gen_id=1,
                           future=SimpleNamespace(
                               error=ServeRejected("server shut down")))
    assert result_response(fake)[0] == 503
    fake.future.error = WasmError(ErrCode.Unreachable)  # a real trap
    assert result_response(fake)[0] == 200

    # args that don't fit a 64-bit lane cell are rejected at
    # SUBMISSION (ValueError -> 400), never on the serving thread
    from wasmedge_tpu.serve.queue import ServeRequest

    with pytest.raises(ValueError):
        ServeRequest("f", (1 << 80,))
    ServeRequest("f", ((1 << 63) - 1, -(1 << 63)))  # extremes fit

    # fleet routing (r16): a request whose rendezvous owner is a
    # SUSPECT peer refuses retryably with Retry-After — 503 at the
    # edge with detail "peer_suspect", never a bare 503 string (the
    # over-the-wire half is pinned in tests/test_fleet.py)
    from wasmedge_tpu.fleet import PeerSuspect
    from wasmedge_tpu.gateway.http import retry_after_of, \
        submit_status_of

    ps = PeerSuspect("10.0.0.2:8080", 41)
    assert ps.retryable is True
    info = rejection_info(ps)
    assert info["retryable"] is True
    assert info["retry_after_s"] > 0
    assert info["detail"] == "peer_suspect"
    assert submit_status_of(ps) == 503
    assert retry_after_of(ps) is not None

    # strict journal replication failure withdraws the acceptance with
    # the same retryable contract as a failed local journal write
    from wasmedge_tpu.fleet import ReplicationFailed

    rf = ReplicationFailed("no peer reachable")
    assert rejection_info(rf)["retryable"] is True


def test_server_submit_rejections_carry_the_flag():
    """BatchServer.submit's two rejection classes are distinguishable
    by flag alone — the gateway's status mapping and the CLI retry
    loop both branch on it, never on strings."""
    from tests.test_serve import _server

    conf = _conf()
    conf.serve.queue_capacity = 1
    srv = _server(conf=conf, lanes=1, quotas={"blocked": 0})
    # permanent admission block: non-retryable
    with pytest.raises(WasmError) as exc:
        srv.submit("fib", [5], tenant="blocked")
    assert exc.value.retryable is False
    # transient backpressure: retryable (fill the 1-slot queue without
    # stepping, so nothing is admitted meanwhile)
    srv.submit("fib", [10])
    with pytest.raises(WasmError) as exc:
        srv.submit("fib", [10])
    assert exc.value.retryable is True
    srv.run_until_idle()
    srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# CLI entry
# ---------------------------------------------------------------------------
def test_cli_gateway_command(tmp_path):
    """Startup line with the bound address + modules, clean --duration
    exit with the summary line.  Deliberately NO invoke: serving is
    covered above, and an invoke would compile a default-geometry
    engine just for this test."""
    import io

    from wasmedge_tpu.cli import gateway_command

    wasm = tmp_path / "fib.wasm"
    wasm.write_bytes(build_fib())
    wasm2 = tmp_path / "dbl.wasm"
    wasm2.write_bytes(build_dbl())
    out, errs = io.StringIO(), io.StringIO()
    rc = gateway_command(
        [str(wasm), "--port", "0", "--lanes", "2",
         "--module", f"second={wasm2}",
         "--duration", "0.2"], out=out, err=errs)
    assert rc == 0, errs.getvalue()
    lines = out.getvalue().splitlines()
    startup = json.loads(lines[0])
    assert startup["modules"] == ["main", "second"]
    assert startup["listening"].startswith("http://127.0.0.1:")
    assert startup["lanes"] == 2
    # the boot health gate ran and the startup line reports it
    assert startup["health"] == "healthy"
    assert startup["durable"] is False and startup["restarts"] == 0
    summary = json.loads(lines[-1])
    assert summary["metric"] == "gateway_exit"
    assert summary["received"] == 0
    # the whole boot set shares ONE generation (no build-and-drain
    # churn per --module)
    assert summary["generations"] == 1

    rc2 = gateway_command(["--module", "badspec"], out=io.StringIO(),
                          err=errs)
    assert rc2 == 2
    assert "badspec" in errs.getvalue()
