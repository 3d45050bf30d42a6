"""End-to-end integrity defense against silent corruption (r24,
wasmedge_tpu/integrity/, marker `integrity`).

Pins the r24 acceptance contract:

  - shadow-audit sampling is deterministic under a fixed seed (same
    boundaries -> same lane subsets, across sampler instances)
  - a clean audited run matches bit-exactly (zero divergence counted)
    and returns results bit-identical to the audit-off run
  - a bit flip injected into a BatchState lane plane is DETECTED by
    the shadow audit, recorded as an "integrity" FailureRecord, rolled
    back, and masked: final results stay bit-correct
  - a corrupted compile-cache entry is caught by the at-rest scrubber
    and evicted; the next registration lowers fresh, correct code
  - a corrupted parked-session blob is repaired from a fleet peer
    replica (GET /v1/fleet/blob/<key>) BEFORE the wake needs it, over
    real sockets, resolving bit-identically
  - a checkpoint member whose sha256 sidecar mismatches is quarantined
    (renamed `.corrupt`) so the recovery walk falls back
  - integrity off (the default) arms no hooks, adds no status block
    and no metric families — bit-identical r23 by construction

Fast by construction: tiny lane counts, short chunks, module-scoped
JAX persistent cache for the gateway legs.
"""

import os
import struct
import tempfile
import time

import numpy as np
import pytest

from wasmedge_tpu.batch.engine import BatchEngine
from wasmedge_tpu.batch.supervisor import BatchSupervisor
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.fleet import FleetConfig
from wasmedge_tpu.gateway import Gateway, GatewayService
from wasmedge_tpu.integrity import AuditSampler, Scrubber
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.testing.faults import BitFlip, FaultInjector, \
    flip_bit_bytes, flip_file
from tests.helpers import instantiate

pytestmark = pytest.mark.integrity

LANES = 16


@pytest.fixture(scope="module", autouse=True)
def _compile_cache():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    d = tempfile.mkdtemp(prefix="integrity-jit-cache-")
    jax.config.update("jax_compilation_cache_dir", d)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def make_conf(audit=False, **integ):
    conf = Configure()
    conf.batch.steps_per_launch = 100
    conf.batch.rng_seed = 7
    conf.supervisor.backoff_base_s = 0.0
    conf.supervisor.checkpoint_every_steps = 200
    conf.integrity.audit = audit
    if audit:
        conf.integrity.audit_every = 1     # audit every boundary
        conf.integrity.audit_lanes = 4
    for k, v in integ.items():
        setattr(conf.integrity, k, v)
    return conf


def make_engine(data, conf, lanes=LANES):
    ex, store, inst = instantiate(data, conf)
    return BatchEngine(inst, store=store, conf=conf, lanes=lanes)


def fib_ref(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


FIB_ARGS = [(np.arange(LANES) % 11).astype(np.int64)]
FIB_WANT = np.array([fib_ref(n % 11) for n in range(LANES)])


# ---------------------------------------------------------------------------
# shadow-audit sampling: seeded, deterministic, bounded
# ---------------------------------------------------------------------------
def test_audit_sampler_deterministic_under_fixed_seed():
    a = AuditSampler(seed=5, every=4, lanes_per_audit=3)
    b = AuditSampler(seed=5, every=4, lanes_per_audit=3)
    picks_a = [a.pick(t, LANES) for t in range(64)]
    picks_b = [b.pick(t, LANES) for t in range(64)]
    for pa, pb in zip(picks_a, picks_b):
        if pa is None:
            assert pb is None
        else:
            assert (pa == pb).all()
    sampled = [p for p in picks_a if p is not None]
    assert sampled, "every=4 over 64 boundaries must sample some"
    assert len(sampled) < 64, "every=4 must not sample EVERY boundary"
    for p in sampled:
        assert len(p) == 3 and len(set(p.tolist())) == 3
        assert list(p) == sorted(p)          # stable gather order
        assert all(0 <= int(x) < LANES for x in p)
    # a different seed draws a different schedule (overwhelmingly)
    other = [AuditSampler(seed=6, every=4, lanes_per_audit=3)
             .pick(t, LANES) for t in range(64)]
    assert [None if p is None else p.tolist() for p in picks_a] != \
           [None if p is None else p.tolist() for p in other]


def test_audited_clean_run_matches_and_is_bit_identical(tmp_path):
    ref = BatchSupervisor(make_engine(build_fib(), make_conf()),
                          checkpoint_dir=str(tmp_path / "ref"))
    rres = ref.run("fib", FIB_ARGS, max_steps=500_000)

    sup = BatchSupervisor(make_engine(build_fib(), make_conf(audit=True)),
                          checkpoint_dir=str(tmp_path / "a"))
    res = sup.run("fib", FIB_ARGS, max_steps=500_000)
    stats = sup.engine._audit_hook.stats
    assert stats["audits"] >= 1
    assert stats["match"] == stats["audits"]
    assert stats["divergence"] == 0
    assert not sup.failures
    # audit-on returns the exact bits audit-off returns
    assert (res.results[0] == rres.results[0]).all()
    assert (res.results[0] == FIB_WANT).all()
    assert (res.trap == rres.trap).all()
    assert (res.retired == rres.retired).all()


# ---------------------------------------------------------------------------
# detection: an injected lane-plane bit flip cannot survive silently
# ---------------------------------------------------------------------------
def test_audit_detects_plane_flip_rolls_back_and_masks(tmp_path):
    inj = FaultInjector([], flips=[
        BitFlip(point="corrupt_plane", at=1, seed=42)])
    sup = BatchSupervisor(make_engine(build_fib(), make_conf(audit=True)),
                          faults=inj, checkpoint_dir=str(tmp_path))
    res = sup.run("fib", FIB_ARGS, max_steps=500_000)
    assert inj.flipped == 1
    stats = sup.engine._audit_hook.stats
    assert stats["divergence"] >= 1
    assert "integrity" in [f.fault_class for f in sup.failures]
    # rollback + re-execution MASKED the corruption: exact results
    assert res.completed.all()
    assert (res.results[0] == FIB_WANT).all()


def test_audit_attributes_device_and_feeds_quarantine(tmp_path):
    inj = FaultInjector([], flips=[
        BitFlip(point="corrupt_plane", at=1, seed=9)])
    sup = BatchSupervisor(make_engine(build_fib(),
                                      make_conf(audit=True,
                                                quarantine_threshold=1)),
                          faults=inj, checkpoint_dir=str(tmp_path))
    sup.run("fib", FIB_ARGS, max_steps=500_000)
    q = sup.engine._audit_hook.quarantine.snapshot()
    assert sum(q["counts"].values()) >= 1, \
        "divergence must attribute to a device counter"


# ---------------------------------------------------------------------------
# at-rest scrub: compile cache
# ---------------------------------------------------------------------------
def test_corrupt_cache_entry_scrubbed_then_relowered_fresh():
    with tempfile.TemporaryDirectory() as cache_dir:
        def conf():
            c = Configure()
            c.batch.steps_per_launch = 256
            c.batch.value_stack_depth = 128
            c.batch.call_stack_depth = 64
            c.imagestore.compile_cache = True
            c.imagestore.compile_cache_dir = cache_dir
            c.integrity.scrub = True
            return c

        data = build_fib()
        svc = GatewayService(conf=conf(), lanes=2)
        try:
            svc.register_module("fib", wasm_bytes=data)
            assert svc.registry.lowered_count == 1
            shas = svc.registry.compile_cache.known_shas()
            assert len(shas) == 1
            # clean pass: entry verifies, nothing moves
            delta = svc.scrub_once()
            assert delta["entries"] >= 1 and delta["corrupt"] == 0
            # rot the persistent entry (disk + in-memory tier)
            entry = [fn for fn in os.listdir(cache_dir)
                     if fn.endswith(".img")][0]
            flip_file(os.path.join(cache_dir, entry), seed=11)
            cc = svc.registry.compile_cache
            with cc._lock:               # the disk copy is the truth now
                cc._payloads.pop(shas[0], None)
            delta = svc.scrub_once()
            assert delta["corrupt"] == 1
            assert delta["evicted"] == 1    # no fleet: evict, not repair
            assert shas[0] not in cc.known_shas()
        finally:
            svc.shutdown()
        # next registration over the scrubbed dir lowers FRESH and runs
        # the right code — rot never becomes servable state
        svc2 = GatewayService(conf=conf(), lanes=2)
        try:
            svc2.register_module("fib", wasm_bytes=data)
            assert svc2.registry.lowered_count == 1
            req = svc2.submit("fib", [12], module="fib",
                              tenant="default")
            assert svc2.wait(req, timeout_s=120.0)
            assert req.future.result(0) == [144]
        finally:
            svc2.shutdown()


# ---------------------------------------------------------------------------
# at-rest scrub: checkpoint lineage sidecars
# ---------------------------------------------------------------------------
def test_corrupt_checkpoint_member_quarantined(tmp_path):
    sup = BatchSupervisor(
        make_engine(build_fib(), make_conf()),
        checkpoint_dir=str(tmp_path))
    sup.run("fib", FIB_ARGS, max_steps=500_000)
    members = [str(tmp_path / fn) for fn in sorted(os.listdir(tmp_path))
               if fn.endswith(".npz")]
    assert members, "the run must have checkpointed"
    victim = members[-1]
    assert os.path.exists(victim + ".sha256"), \
        "checkpoint.save must write the integrity sidecar"
    flip_file(victim, seed=21)
    scrub = Scrubber(Configure().integrity,
                     checkpoints=lambda: members)
    delta = scrub.scrub_once()
    assert delta["quarantined_members"] == 1
    assert not os.path.exists(victim)
    assert os.path.exists(victim + ".corrupt")
    # older members are untouched — the recovery walk falls back
    for m in members[:-1]:
        assert os.path.exists(m)


# ---------------------------------------------------------------------------
# at-rest scrub: parked-session blob repaired from a fleet peer replica
# ---------------------------------------------------------------------------
def _fleet_cfg(peers=(), **kw):
    kw.setdefault("auto_tick", False)
    kw.setdefault("backoff_base_s", 0.0)
    return FleetConfig(peers=peers, **kw)


@pytest.mark.parametrize("both_copies", [False, True])
def test_swapstore_rot_heals_from_mirror_or_replica(tmp_path, both_copies):
    """SwapStore rot under the scrubber: a bad memory copy heals from
    the disk mirror; rot in both copies repairs through the fetch
    closure (a peer's replica).  Either way one corrupt entry is counted,
    one repaired, and the payload reads back bit-exact."""
    from wasmedge_tpu.hv.swapstore import SwapStore

    store = SwapStore(dir=str(tmp_path))
    payload = np.random.RandomState(5).bytes(4096)
    key = store.put(payload)
    store._mem[key] = flip_bit_bytes(store._mem[key], seed=5)
    if both_copies:
        flip_file(store._path(key), seed=6)
    scrub = Scrubber(Configure().integrity,
                     swap_stores=lambda: [("swap", store, False)],
                     fetch_blob={key: payload}.get)
    delta = scrub.scrub_once()
    assert delta["corrupt"] == 1 and delta["repaired"] == 1
    assert store.get(key) == payload
    assert scrub.scrub_once()["corrupt"] == 0


def test_corrupt_parked_blob_repaired_from_peer_before_wake():
    from tests.test_fleet import _await_mod, _drain

    def conf():
        c = Configure()
        c.batch.steps_per_launch = 256
        c.batch.value_stack_depth = 64
        c.batch.call_stack_depth = 32
        c.effects.suspend = True
        c.integrity.scrub = True
        return c

    svc_a = GatewayService(conf=conf(), lanes=2, fleet=_fleet_cfg())
    gw_a = Gateway(svc_a, port=0).start()
    svc_a.register_module("awaitmod", wasm_bytes=_await_mod(),
                          source="boot")
    svc_b = GatewayService(
        conf=conf(), lanes=2,
        fleet=_fleet_cfg([f"{gw_a.host}:{gw_a.port}"]))
    gw_b = Gateway(svc_b, port=0).start()
    try:
        svc_b.fleet.tick()   # learn manifest + replicate awaitmod
        svc_b.fleet.tick()
        req = svc_a._submit_local("wait", [5], module="awaitmod")
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if req.id in svc_a.current.server.list_swapped():
                break
            time.sleep(0.01)
        else:
            raise TimeoutError("session never parked")
        store_a = svc_a.current.server.effects.store
        (key,) = store_a.scrub_keys()
        payload = store_a.get(key)
        # B holds a verified replica (the migration/adoption channel)
        svc_b.current.server.effects.store.adopt(key, payload)
        # rot A's only copy; get() would now refuse the wake's swap-in
        store_a._mem[key] = flip_bit_bytes(store_a._mem[key], seed=3)
        delta = svc_a.scrub_once()
        assert delta["corrupt"] == 1 and delta["repaired"] == 1
        assert store_a.get(key) == payload   # repaired bit-exact
        assert svc_b.fleet.counters["blob_repairs_served"] == 1
        # the wake rides the repaired blob to a bit-correct resolution
        svc_a.wake(req.id, struct.pack("<I", 900))
        _drain(svc_a, [req], timeout_s=120.0)
        assert req.future.result(0) == [905]
        # telemetry: status block + metric family present when on
        assert svc_a.status()["integrity"]["scrub"]["repaired"] == 1
        assert "wasmedge_integrity_scrub_entries_total" \
            in svc_a.metrics_text()
    finally:
        gw_b.shutdown()
        gw_a.shutdown()


# ---------------------------------------------------------------------------
# integrity off IS r23: no hooks, no status block, no metric families
# ---------------------------------------------------------------------------
def test_integrity_off_is_inert(tmp_path):
    conf = make_conf()
    assert conf.integrity.active is False
    sup = BatchSupervisor(make_engine(build_fib(), conf),
                          checkpoint_dir=str(tmp_path))
    res = sup.run("fib", FIB_ARGS, max_steps=500_000)
    assert (res.results[0] == FIB_WANT).all()
    assert getattr(sup.engine, "_audit_hook", None) is None
    assert getattr(sup.engine, "_flip_hook", None) is None

    svc = GatewayService(conf=Configure(), lanes=2)
    try:
        svc.register_module("fib", wasm_bytes=build_fib())
        assert svc.scrubber is None
        assert svc.integrity_stats() is None
        assert "integrity" not in svc.status()
        assert "wasmedge_integrity" not in svc.metrics_text()
    finally:
        svc.shutdown()
