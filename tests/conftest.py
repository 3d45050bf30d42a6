"""Test config: force JAX onto a virtual 8-device CPU mesh (no TPU needed).

Must run before any jax import, hence env mutation at conftest import time.
The driver's dryrun_multichip uses the same mechanism.
"""

import os

# Force, not setdefault: a session env may name an accelerator, but
# tests must be deterministic IEEE CPU (the TPU flushes f32 denormals to
# zero — a documented batch-engine divergence, see
# wasmedge_tpu/batch/__init__.py).  This is the only way onto the CPU:
# the program itself has no fallback (batch.ensure_jax_backend).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# the config knob outranks whatever a platform plugin reads
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minutes-scale suite, skipped by --fast")
    config.addinivalue_line(
        "markers", "faults: deterministic fault-injection suite "
        "(supervised execution; tier-1 fast, runs under -m 'not slow')")
    config.addinivalue_line(
        "markers", "obs: observability suite (flight recorder, trace/"
        "metrics export; tier-1 fast, runs under -m 'not slow')")
    config.addinivalue_line(
        "markers", "serve: continuous-batching serving suite (request "
        "queue, lane recycling, fairness; tier-1 fast, runs under "
        "-m 'not slow')")
    config.addinivalue_line(
        "markers", "analysis: static bytecode analyzer suite (CFG/"
        "cost/divergence reports, gateway admission policy; tier-1 "
        "fast, runs under -m 'not slow')")
    config.addinivalue_line(
        "markers", "hv: lane-memory virtualization suite (swap store, "
        "eviction policy, oversubscribed serving; tier-1 fast, runs "
        "under -m 'not slow')")
    config.addinivalue_line(
        "markers", "fuse: SIMT superinstruction-fusion suite "
        "(translation pass, fused-dispatch bit-exactness, ladder "
        "demotion; tier-1 fast, runs under -m 'not slow')")
    config.addinivalue_line(
        "markers", "tierup: compiled-function tier suite (whole-"
        "function promotion, per-call dispatch, demotion ladder; "
        "tier-1 fast, runs under -m 'not slow')")
    config.addinivalue_line(
        "markers", "compact: divergence-aware lane-compaction suite "
        "(PC-sorted regrouping, serving/hv/checkpoint permutation "
        "remap; tier-1 fast, runs under -m 'not slow')")
    config.addinivalue_line(
        "markers", "effects: guest suspend/resume suite (parked "
        "sessions, external wake, streamed output; tier-1 fast, runs "
        "under -m 'not slow')")
    config.addinivalue_line(
        "markers", "integrity: silent-corruption defense suite "
        "(shadow-audit lanes, at-rest scrubbing, device quarantine; "
        "tier-1 fast, runs under -m 'not slow')")


def pytest_addoption(parser):
    parser.addoption(
        "--fast", action="store_true", default=False,
        help="run only the fast subset (skip @pytest.mark.slow suites)")


# Known minutes-scale suites are auto-marked slow so --fast works
# without touching each file; NEW slow files should carry
# `pytestmark = pytest.mark.slow` themselves (the marker is the
# mechanism, this list is back-compat).
_SLOW_FILES = {
    "test_spec.py", "test_batch_parity.py",
    "test_pallas_engine.py", "test_pallas_hbm.py", "test_optimistic.py",
    "test_mesh.py", "test_simd.py",
}


def pytest_collection_modifyitems(config, items):
    """`pytest --fast` (or `-m "not slow"`) skips the slow suites —
    an iteration loop in ~minutes instead of the >60-minute nightly
    wall.  The slow suites stay the default so `python -m pytest
    tests/ -x -q` remains the full bar."""
    import pytest as _pytest

    for item in items:
        if item.fspath.basename in _SLOW_FILES:
            item.add_marker(_pytest.mark.slow)
    if not config.getoption("--fast"):
        return
    skip = _pytest.mark.skip(reason="slow suite (run without --fast)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
