"""Deterministic testing harnesses for the batch engines.

`wasmedge_tpu.testing.faults` is the fault-injection harness behind the
supervised-execution tier-1 suite (tests/test_supervisor.py).
"""
