"""Spawn-time regression tests: scalar/native paths never pay the JAX
import tax (r5's python_spawn_floor attribution).

The assertions run fresh interpreters, so the suite marks them slow;
tier-1 CI keeps the cheap in-process guard at the bottom.
"""

import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _run_py(code: str) -> str:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**os.environ, "PYTHONPATH": ROOT},
    )
    assert out.returncode == 0, out.stderr
    return out.stdout


@pytest.mark.slow
def test_package_import_stays_light():
    """`import wasmedge_tpu` must not pull jax/jaxlib/numpy."""
    stdout = _run_py("""
        import sys
        import wasmedge_tpu
        print(sorted(m for m in ("jax", "jaxlib", "numpy")
                     if m in sys.modules))
    """)
    assert stdout.strip() == "[]"


@pytest.mark.slow
def test_scalar_cli_run_skips_jax():
    """A scalar-engine CLI run end-to-end must never import jax: the
    JAX import tax belongs to the batch engines only."""
    stdout = _run_py("""
        import sys
        from wasmedge_tpu.common.configure import Configure
        from wasmedge_tpu.executor import Executor
        from wasmedge_tpu.loader import Loader
        from wasmedge_tpu.runtime.store import StoreManager
        from wasmedge_tpu.utils.builder import ModuleBuilder
        from wasmedge_tpu.validator import Validator

        b = ModuleBuilder()
        b.add_function(["i32"], ["i32"], [], [
            ("local.get", 0), ("i32.const", 1), "i32.add",
        ], export="inc")
        conf = Configure()
        mod = Validator(conf).validate(Loader(conf).parse_module(b.build()))
        store = StoreManager()
        ex = Executor(conf)
        inst = ex.instantiate(store, mod)
        assert ex.invoke(store, inst.find_func("inc"), [41]) == [42]
        print("jax" in sys.modules or "jaxlib" in sys.modules)
    """)
    assert stdout.strip() == "False"


def test_inprocess_lazy_surface():
    """Cheap tier-1 guard: the lazy re-exports resolve and the eager
    import surface of wasmedge_tpu stays numpy/jax-free (checked via
    module dependency scan, not a fresh interpreter)."""
    import importlib.util

    for mod in ("wasmedge_tpu", "wasmedge_tpu.common.configure",
                "wasmedge_tpu.common.errors", "wasmedge_tpu.common.types",
                "wasmedge_tpu.cli"):
        spec = importlib.util.find_spec(mod)
        assert spec is not None
        src = open(spec.origin).read()
        for heavy in ("\nimport jax", "\nimport numpy",
                      "\nfrom jax", "\nfrom numpy"):
            assert heavy not in src, f"{mod} imports eagerly: {heavy!r}"
    import wasmedge_tpu

    assert wasmedge_tpu.VM is not None
    assert wasmedge_tpu.make_engine is not None
