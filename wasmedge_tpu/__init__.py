"""tpu-wasm: a TPU-native WebAssembly runtime with WasmEdge's capabilities.

Pipeline (mirrors the reference's Load -> Validate -> Instantiate -> Execute
staging, /root/reference/include/vm/vm.h:241):

  loader    : bytes -> AST (flat, branch-annotated instructions)
  validator : type-check + lowering to a dense SoA bytecode image
  executor  : scalar reference engine (oracle) over the lowered image
  batch     : SIMT lockstep JAX/Pallas engine, thousands of lanes per chip
  host      : WASI + process host modules (device lanes trap out to CPU)
  vm        : VM facade + Configure-driven engine selection
"""

__version__ = "0.1.0"

# Import-tax discipline: this module (and everything it pulls in) must
# stay free of jax/jaxlib/numpy so `import wasmedge_tpu` and the
# scalar/native CLI paths never pay the JAX import tax (~1s of
# r5's python_spawn_floor).  Heavy entry points are exposed lazily
# below; tests/test_spawn_time.py asserts the invariant in a fresh
# interpreter.
import os as _os

from wasmedge_tpu.common.configure import Configure, EngineKind
from wasmedge_tpu.common.errors import ErrCode, TrapError, WasmError

# The chip's compiler infers a Pallas kernel's vector layouts by
# recursing over its nested regions on a fiber's stack, and libtpu's
# default stack holds some twelve levels (tests/test_chip_compile.py):
# a kernel of many handlers nests deeper (CoreMark's, fifteen) and
# crashed the compiler.  A larger stack compiles the same program (the
# gemm kernel's text is the same to the byte with it) and lets the
# compiler go deeper.  Set before libtpu starts, which reads it once; a
# setting the environment already makes is kept.
_STACK_FLAG = "--fibers_default_thread_stack_size="
if _STACK_FLAG not in _os.environ.get("LIBTPU_INIT_ARGS", ""):
    _os.environ["LIBTPU_INIT_ARGS"] = " ".join(filter(None, (
        _os.environ.get("LIBTPU_INIT_ARGS"), _STACK_FLAG + str(8 << 20))))

_LAZY = {
    "VM": ("wasmedge_tpu.vm", "VM"),
    "make_engine": ("wasmedge_tpu.batch", "make_engine"),
    "WasiModule": ("wasmedge_tpu.host.wasi", "WasiModule"),
}


def __getattr__(name: str):
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(target[0]), target[1])


__all__ = [
    "Configure",
    "EngineKind",
    "ErrCode",
    "TrapError",
    "WasmError",
    "VM",
    "make_engine",
    "WasiModule",
]
