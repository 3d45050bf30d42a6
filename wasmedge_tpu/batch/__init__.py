"""tpu_batch engine: thousands of Wasm instances in SIMT lockstep on TPU.

This is the component the north star mandates (BASELINE.json): the
reference's `Executor::execute` dispatch loop (/root/reference/lib/executor/
engine/engine.cpp:68-1641) re-imagined as a vectorized lane machine. Each
TPU lane holds one instance's {pc, sp, fp, operand stack, call stack, linear
memory} as struct-of-arrays in HBM; every step fetches each lane's
instruction and executes all opcode-class handlers under lane masks
(divergence-safe SIMT), with traps recorded per lane instead of unwinding.

Values are two int32 planes (lo, hi): i32/f32 live in lo, i64 spans both —
the TPU-native layout (no 64-bit emulation tax on 32-bit ops, f32 via
bitcast). f64 and a few rare conversions are feature-gated: modules using
them fall back to the scalar/native engine via the Configure engine seam.

Known divergence on real TPU hardware: the TPU VPU flushes f32 subnormals
to zero, so float workloads touching denormals differ from IEEE in the last
ulp-range; integer workloads (the headline benches) are bit-exact. The
parity suite runs on the CPU backend where XLA is IEEE-strict; a softfloat
rare-path for denormals is planned (tracked in SURVEY.md §7 hard part (b)).
"""

import os

from wasmedge_tpu.batch.engine import BatchEngine, BatchResult
from wasmedge_tpu.batch.image import DeviceImage, batchability
from wasmedge_tpu.batch.uniform import UniformBatchEngine


def compile_cache_dir() -> str:
    """Where compiled executables (and the exported kernels, under
    `kexport/`) persist: JAX_COMPILATION_CACHE_DIR where set, else the
    one fixed `.jax_cache` of the checkout (listed in .gitignore).  The
    path is part of the cache key, so it never moves: no temporary name,
    pid or time.  Needs no JAX."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def ensure_jax_backend():
    """Initialize the JAX backend and the persistent XLA compilation
    cache (content-addressed on-disk, like the reference's AOT cache
    lib/aot/cache.cpp:36-61): a fresh process re-running a previously
    compiled kernel geometry loads the compiled executable from disk
    instead of re-running XLA/Mosaic.

    A device that will not initialise raises here: there is no CPU
    fallback (JAX_PLATFORMS=cpu is the only way onto the CPU).  The
    cache lives where JAX_COMPILATION_CACHE_DIR says; where nothing has
    set a directory yet it is `.jax_cache` in the checkout."""
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.devices()


def jit_in_place(fn, *planes, **jit_kw):
    """jax.jit(fn, **jit_kw) with the arguments `planes` donated, so
    that a program which sets a few rows or columns of a plane (or
    carries the whole state) writes it in place and the caller rebinds
    the result.  Not on the CPU with a persistent compile cache (the
    directory `ensure_jax_backend` sets): an executable deserialized
    from it there can lose its input/output aliasing and serve garbage
    outputs (jax 0.4.x), and on the CPU donation saves only allocator
    churn."""
    import jax

    if jax.default_backend() == "cpu" and \
            getattr(jax.config, "jax_compilation_cache_dir", None):
        planes = ()
    return jax.jit(fn, donate_argnums=planes, **jit_kw)


def make_engine(inst, store=None, conf=None, lanes=None, mesh=None):
    """Engine-selection seam: uniform fast path (with SIMT fallback) when
    Configure.batch.uniform is set, plain SIMT otherwise."""
    from wasmedge_tpu.common.configure import Configure

    conf = conf or Configure()
    if conf.batch.uniform:
        return UniformBatchEngine(inst, store=store, conf=conf, lanes=lanes,
                                  mesh=mesh)
    return BatchEngine(inst, store=store, conf=conf, lanes=lanes, mesh=mesh)


__all__ = ["BatchEngine", "BatchResult", "DeviceImage", "batchability",
           "UniformBatchEngine", "make_engine"]
