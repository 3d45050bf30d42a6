"""Example wasm workload corpus (the reference ships fibonacci/factorial wat
examples, /root/reference/tools/wasmedge/examples/). Built programmatically
via utils.builder since the image has no wat2wasm and copying reference
bytes is off-limits. These are the benchmark workloads from BASELINE.md:
fib (config 1), CoreMark 1.0 lowered from its C (`build_coremark`, config
2), a small CoreMark-flavoured integer/memory probe (`build_coremark_kernel`),
plus small modules exercising each subsystem.
"""

from wasmedge_tpu.models.programs import (
    build_call_counted_loop,
    build_coremark_kernel,
    build_counted_loop,
    build_echo,
    build_fac,
    build_fib,
    build_loop_sum,
    build_memfuse_workload,
    build_memory_workload,
    build_simd_kernel,
    build_simd_memfuse_workload,
)
# the guest of the benchmark's mem-batch-4096, which looks its builder
# up here by name (benchmark/drivers/batch.py); not part of the corpus
from wasmedge_tpu.models.programs import build_memory_batch  # noqa: F401
# likewise the guest of polybench-gemm-4096 (benchmark/drivers/batch_seeded.py)
from wasmedge_tpu.models.programs import build_polybench_gemm  # noqa: F401
# and of chacha20-simd-4096 (benchmark/drivers/batch_seeded_simd.py)
from wasmedge_tpu.models.programs import build_chacha20  # noqa: F401
# and of chacha20-wasi-4096 (benchmark/drivers/batch_wasi.py)
from wasmedge_tpu.models.programs import build_chacha20_wasi  # noqa: F401
# and CoreMark 1.0 itself, of coremark-2k-4096
# (benchmark/drivers/batch_seeded_indirect.py)
from wasmedge_tpu.models.programs import build_coremark  # noqa: F401

__all__ = [
    "build_fib",
    "build_fac",
    "build_loop_sum",
    "build_counted_loop",
    "build_call_counted_loop",
    "build_memory_workload",
    "build_memfuse_workload",
    "build_simd_memfuse_workload",
    "build_coremark_kernel",
    "build_echo",
    "build_simd_kernel",
]
