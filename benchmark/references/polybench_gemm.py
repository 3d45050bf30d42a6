"""The plain reference of the PolyBench/C 4.2.1 gemm guest
(`build_polybench_gemm`, export `gemm`): the source's loops in numpy
float64, independent of every engine.

    init_array:  C[i][j] = (double)((i*j+1+seed) % ni) / ni
                 A[i][k] = (double)((i*(k+1)+seed) % nk) / nk
                 B[k][j] = (double)((k*(j+2)+seed) % nj) / nj
    kernel_gemm: for i < NI { for j < NJ  C[i][j] *= beta;
                              for k < NK  for j < NJ
                                  C[i][j] += alpha * A[i][k] * B[k][j]; }
    the fold:    acc = rotl(acc, 1) ^ bits(C[i][j]), row-major, 64 bits

Departures from the source text, each the configuration's (`assumed`):
- the lane's seed is added to the three integer numerators before the
  remainder (unsigned: seeds stay below 2**20 and the sums below 2**31),
  so that lanes hold different data; seed 0 is the source's own arrays;
- the fold of every bit of C stands where `print_array` prints C;
- vectorised over lanes (they share nothing) and over j within a row:
  elementwise, each product and each sum rounded once to binary64 and
  in the source's order, `(alpha * A[i][k]) * B[k][j]` and then the
  add, so the arithmetic is the scalar loop's, operation for operation.
  numpy contracts nothing into a fused multiply-add.  Nothing is
  reordered over k, the one axis along which rounding accumulates.

Results are the raw 64-bit cells a wasm i64 result occupies.
`reference(func, args)` answers one lane at SMALL, `reference_lanes`
all lanes in one call at the sizes it is given.
"""

import numpy as np

NI, NJ, NK = 60, 70, 80         # SMALL_DATASET
ALPHA, BETA = 1.5, 1.2


def gemm_c(seeds, ni=NI, nj=NJ, nk=NK):
    """C after init_array and kernel_gemm: float64[len(seeds), ni, nj]."""
    seeds = np.asarray(seeds, np.int64).reshape(-1, 1, 1)

    def init(rows, cols, col_add, add, mod):
        r = np.arange(rows, dtype=np.int64).reshape(1, -1, 1)
        c = np.arange(cols, dtype=np.int64).reshape(1, 1, -1)
        return ((r * (c + col_add) + add + seeds) % mod) \
            .astype(np.float64) / np.float64(mod)

    C = init(ni, nj, 0, 1, ni)
    A = init(ni, nk, 1, 0, nk)
    B = init(nk, nj, 2, 0, nj)
    for i in range(ni):
        C[:, i, :] *= np.float64(BETA)
        for k in range(nk):
            aik = np.float64(ALPHA) * A[:, i, k]
            C[:, i, :] += aik[:, None] * B[:, k, :]
    return C


def fold(C):
    """acc = rotl(acc, 1) ^ bits(C[i][j]) over each lane's C, row-major:
    uint64[lanes]."""
    acc = np.zeros(C.shape[0], np.uint64)
    one, back = np.uint64(1), np.uint64(63)
    for bits in np.ascontiguousarray(C).reshape(C.shape[0], -1) \
            .view(np.uint64).T:
        acc = ((acc << one) | (acc >> back)) ^ bits
    return acc


def reference_lanes(func, lane_args, ni=NI, nj=NJ, nk=NK):
    """Every lane's raw result cell in one call: uint64[lanes]."""
    if func != "gemm":
        raise KeyError(func)
    return fold(gemm_c(lane_args, ni, nj, nk))


def reference(func, args):
    return [int(reference_lanes(func, [int(args[0])])[0])]
