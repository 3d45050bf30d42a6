"""Benchmark/workload module builders."""

from __future__ import annotations

import os
import struct

from wasmedge_tpu.utils.builder import ModuleBuilder
from wasmedge_tpu.utils.wat import parse_wat


def build_fib() -> bytes:
    """Recursive fib(n) — BASELINE config 1: i32 numeric + call/br only."""
    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.const", 2), "i32.lt_s",
        ("if", "i32"),
        ("local.get", 0),
        "else",
        ("local.get", 0), ("i32.const", 1), "i32.sub", ("call", 0),
        ("local.get", 0), ("i32.const", 2), "i32.sub", ("call", 0),
        "i32.add",
        "end",
    ], export="fib")
    return b.build()


def build_fac() -> bytes:
    """Recursive factorial over i64 (reference example: fac(12))."""
    b = ModuleBuilder()
    b.add_function(["i64"], ["i64"], [], [
        ("local.get", 0), ("i64.const", 1), "i64.le_s",
        ("if", "i64"),
        ("i64.const", 1),
        "else",
        ("local.get", 0),
        ("local.get", 0), ("i64.const", 1), "i64.sub", ("call", 0),
        "i64.mul",
        "end",
    ], export="fac")
    return b.build()


def build_loop_sum() -> bytes:
    """sum(0..n) via a loop — pure-branch workload, no calls."""
    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], ["i32", "i32"], [
        ("block", None),
        ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        ("local.get", 2), ("local.get", 1), "i32.add", ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0),
        "end",
        "end",
        ("local.get", 2),
    ], export="loop_sum")
    return b.build()


def build_memory_workload(passes: int = 1, fold: str = "xor") -> bytes:
    """Write-then-checksum over linear memory (config 2 memory traffic).

    `passes` repeats the whole write+checksum cycle (same load/store mix,
    more work per invocation) so benchmarks can amortize fixed host-link
    round trips over enough device work to measure the engine rather
    than the link.  `fold` is how a loaded word joins the checksum:
    "xor", under which an even number of passes cancels to 0 whatever
    the memory holds, or "add", under which every pass's words count
    (same instruction count and block shapes)."""
    fold_op = {"xor": "i32.xor", "add": "i32.add"}[fold]
    b = ModuleBuilder()
    b.add_memory(1, 16)
    # locals: 0=n (param), 1=i, 2=acc, 3=pass counter
    b.add_function(["i32"], ["i32"], ["i32", "i32", "i32"], [
        ("i32.const", passes), ("local.set", 3),
        ("block", None),
        ("loop", None),
        # store n words of i*2654435761
        ("i32.const", 0), ("local.set", 1),
        ("block", None),
        ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        ("local.get", 1), ("i32.const", 4), "i32.mul",
        ("local.get", 1), ("i32.const", 0x9E3779B1 - 2**32), "i32.mul",
        ("local.get", 3), ("i32.const", 1), "i32.sub", "i32.xor",
        ("i32.store", 2, 0),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0),
        "end",
        "end",
        # fold them back into acc
        ("i32.const", 0), ("local.set", 1),
        ("block", None),
        ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        ("local.get", 2),
        ("local.get", 1), ("i32.const", 4), "i32.mul", ("i32.load", 2, 0),
        fold_op, ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0),
        "end",
        "end",
        ("local.get", 3), ("i32.const", 1), "i32.sub", ("local.tee", 3),
        "i32.eqz", ("br_if", 1),
        ("br", 0),
        "end",
        "end",
        ("local.get", 2),
    ], export="mem_checksum")
    return b.build()


def build_memory_batch() -> bytes:
    """The guest of the benchmark's mem-batch-4096: the memory sweep at
    64 passes, summed and not xored, so that the answer depends on every
    word each pass stored and read back (benchmark/drivers/batch.py
    takes a builder without arguments)."""
    return build_memory_workload(passes=64, fold="add")


def build_polybench_gemm(ni: int = 60, nj: int = 70, nk: int = 80) -> bytes:
    """PolyBench/C 4.2.1 `linear-algebra/blas/gemm`, DATA_TYPE double,
    alpha 1.5, beta 1.2, hand-lowered loop by loop (the defaults are
    SMALL_DATASET).  Export `gemm(seed: i32) -> i64`: `init_array`,
    `kernel_gemm` and, in place of `print_array`, a fold of every bit
    of C, in that order.  C, A and B are contiguous row-major f64
    arrays from address 0.

        C[i][j] = (double)((i*j+1+seed) % ni) / ni
        A[i][k] = (double)((i*(k+1)+seed) % nk) / nk
        B[k][j] = (double)((k*(j+2)+seed) % nj) / nj
        for i: for j: C[i][j] *= beta
               for k: for j: C[i][j] += alpha * A[i][k] * B[k][j]
        for i: for j: acc = rotl(acc, 1) ^ bits(C[i][j])

    The seed (unsigned, as the remainders are) changes data and never
    control flow.  Lowered as scalar -O2 code: one i32 pointer a stream
    bumped by 8, `alpha * A[i][k]` hoisted into an f64 local, constant
    bounds, bottom-tested loops; nothing unrolled, vectorised or
    reassociated."""
    c_base, a_base = 0, ni * nj * 8
    b_base = a_base + ni * nk * 8
    pages = -(-(b_base + nk * nj * 8) // 65536)
    SEED, I, J, K, PC, PA, PB, ROWC, AIK, ACC = range(10)

    def loop(var, bound, body):
        """do { body } while (++var != bound), var from 0."""
        return [("i32.const", 0), ("local.set", var), ("loop", None),
                *body,
                ("local.get", var), ("i32.const", 1), "i32.add",
                ("local.tee", var), ("i32.const", bound), "i32.ne",
                ("br_if", 0), "end"]

    def bump(ptr):
        return [("local.get", ptr), ("i32.const", 8), "i32.add",
                ("local.set", ptr)]

    def init(ptr, base, rows, cols, row, col, col_add, add, mod):
        """ptr[row][col] = (double)((row * (col + col_add) + add + seed)
        % mod) / mod over one array, the pointer running through it."""
        return [("i32.const", base), ("local.set", ptr)] + loop(
            row, rows, loop(col, cols, [
                ("local.get", ptr),
                ("local.get", row), ("local.get", col),
                *([("i32.const", col_add), "i32.add"] if col_add else []),
                "i32.mul",
                *([("i32.const", add), "i32.add"] if add else []),
                ("local.get", SEED), "i32.add",
                ("i32.const", mod), "i32.rem_u",
                "f64.convert_i32_u", ("f64.const", float(mod)), "f64.div",
                ("f64.store", 3, 0),
                *bump(ptr)]))

    body = [
        # init_array
        *init(PC, c_base, ni, nj, I, J, 0, 1, ni),
        *init(PA, a_base, ni, nk, I, K, 1, 0, nk),
        *init(PB, b_base, nk, nj, K, J, 2, 0, nj),
        # kernel_gemm
        ("i32.const", c_base), ("local.set", ROWC),
        ("i32.const", a_base), ("local.set", PA),
        *loop(I, ni, [
            ("local.get", ROWC), ("local.set", PC),
            *loop(J, nj, [
                ("local.get", PC),
                ("local.get", PC), ("f64.load", 3, 0),
                ("f64.const", 1.2), "f64.mul",
                ("f64.store", 3, 0),
                *bump(PC)]),
            ("i32.const", b_base), ("local.set", PB),
            *loop(K, nk, [
                ("f64.const", 1.5), ("local.get", PA), ("f64.load", 3, 0),
                "f64.mul", ("local.set", AIK),
                *bump(PA),
                ("local.get", ROWC), ("local.set", PC),
                *loop(J, nj, [
                    ("local.get", PC),
                    ("local.get", PC), ("f64.load", 3, 0),
                    ("local.get", AIK), ("local.get", PB),
                    ("f64.load", 3, 0), "f64.mul",
                    "f64.add",
                    ("f64.store", 3, 0),
                    *bump(PC), *bump(PB)])]),
            ("local.get", ROWC), ("i32.const", nj * 8), "i32.add",
            ("local.set", ROWC)]),
        # the fold that stands for print_array
        ("i32.const", c_base), ("local.set", PC),
        *loop(I, ni, loop(J, nj, [
            ("local.get", ACC), ("i64.const", 1), "i64.rotl",
            ("local.get", PC), ("i64.load", 3, 0), "i64.xor",
            ("local.set", ACC),
            *bump(PC)])),
        ("local.get", ACC),
    ]
    b = ModuleBuilder()
    b.add_memory(pages, pages)
    b.add_function(["i32"], ["i64"], ["i32"] * 7 + ["f64", "i64"], body,
                   export="gemm")
    return b.build()


# ChaCha20 (RFC 8439) as a 128-bit implementation holds it: the state's
# four rows in four v128, the diagonal round as lane rotations of rows
# b, c, d.  Addresses of the guest's page 0, a compiled module's layout:
# read-only data from 1024, the shadow stack's one frame under 65536.
CHACHA_SIGMA = 1024             # "expand 32-byte k", a data segment
CHACHA_CTX = 65536 - 64         # uint32_t input[16]: the context
CHACHA_MSG = 65536              # the message, pages 1..3 at 3072 blocks
CHACHA_IOV = CHACHA_CTX - 16    # the WASI guest's iovec and nwritten
# the seed's recurrence (`assumed` in the configuration): Numerical
# Recipes' w' = w * 1664525 + 1013904223 mod 2**32, from w = seed, gives
# the eight key words and the three nonce words in turn; the twelfth
# value, splatted, times CHACHA_MSG_MUL plus CHACHA_MSG_ADD is the
# message's first 16 bytes, and each next 16 the same step lane by lane
CHACHA_LCG = (1664525, 1013904223)
CHACHA_MSG_MUL = (0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F)
CHACHA_MSG_ADD = (1, 2, 3, 4)


def _chacha20_module(blocks: int, export: str,
                     chunk_blocks: int = 0) -> bytes:
    """The one source of the two ChaCha20 guests: `build_chacha20`'s
    module, or with `chunk_blocks` `build_chacha20_wasi`'s, which is the
    same but for the block loop cut into chunks, each handed to
    `fd_write` once it is encrypted."""
    def v(*lanes):
        return ("v128.const", b"".join(
            (x & 0xFFFFFFFF).to_bytes(4, "little") for x in lanes))

    def lanes_left(n):
        """The shuffle mask that moves 32-bit lane i + n to lane i."""
        return ("i8x16.shuffle", [4 * ((i + n) % 4) + k
                                  for i in range(4) for k in range(4)])

    end = CHACHA_MSG + 64 * blocks
    SEED, P, I, W, ACC = range(5)
    S = [5, 6, 7, 8]            # the input rows: constants, key, key,
    X = [9, 10, 11, 12]         # counter and nonce; the working rows
    T, M = 13, 14
    CE = 15                     # the chunk's end (the WASI guest only)
    mul, add = CHACHA_LCG

    def lcg():
        return [("local.get", W), ("i32.const", mul), "i32.mul",
                ("i32.const", add), "i32.add", ("local.tee", W)]

    def bump(ptr, by, bound):
        """... while ((ptr += by) != bound)"""
        return [("local.get", ptr), ("i32.const", by), "i32.add",
                ("local.tee", ptr), bound, "i32.ne", ("br_if", 0)]

    def step(a, b, d, n):
        """a += b; d ^= a; d <<<= n, on rows"""
        return [("local.get", a), ("local.get", b), "i32x4.add",
                ("local.set", a),
                ("local.get", d), ("local.get", a), "v128.xor",
                ("local.tee", T), ("i32.const", n), "i32x4.shl",
                ("local.get", T), ("i32.const", 32 - n), "i32x4.shr_u",
                "v128.or", ("local.set", d)]

    a, b, c, d = X
    quarter = step(a, b, d, 16) + step(c, d, b, 12) \
        + step(a, b, d, 8) + step(c, d, b, 7)

    def rotate(row, n):
        return [("local.get", row), ("local.get", row), lanes_left(n),
                ("local.set", row)]

    body = [
        # (a) the context: the constants out of .rodata, eight key
        # words, the counter, three nonce words
        ("i32.const", CHACHA_CTX), ("i32.const", CHACHA_SIGMA),
        ("v128.load", 4, 0), ("v128.store", 4, 0),
        ("local.get", SEED), ("local.set", W),
    ]
    for i in range(8):
        body += [("i32.const", CHACHA_CTX), *lcg(),
                 ("i32.store", 2, 16 + 4 * i)]
    body += [("i32.const", CHACHA_CTX), ("i32.const", 1),
             ("i32.store", 2, 48)]
    for i in range(3):
        body += [("i32.const", CHACHA_CTX), *lcg(),
                 ("i32.store", 2, 52 + 4 * i)]
    body += [
        # the message, a block a turn
        *lcg(), "i32x4.splat", v(*CHACHA_MSG_MUL), "i32x4.mul",
        v(*CHACHA_MSG_ADD), "i32x4.add", ("local.set", M),
        ("i32.const", CHACHA_MSG), ("local.set", P),
        ("loop", None),
    ]
    for k in range(4):
        body += [("local.get", P), ("local.get", M),
                 ("v128.store", 4, 16 * k),
                 ("local.get", M), v(*[mul] * 4), "i32x4.mul",
                 v(*[add] * 4), "i32x4.add", ("local.set", M)]
    body += [*bump(P, 64, ("i32.const", end)), "end"]
    # (b) encryption in place
    for k in range(4):
        body += [("i32.const", CHACHA_CTX), ("v128.load", 4, 16 * k),
                 ("local.set", S[k])]
    body += [("i32.const", CHACHA_MSG), ("local.set", P)]
    if chunk_blocks:
        # a chunk a turn of the outer loop: its end, then its blocks
        body += [("loop", None),
                 ("local.get", P), ("i32.const", 64 * chunk_blocks),
                 "i32.add", ("local.set", CE)]
    body += [("loop", None)]
    for k in range(4):
        body += [("local.get", S[k]), ("local.set", X[k])]
    body += [
        ("i32.const", 10), ("local.set", I),
        ("loop", None),
        *quarter,
        *rotate(b, 1), *rotate(c, 2), *rotate(d, 3),
        *quarter,
        *rotate(b, 3), *rotate(c, 2), *rotate(d, 1),
        ("local.get", I), ("i32.const", 1), "i32.sub", ("local.tee", I),
        ("br_if", 0), "end",
    ]
    for k in range(4):
        body += [("local.get", X[k]), ("local.get", S[k]), "i32x4.add",
                 ("local.set", X[k])]
    for k in range(4):
        body += [("local.get", P),
                 ("local.get", P), ("v128.load", 4, 16 * k),
                 ("local.get", X[k]), "v128.xor",
                 ("v128.store", 4, 16 * k)]
    body += [
        ("local.get", S[3]), v(1, 0, 0, 0), "i32x4.add",
        ("local.set", S[3]),
        *bump(P, 64, ("local.get", CE) if chunk_blocks
              else ("i32.const", end)), "end",
    ]
    if chunk_blocks:
        nbytes = 64 * chunk_blocks
        body += [
            # write(1, chunk, nbytes): one iovec in the frame, the call,
            # and abort() unless all of it was taken
            ("i32.const", CHACHA_IOV), ("local.get", P),
            ("i32.const", nbytes), "i32.sub", ("i32.store", 2, 0),
            ("i32.const", CHACHA_IOV), ("i32.const", nbytes),
            ("i32.store", 2, 4),
            ("i32.const", 1), ("i32.const", CHACHA_IOV), ("i32.const", 1),
            ("i32.const", CHACHA_IOV + 8), ("call", 0),
            ("if", None), "unreachable", "end",
            ("i32.const", CHACHA_IOV), ("i32.load", 2, 8),
            ("i32.const", nbytes), "i32.ne",
            ("if", None), "unreachable", "end",
            ("local.get", P), ("i32.const", end), "i32.ne", ("br_if", 0),
            "end",
        ]
    body += [
        # (c) the fold of every bit of the ciphertext
        ("i32.const", CHACHA_MSG), ("local.set", P),
        ("loop", None),
        ("local.get", ACC), ("i64.const", 1), "i64.rotl",
        ("local.get", P), ("i64.load", 3, 0), "i64.xor",
        ("local.set", ACC),
        *bump(P, 8, ("i32.const", end)), "end",
        ("local.get", ACC),
    ]
    mb = ModuleBuilder()
    if chunk_blocks:
        mb.import_func("wasi_snapshot_preview1", "fd_write",
                       ["i32"] * 4, ["i32"])
    pages = -(-end // 65536)
    mb.add_memory(pages, pages)
    mb.add_active_data(0, [("i32.const", CHACHA_SIGMA)],
                       b"expand 32-byte k")
    mb.add_function(["i32"], ["i64"],
                    ["i32"] * 3 + ["i64"] + ["v128"] * 10
                    + ["i32"] * bool(chunk_blocks), body, export=export)
    return mb.build()


def build_chacha20(blocks: int = 3072) -> bytes:
    """ChaCha20 encryption of `blocks` 64-byte blocks in place, RFC 8439
    sections 2.1, 2.3 and 2.4 (20 rounds, 256-bit key, 96-bit nonce,
    32-bit block counter from 1), v128 from end to end.  Export
    `chacha20(seed: i32) -> i64`:

        (a) key, nonce and message from the seed by i32 arithmetic (the
            recurrence above), the message 16 bytes a `v128.store`;
        (b) per block: the context's four rows into v128 locals, ten
            double rounds (a column round on the rows, rows b, c, d
            rotated by one, two, three lanes with `i8x16.shuffle`, the
            same round, the rotation back), the add of the input rows,
            four `v128.load` / `v128.xor` / `v128.store`, the counter
            bumped by `i32x4.add` of (1, 0, 0, 0);
        (c) acc = rotl(acc, 1) ^ i64.load(p) over the ciphertext.

    The seed changes data and never control flow.  Lowered as portable
    vector code (`uint32_t __attribute__((vector_size(16)))`) comes out
    of clang -O2 -msimd128 as far as can be said without a toolchain:
    rotations as shl / shr_u / or, the four stores of a block's rows
    unrolled, bottom-tested loops on a pointer or a down-counter."""
    return _chacha20_module(blocks, "chacha20")


def build_chacha20_wasi(blocks: int = 3072,
                        chunk_blocks: int = 128) -> bytes:
    """`build_chacha20` as a WASI command that writes its output: the
    same key, nonce, message, block function and fold (one source), and
    after every `chunk_blocks` blocks encrypted in place one
    `fd_write(1, iov, 1, nwp)` with one iovec over the ciphertext just
    produced (64 * chunk_blocks bytes: Rust's `std::io` moves
    `DEFAULT_BUF_SIZE` = 8 KiB a `write`, and wasm32-wasi's `write` is
    one `fd_write`), the iovec and `nwritten` in the frame under the
    context; `unreachable` unless the errno is 0 and all of it was
    taken.  Imports `wasi_snapshot_preview1.fd_write`, exports
    `chacha20_write(seed: i32) -> i64`, whose answer is `chacha20`'s
    for the same seed."""
    if chunk_blocks <= 0 or blocks % chunk_blocks:
        raise ValueError(f"blocks {blocks} is no multiple of "
                         f"chunk_blocks {chunk_blocks}")
    return _chacha20_module(blocks, "chacha20_write", chunk_blocks)


def build_counted_loop(n: int = 64) -> bytes:
    """Latch-tested counted loop with a CONSTANT limit — the canonical
    shape the absint trip analysis (analysis/absint.py) bounds
    EXACTLY: body runs `n` times, cost_bound == measured retired.
    Before r19 this verdict was "unbounded" (any loop was); the
    admission-precision fixture for `require_bounded` policies."""
    b = ModuleBuilder()
    # locals: 0=arg (ignored: limits must be static), 1=i, 2=acc
    b.add_function(["i32"], ["i32"], ["i32", "i32"], [
        ("block", None),
        ("loop", None),
        ("local.get", 2), ("local.get", 1), "i32.add", ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("local.get", 1), ("i32.const", n), "i32.lt_u", ("br_if", 0),
        "end", "end",
        ("local.get", 2),
    ], export="count")
    return b.build()


def build_call_counted_loop(n: int = 64, calls: int = 24) -> bytes:
    """A non-promotable driver calling a promotable counted-loop leaf
    `calls` times — the r20 tier-up cadence fixture.  The driver has
    CALL ops so the compiled-function verdict refuses it; the leaf is
    the build_counted_loop shape (constant latch, exact absint trip
    bound) so it promotes.  With the compiled tier on, each call
    retires through ONE compiled-body dispatch plus the driver's
    per-op glue — enough launches either way that supervised runs
    cross checkpoint boundaries mid-stream (tests/test_tierup.py).

    Result: arg + calls * (n*(n-1)/2)."""
    b = ModuleBuilder()
    # func 0 (driver): locals 0=arg, 1=j, 2=acc
    b.add_function(["i32"], ["i32"], ["i32", "i32"], [
        ("local.get", 0), ("local.set", 2),
        ("block", None),
        ("loop", None),
        ("local.get", 2), ("local.get", 1), ("call", 1), "i32.add",
        ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("local.get", 1), ("i32.const", calls), "i32.lt_u", ("br_if", 0),
        "end", "end",
        ("local.get", 2),
    ], export="call_count")
    # func 1 (leaf): the counted-loop body — locals 0=arg, 1=i, 2=acc
    b.add_function(["i32"], ["i32"], ["i32", "i32"], [
        ("block", None),
        ("loop", None),
        ("local.get", 2), ("local.get", 1), "i32.add", ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("local.get", 1), ("i32.const", n), "i32.lt_u", ("br_if", 0),
        "end", "end",
        ("local.get", 2),
    ])
    return b.build()


def build_memfuse_workload(n_words: int = 1024, passes: int = 1,
                           byte_offset: int = 0,
                           store_width: int = 4) -> bytes:
    """Write-then-xor-checksum with STATIC bounds — the r19 memory-run
    fusion workload.  Unlike build_memory_workload (whose limits are
    params, so nothing licenses), every loop here is counted against a
    constant, so absint proves each store/load in-bounds and aligned
    and batch/fuse.py compiles the whole loop bodies into fused
    gather/scatter runs.

    `byte_offset`/`store_width` build the ADVERSARIAL variants: a
    byte_offset of 2 with store_width 4 makes every access misaligned
    (license refused -> per-op path), and an n_words pushing
    n_words*4 + byte_offset past the 64 KiB page makes the tail access
    OOB (license refused; the trap must land identically on the
    per-op path whether fusion is on or off)."""
    b = ModuleBuilder()
    b.add_memory(1, 1)
    store_op = {1: "i32.store8", 2: "i32.store16", 4: "i32.store"}[
        store_width]
    # locals: 0=arg (ignored), 1=i, 2=acc, 3=pass counter
    b.add_function(["i32"], ["i32"], ["i32", "i32", "i32"], [
        ("i32.const", passes), ("local.set", 3),
        ("block", None), ("loop", None),
        # store n_words words of i*0x9E3779B1 ^ (pass-1)
        ("i32.const", 0), ("local.set", 1),
        ("block", None), ("loop", None),
        ("local.get", 1), ("i32.const", 4), "i32.mul",
        ("i32.const", byte_offset), "i32.add",
        ("local.get", 1), ("i32.const", 0x9E3779B1 - 2 ** 32),
        "i32.mul",
        ("local.get", 3), ("i32.const", 1), "i32.sub", "i32.xor",
        (store_op, 0, 0),
        ("local.get", 1), ("i32.const", 1), "i32.add",
        ("local.set", 1),
        ("local.get", 1), ("i32.const", n_words), "i32.lt_u",
        ("br_if", 0),
        "end", "end",
        # xor-reduce them back
        ("i32.const", 0), ("local.set", 1),
        ("block", None), ("loop", None),
        ("local.get", 2),
        ("local.get", 1), ("i32.const", 4), "i32.mul",
        ("i32.const", byte_offset), "i32.add",
        ("i32.load", 2, 0),
        "i32.xor", ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add",
        ("local.set", 1),
        ("local.get", 1), ("i32.const", n_words), "i32.lt_u",
        ("br_if", 0),
        "end", "end",
        # next pass (counted down to zero: `ne 0` trip shape)
        ("local.get", 3), ("i32.const", 1), "i32.sub",
        ("local.tee", 3), ("br_if", 0),
        "end", "end",
        ("local.get", 2),
    ], export="memfuse")
    return b.build()


def build_simd_memfuse_workload(n_vecs: int = 64,
                                passes: int = 1) -> bytes:
    """v128 analog of build_memfuse_workload: fill `n_vecs` 16-byte
    vectors with splatted counters, then xor-reduce a lane back out
    through v128 loads.  Every access sits at i*16 against CONSTANT
    loop bounds, so absint proves each v128 site in-bounds and
    word-aligned (16-byte stride => 4-aligned) and licenses it — the
    r20 satellite that lets batch/fuse.py compile the SIMD loop bodies
    into fused four-word gather/scatter runs.  The splat/extract cells
    stay per-op (not fusion-eligible), so each loop body realizes one
    fused run holding the licensed v128 access."""
    b = ModuleBuilder()
    b.add_memory(1, 1)
    # locals: 0=arg (ignored: limits must be static), 1=i, 2=acc, 3=pass
    b.add_function(["i32"], ["i32"], ["i32", "i32", "i32"], [
        ("i32.const", passes), ("local.set", 3),
        ("block", None), ("loop", None),
        # store n_vecs splatted vectors of i + pass
        ("i32.const", 0), ("local.set", 1),
        ("block", None), ("loop", None),
        ("local.get", 1), ("i32.const", 16), "i32.mul",
        ("local.get", 1), ("local.get", 3), "i32.add", "i32x4.splat",
        ("v128.store", 0, 0),
        ("local.get", 1), ("i32.const", 1), "i32.add",
        ("local.set", 1),
        ("local.get", 1), ("i32.const", n_vecs), "i32.lt_u",
        ("br_if", 0),
        "end", "end",
        # xor-reduce one lane of each back
        ("i32.const", 0), ("local.set", 1),
        ("block", None), ("loop", None),
        ("local.get", 2),
        ("local.get", 1), ("i32.const", 16), "i32.mul",
        ("v128.load", 0, 0),
        ("i32x4.extract_lane", 1),
        "i32.xor", ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add",
        ("local.set", 1),
        ("local.get", 1), ("i32.const", n_vecs), "i32.lt_u",
        ("br_if", 0),
        "end", "end",
        # next pass (counted down to zero: `ne 0` trip shape)
        ("local.get", 3), ("i32.const", 1), "i32.sub",
        ("local.tee", 3), ("br_if", 0),
        "end", "end",
        ("local.get", 2),
    ], export="simd_memfuse")
    return b.build()


def build_coremark(total_data_size: int = 2000, seed1: int = 0,
                   seed2: int = 0, seed3: int = 0x66) -> bytes:
    """EEMBC CoreMark 1.0, lowered by hand from its C one wasm function a
    C function (coremark.wat beside this file): export
    `coremark(iterations: i32) -> i64` runs what core_main.c's main runs
    between portable_init and the report (the seeds, the three inits on
    the MEM_STATIC block, `iterate`) and packs crcfinal | crclist << 16 |
    crcmatrix << 32 | crcstate << 48.  The seeds are the build's
    constants, the SEED_VOLATILE port's volatiles in a data segment; the
    defaults are the 2K performance run (known_id 3).  Switches of four
    cases or more are `br_table`, the sort's comparator a
    `call_indirect`, the list and the matrices 16-bit accesses, the state
    input bytes, and the frames of core_bench_list, core_list_init,
    core_bench_state and main live on a shadow stack under a mutable
    global.  One page: the block must fit below 64 KiB."""
    if not 0 < total_data_size <= 65536 - 1248:
        raise ValueError(f"TOTAL_DATA_SIZE {total_data_size} does not fit "
                         f"one page above the data")
    with open(os.path.join(os.path.dirname(__file__), "coremark.wat")) as f:
        src = f.read()
    volatiles = struct.pack("<5i", seed1, seed2, seed3, 0, 0)
    src = src.replace("@TOTAL_DATA_SIZE@", str(total_data_size)).replace(
        "@SEED_VOLATILES@", "".join(f"\\{b:02x}" for b in volatiles))
    return parse_wat(src)


def build_coremark_kernel() -> bytes:
    """CoreMark-flavored kernel: list-free core mix of matrix-multiply-ish
    integer MACs, state-machine branches, and CRC over linear memory.
    Not CoreMark (that is `build_coremark`): a small fixed op mix that
    the engines' tests use as a quick integer/memory/`br_table` probe."""
    b = ModuleBuilder()
    b.add_memory(1, 16)

    # crc16 step: crc = (crc >> 1) ^ (0xA001 if (crc^bit)&1 else 0)
    crc8 = b.add_function(["i32", "i32"], ["i32"], ["i32"], [
        # for 8 bits
        ("block", None),
        ("loop", None),
        ("local.get", 2), ("i32.const", 8), "i32.ge_u", ("br_if", 1),
        ("local.get", 1), ("local.get", 0), "i32.xor", ("i32.const", 1), "i32.and",
        ("if", None),
        ("local.get", 1), ("i32.const", 1), "i32.shr_u",
        ("i32.const", 0xA001), "i32.xor", ("local.set", 1),
        "else",
        ("local.get", 1), ("i32.const", 1), "i32.shr_u", ("local.set", 1),
        "end",
        ("local.get", 0), ("i32.const", 1), "i32.shr_u", ("local.set", 0),
        ("local.get", 2), ("i32.const", 1), "i32.add", ("local.set", 2),
        ("br", 0),
        "end",
        "end",
        ("local.get", 1),
    ])

    # matrix-ish MAC over memory words + state machine + crc
    b.add_function(["i32"], ["i32"], ["i32", "i32", "i32", "i32"], [
        # locals: 0=n 1=i 2=acc 3=state 4=crc
        ("i32.const", 0xFFFF), ("local.set", 4),
        ("block", None),
        ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        # acc += (i*3) * (i+7)  (MAC)
        ("local.get", 2),
        ("local.get", 1), ("i32.const", 3), "i32.mul",
        ("local.get", 1), ("i32.const", 7), "i32.add",
        "i32.mul", "i32.add", ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        # state-machine dispatch on acc low bits: all arms continue the loop
        ("local.get", 2), ("i32.const", 7), "i32.and",
        ("br_table", [0, 0, 0], 0),
        "end",
        "end",
        # store acc, crc it
        ("i32.const", 0), ("local.get", 2), ("i32.store", 2, 0),
        ("local.get", 2), ("i32.const", 0xFF), "i32.and",
        ("local.get", 4), ("call", crc8), ("local.set", 4),
        ("local.get", 4), ("local.get", 2), "i32.xor",
    ], export="coremark")
    return b.build()


def build_echo() -> bytes:
    """The echo guest of BASELINE.json configs[3]: echo(n) writes a
    16-byte message to stdout through WASI fd_write twice an iteration,
    n iterations (two host outcalls a turn of the loop), and returns
    the last errno."""
    b = ModuleBuilder()
    b.import_func("wasi_snapshot_preview1", "fd_write",
                  ["i32", "i32", "i32", "i32"], ["i32"])
    b.add_memory(1, 1)
    # iovec at 64 -> "hello wasi echo\n" at 128 (16 bytes)
    body = [
        ("i32.const", 64), ("i32.const", 128), ("i32.store", 2, 0),
        ("i32.const", 68), ("i32.const", 16), ("i32.store", 2, 0),
    ]
    msg = b"hello wasi echo\n"
    for i, ch in enumerate(msg):
        body += [("i32.const", 128 + i), ("i32.const", ch),
                 ("i32.store8", 0, 0)]
    body += [
        ("block", None), ("loop", None),
        ("local.get", 1), ("local.get", 0), "i32.ge_u", ("br_if", 1),
        # write the message
        ("i32.const", 1), ("i32.const", 64), ("i32.const", 1),
        ("i32.const", 32), ("call", 0), ("local.set", 2),
        # write again (second syscall per iteration)
        ("i32.const", 1), ("i32.const", 64), ("i32.const", 1),
        ("i32.const", 32), ("call", 0), ("local.set", 2),
        ("local.get", 1), ("i32.const", 1), "i32.add", ("local.set", 1),
        ("br", 0), "end", "end",
        ("local.get", 2),
    ]
    b.add_function(["i32"], ["i32"], ["i32", "i32"], body, export="echo")
    return b.build()


# The v128 guest of BASELINE.json configs[2]: i32x4 lane math, a
# shuffle and unaligned v128 memory traffic in a counted loop.
_SIMD_KERNEL_WAT = """
(module
  (memory 1)
  (func (export "vloop") (param i32) (result i32)
    (local $acc v128)
    (local $mul v128)
    (local $i i32)
    (local.set $acc (v128.const i32x4 1 2 3 4))
    (local.set $mul (v128.const i32x4 3 5 7 11))
    (block (loop
      (br_if 1 (i32.ge_u (local.get $i) (local.get 0)))
      (local.set $acc
        (i32x4.add
          (i32x4.mul (local.get $acc) (local.get $mul))
          (i32x4.splat (local.get $i))))
      (local.set $acc
        (v128.xor (local.get $acc)
                  (i8x16.shuffle 4 5 6 7 0 1 2 3 12 13 14 15 8 9 10 11
                                 (local.get $acc) (local.get $acc))))
      (local.set $i (i32.add (local.get $i) (i32.const 1)))
      (br 0)))
    (v128.store offset=5 (i32.const 32) (local.get $acc))
    (local.set $acc (v128.load offset=5 (i32.const 32)))
    (i32.add
      (i32x4.extract_lane 0 (local.get $acc))
      (i32.add (i32x4.extract_lane 1 (local.get $acc))
               (i32.add (i32x4.extract_lane 2 (local.get $acc))
                        (i32x4.extract_lane 3 (local.get $acc)))))))
"""


def build_simd_kernel() -> bytes:
    """vloop(n): n turns of the v128 loop above, then the lanes' sum."""
    return parse_wat(_SIMD_KERNEL_WAT)
