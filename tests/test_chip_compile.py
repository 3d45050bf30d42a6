"""Ask the chip's compiler about the main path's device programs, with
no chip: each test lowers one program at its real width and compiles it
for one described TPU v5e (jax.experimental.topologies).  Nothing runs,
so this says nothing about results or times — it catches what interpret
mode and the CPU backend cannot: scoped-VMEM and SMEM limits, slices
that miss the tiling, a lowering the installed JAX no longer accepts.

The topology is described inside a module-scoped fixture of THIS file
(never at import, never in conftest.py): only the xdist worker that is
handed the file loads the TPU's library.  Everything stays in this one
file and in the test's own process for the same reason.
"""

import os

import numpy as np
import pytest

from tests.helpers import instantiate

LANES = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip's sharding, with the program steered onto its
    chip branch for the life of the module: code that asks
    jax.default_backend() (donation, interpret mode, engine pick) sees
    "tpu", the kernel export cache (which lowers for the process's own
    backend and writes to disk) is bypassed, and the persistent compile
    cache is off — an entry compiled for a described chip cannot be read
    back without one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    mp.setattr(PallasUniformEngine, "_with_export_cache",
               lambda self, build: build())
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        mp.undo()
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def _on(sharding, tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _simd_wasm():
    from wasmedge_tpu.models import build_simd_kernel

    return build_simd_kernel()


def _fib_wasm():
    from wasmedge_tpu.models import build_fib

    return build_fib()


def _memory_wasm():
    from wasmedge_tpu.models import build_memory_workload

    return build_memory_workload(passes=64)


def _gemm_wasm():
    """The benchmark's polybench-gemm-4096 at SMALL: software binary64
    (f64.mul, f64.add, f64.div with its loop) inside fused blocks,
    8-byte loads and stores behind the HBM window."""
    from wasmedge_tpu.models.programs import build_polybench_gemm

    return build_polybench_gemm()


def _coremark_wasm():
    """The benchmark's coremark-2k-4096: EEMBC CoreMark 1.0's 2K
    performance run, 141 handlers (br_table, call_indirect, 8- and
    16-bit accesses, a shadow stack under a mutable global) behind the
    HBM window, its loads and stores each on a dispatch of its own
    (MAX_INLINE_ACCESSES), the br_table and call_indirect count in a
    17th ctrl column."""
    from wasmedge_tpu.models.programs import build_coremark

    return build_coremark()


def _chacha20_wasm():
    """The benchmark's chacha20-simd-4096 at 3,072 blocks: ten v128
    locals, blocks of 24 ops that hold v128 arithmetic and shuffles,
    16-byte loads and stores behind the HBM window over four pages a
    lane, the v128 instruction count in a 17th ctrl column."""
    from wasmedge_tpu.models.programs import build_chacha20

    return build_chacha20()


def _chacha20_wasi_wasm():
    """The benchmark's chacha20-wasi-4096: `_chacha20_wasm`'s guest with
    one `fd_write` a 128 blocks, so H_HOSTCALL, H_CALL, H_BRZ and H_TRAP
    among the handlers of a v128 kernel behind the HBM window."""
    from wasmedge_tpu.models.programs import build_chacha20_wasi

    return build_chacha20_wasi()


def _superblock_wasm():
    """A guest with a memory whose hot block is a superblock of every
    kind (PR 29): the guard's tail ends in a `call` (of a callee with a
    local to zero, so the call opens two regions), and the then-arm's
    `br end` is a jump that runs on into the `return`."""
    from wasmedge_tpu.utils.builder import ModuleBuilder

    b = ModuleBuilder()
    b.add_memory(1, 1)
    leaf = b.add_function(["i32"], ["i32"], ["i32"], [
        ("local.get", 0), ("i32.const", 1), "i32.add", ("local.tee", 1)])
    b.add_function(["i32"], ["i32"], ["i32"], [
        ("local.get", 0), ("i32.load", 2, 0), ("local.tee", 1),
        ("if", "i32"),
        ("local.get", 1), ("i32.load", 2, 0),
        "else",
        ("local.get", 1), ("call", leaf),
        "end",
    ], export="f")
    return b.build()


def _pallas_engine(wasm, depth, call_depth, mem_hbm=None, blk_cap=None):
    """The engine UniformBatchEngine picks for a TPU backend, built at
    4096 lanes (what VM.execute_batch holds; the stack depths are each
    guest's own in _KERNELS and _DEPTHS)."""
    from wasmedge_tpu.batch.uniform import UniformBatchEngine
    from wasmedge_tpu.common.configure import Configure

    conf = Configure()
    conf.batch.steps_per_launch = 50_000_000
    conf.batch.value_stack_depth = depth
    conf.batch.call_stack_depth = call_depth
    conf.batch.mem_hbm = mem_hbm
    from wasmedge_tpu.host.wasi import WasiModule

    # (a guest without imports takes nothing from the WASI module)
    _ex, store, inst = instantiate(wasm, conf, imports=[WasiModule()])
    eng = UniformBatchEngine(inst, store=store, conf=conf,
                             lanes=LANES).pallas
    assert eng is not None and eng.eligible
    assert eng._interpret() is False
    if blk_cap is not None:
        # the block scheduler's geometry for grouped arguments
        # (batch/scheduler.py _plan): same lanes, smaller lane blocks
        eng._blk_cap = blk_cap
    eng._build()
    return eng


def _kernel_event_names(hlo_text):
    """The names a device trace gives the program's Mosaic kernels:
    the left-hand sides of its tpu_custom_call instructions."""
    import re

    return re.findall(r"(%[\w.\-]+) = [^\n]*custom-call\([^\n]*"
                      r"tpu_custom_call", hlo_text)


def _hbm_planes(hlo_text):
    """The kernel's plane operands (from index 16) that the compiled
    program leaves in HBM: those whose layout names no memory space
    (`S(1)` is VMEM)."""
    import re

    (line,) = [x for x in hlo_text.splitlines()
               if "tpu_custom_call" in x and "custom-call(" in x]
    ops = re.search(r"custom-call\(([^)]*)\)", line).group(1)
    ops = [re.sub(r"/\*index=\d+\*/", "", o).strip()
           for o in ops.split(",")]
    shapes = dict(re.findall(r"^\s*(%[\w.\-]+) = (\S+) ", hlo_text,
                             re.MULTILINE))
    return [i for i, o in enumerate(ops)
            if i >= 16 and "S(" not in shapes[o]]


def _compile_kernel(eng, fn, one_chip, careful=False):
    compiled = fn.lower(*_on(one_chip, eng._arg_specs())).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's event in a profiler trace: the optimistic and the
    # careful kernel can be told apart by name
    assert _kernel_event_names(compiled.as_text()) == [
        "%wasm_kernel_careful.1" if careful
        else "%wasm_kernel_optimistic.1"]
    # the memory planes are donated and aliased in place, and the
    # kernel reads every other plane from a copy in VMEM (PR 42)
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    assert set(_hbm_planes(compiled.as_text())) <= {20, 27}
    return compiled


# (wasm, value stack, call stack, mem_hbm, lane-block cap,
#  careful kernel?) -> expected (lane block, mem_hbm mode)
_KERNELS = {
    "fib-optimistic": (_fib_wasm, 256, 256, None, None, False,
                       (4096, False)),
    "fib-careful": (_fib_wasm, 256, 256, None, None, True,
                    (4096, False)),
    "fib-grouped-blocks": (_fib_wasm, 256, 256, None, 256, False,
                           (256, False)),
    "memory-resident": (_memory_wasm, 128, 64, False, None, False,
                        (128, False)),
    "memory-hbm-window": (_memory_wasm, 128, 64, True, None, False,
                          (4096, True)),
    # the benchmark's mem-batch-4096 as it runs: mem_hbm left to the
    # auto rule, the window's DMA counters in the kernel
    "memory-auto": (_memory_wasm, 128, 64, None, None, False,
                    (4096, True)),
    "v128": (_simd_wasm, 64, 16, None, None, False, (4096, True)),
    # two pages a lane, the softfloat counter in the kernel (PR 34)
    "gemm-auto": (_gemm_wasm, 128, 64, None, None, False, (4096, True)),
    "gemm-auto-careful": (_gemm_wasm, 128, 64, None, None, True,
                          (4096, True)),
    # four pages a lane (1 GiB of plane), four stack planes, the v128
    # counter and the wider ctrl row in the kernel (PR 38)
    "chacha20-auto": (_chacha20_wasm, 64, 16, None, None, False,
                      (4096, True)),
    "chacha20-auto-careful": (_chacha20_wasm, 64, 16, None, None, True,
                              (4096, True)),
    # the same with a host call every 128 blocks (PR 40): the first
    # v128 kernel behind the window that parks
    "chacha20-wasi-auto": (_chacha20_wasi_wasm, 64, 16, None, None, False,
                           (4096, True)),
    "chacha20-wasi-auto-careful": (_chacha20_wasi_wasm, 64, 16, None, None,
                                   True, (4096, True)),
    # CoreMark: fifteen regions deep, which crashed the compiler
    # on libtpu's default fiber stack (`import wasmedge_tpu` gives it a
    # larger one), and 20.0 MB with its 109 loads and stores inline
    "coremark-auto": (_coremark_wasm, 128, 64, None, None, False,
                      (4096, True)),
    "coremark-auto-careful": (_coremark_wasm, 128, 64, None, None, True,
                              (4096, True)),
    # a superblock with a jump and a tail ending in `call`, behind the
    # HBM window: the guard for the eleven nested regions Mosaic's
    # layout inference survives (tails hold no memory op, so there is
    # no hbm-window tail to lower)
    "superblock-call-tail": (_superblock_wasm, 128, 64, None, None,
                             False, (4096, True)),
    "superblock-call-tail-careful": (_superblock_wasm, 128, 64, None,
                                     None, True, (4096, True)),
}


@pytest.mark.parametrize("case", sorted(_KERNELS))
def test_pallas_kernel_compiles_for_v5e(case, one_chip):
    wasm, depth, cdepth, mem_hbm, cap, careful, expect = _KERNELS[case]
    eng = _pallas_engine(wasm(), depth, cdepth, mem_hbm=mem_hbm,
                         blk_cap=cap)
    assert (eng._geom[3], eng._mem_mode()) == expect
    if case.startswith("superblock"):
        from wasmedge_tpu.batch.pallas_engine import H_CALL, H_RETURN

        (head,) = [s for s in eng._kargs[17] if ("jump", 1) in s]
        assert head[-1] == ("term", H_RETURN)
        (tail,) = [op[1] for op in head if op[0] == "guardz"]
        assert tail[-1] == ("term", H_CALL)
    fn = eng._fn_careful() if careful else eng._fn
    compiled = _compile_kernel(eng, fn, one_chip, careful=careful)
    _CODE_BYTES[case] = \
        compiled.memory_analysis().generated_code_size_in_bytes


_CODE_BYTES = {}    # kernel -> bytes of code, as each compile above left it
# What the chip's compiler makes of a kernel's size (PR 39, read from its
# log here and timed on the chip): it cuts a program into instruction
# overlays, the ChaCha20 kernel into 5 up to 107,369 bundles (6.90 MB of
# code) and into 10 to 18 from 109,161 (7.03 MB) on, and then the hot
# loop crosses overlays: a job took 0.529 s at 118,121 bundles and 6.37 s
# at 110,735 for 0.199 s at 105,577 and under.  The snapshot's copies are
# inlined at every windowed access, so a whole-plane DMA more there (a
# descriptor a row) is 7,000 bundles.  The four v128 kernels since PR 41
# (a shuffle whose mask moves whole 32-bit lanes is row moves in a fused
# block, six inlined vshuffle_dyn bodies a double round fewer; bundles
# and overlays from the compile log, bytes from here):
#   chacha20-auto               89,018 in 5   5,725,696 B  (93,609 before)
#   chacha20-wasi-auto          97,037 in 5   6,239,232 B  (101,630, 6.53 MB)
#   chacha20-wasi-auto-careful  68,752 in 5   4,428,288 B  (73,349)
#   v128                        41,866 in 5   2,707,456 B  (42,618)
# The WASI command's optimistic kernel (PR 40: the ChaCha20 kernel and
# 8,019 bundles for H_HOSTCALL, H_CALL, H_BRZ, H_TRAP, an `i32.load` and
# five block shapes with three windowed accesses) had a limit of its own
# at 6.66 MB while it stood 33 KB over this one; at 6.24 MB it is held
# to this one like the others, with 4 % of room under it and 10,300
# bundles under the cliff's last good reading.  Most of those bundles
# were not the kernel's: they were XLA's relayouts of the planes
# between [rows, L] and the kernel's [rows, L/Lpb, Lpb] around it.
# Since PR 42 the planes live in the kernel's layout and the program is
# the kernel alone (bytes from here, PR 42's parent in brackets):
#   chacha20-auto               1,406,464 B  (5,725,696)
#   chacha20-wasi-auto          1,638,912 B  (6,239,232)
#   chacha20-wasi-auto-careful  2,055,680 B  (4,428,288)
#   v128                          461,312 B  (2,707,456)
_CODE_BYTES_LIMIT = 6_500_000


@pytest.mark.parametrize("case", [
    "chacha20-auto", "v128", "chacha20-wasi-auto",
    "chacha20-wasi-auto-careful"])
def test_a_v128_kernel_stays_under_the_overlay_cliff(case, one_chip):
    if case not in _CODE_BYTES:     # run alone, or on another worker
        test_pallas_kernel_compiles_for_v5e(case, one_chip)
    assert 0 < _CODE_BYTES[case] < _CODE_BYTES_LIMIT


@pytest.mark.parametrize("case", ["coremark-auto", "coremark-auto-careful"])
def test_the_coremark_kernel_stays_under_the_overlay_cliff(case, one_chip):
    """No load or store inline (the image would fuse 109), so the program
    is some 2.2 MB (optimistic) where it was 20.0 MB.  Its 96 block
    shapes are those of its deepest loops (its heads want 177):
    optimistic 34,441 bundles in 4 overlays, 2,173,952 B; careful
    27,112 in 2, 1,702,400 B; 37,837 in 4, 2,391,552 B and 30,114 in 2,
    1,894,400 B with the 96 that came first in code order (bundles and
    overlays from the compile log of the kernel under a jit that adds
    one op, bytes from here)."""
    if case not in _CODE_BYTES:     # run alone, or on another worker
        test_pallas_kernel_compiles_for_v5e(case, one_chip)
    assert 0 < _CODE_BYTES[case] < _CODE_BYTES_LIMIT
    eng = _pallas_engine(_coremark_wasm(), 128, 64)
    shapes = eng._kargs[17]
    assert len(shapes) == 96 and not any(
        op[0] in ("loadi", "storei") for shape in shapes for op in shape)
    assert eng.counts_indirect and eng.ctrl_width == 17


def _inner(eqn):
    """The jaxprs an equation holds."""
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else [v]:
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _region_depth(jaxpr, depth=0):
    """The deepest nesting of cond/while/scan regions in a jaxpr: what
    Mosaic's infer-vector-layout recurses over on its small stack."""
    deepest = depth
    for eqn in jaxpr.eqns:
        inner = depth + (eqn.primitive.name in ("cond", "while", "scan"))
        for x in _inner(eqn):
            deepest = max(deepest, _region_depth(x, inner))
    return deepest


# kernel -> regions deep, (optimistic, careful).  PR 27 found the
# chip's compiler (infer-vector-layout's recursion on a 72 KB stack)
# surviving eleven and dying at the twelfth (PERF.md section 6); the
# parent of PR 29 read (9, 8) for fib and (11, 11) for the memory
# guest, and superblocks may deepen no kernel the benchmark's cells
# build.  The gemm kernel of PR 34 is twelve deep (fused blocks with
# three windowed accesses and softfloat between them), compiles here
# and ran on the chip: what the recursion's stack holds is not a count
# of levels alone, so twelve is pinned for that kernel and for PR 38's
# ChaCha20 kernel only, and their compiles above are the guard.
_DEPTHS = {
    "fib": (_fib_wasm, 256, 256, (9, 7)),
    "memory-auto": (_memory_wasm, 128, 64, (11, 10)),
    "superblock-call-tail": (_superblock_wasm, 128, 64, (10, 9)),
    "gemm-auto": (_gemm_wasm, 128, 64, (12, 10)),
    # PR 38: twelve too (16-byte accesses behind the window at the end
    # of blocks of v128 ops); its compile above is the guard, as gemm's
    "chacha20-auto": (_chacha20_wasm, 64, 16, (12, 11)),
}
_JAXPRS = {}


def _kernel_jaxprs(case):
    """(engine, the optimistic kernel's jaxpr, the careful kernel's) of
    a `_DEPTHS` guest at 4096 lanes, traced once a process."""
    import jax

    if case not in _JAXPRS:
        wasm, depth, cdepth, _expect = _DEPTHS[case]
        eng = _pallas_engine(wasm(), depth, cdepth)
        _JAXPRS[case] = (eng,) + tuple(
            jax.make_jaxpr(fn)(*eng._arg_specs())
            for fn in (eng._fn, eng._fn_careful()))
    return _JAXPRS[case]


@pytest.mark.parametrize("case", sorted(_DEPTHS))
def test_kernel_region_depth(case, one_chip):
    expect = _DEPTHS[case][3]
    got = tuple(_region_depth(j.jaxpr) for j in _kernel_jaxprs(case)[1:])
    assert got == expect and max(got) <= (
        12 if case in ("gemm-auto", "chacha20-auto") else 11)


# kernel -> sha256 of its jaxprs' text (optimistic, careful): what the
# benchmark's fib, memory and gemm cells run.  PR 38 put a counter and a
# wider ctrl row into the kernel of an image with v128 and into no
# other, and this holds it: the same jaxpr is the same Mosaic kernel.  A
# PR that changes one of these kernels on purpose pins its new text here
# (PR 30 to PR 37 compared the same hashes by hand).  PR 42 took the
# stripe remap's reshapes out of the launch (the planes live in the
# kernel's layout, `plane_shape`), so the launch's text is new; the
# kernel's own jaxpr, the `pallas_call`'s (`_KERNEL_SHA256`), is the
# text PR 37 left, read on PR 42's parent and on PR 42 alike.
_JAXPR_SHA256 = {
    "fib": ("669d4d194db5f70444dfbd1fcf1bfa3bddefede813b4533dd5bdb765df631830",
            "3e0786e146ffb8e5836f969ea31d3f97d98e9a14b198194582770e857157890f"),
    "memory-auto": (
        "6ddeb1cf47b1577a246982d297c4bda40397350c8dd23597c783f9585acad45a",
        "6b327de4d44e0f4fe01296d72d6610d5c8a895d3f841999c40bae57ebb8c7421"),
    "gemm-auto": (
        "6226e98499a5a9ba8c4a17fe145b2f939b100e6cf805bf508fbbb7dc78320763",
        "485e46a59c11c19ca7aeedc8f896d1897b6c40a8ac5a3e72e812141d630ec15c"),
}
_KERNEL_SHA256 = {
    "fib": ("c5a262908cfb87cd322ca16e9ada5c96d26f4828a451a09131ca49397857409e",
            "8989743cb32560cfd1a5d840f79f461397fbe515cd701fb004c7b1b43a70d4fa"),
    "memory-auto": (
        "d4a2dd7c9a437b4b67c710fc595987354dbd1083b7d5b5d0ea16e10c482ba7f5",
        "b7dd9ffa8ee8f8e808a66d65a8722779565adf87d81708e0cf81a13f25c933d7"),
    "gemm-auto": (
        "1bb104966d7d0a7f43121392e23a0a0dcde13bc962fb97fd0bb1523243f927b6",
        "f1366119c96030e1384396ee42e7338c57f6936536549831c68462e20b4124c8"),
}


def _kernel_body(jaxpr):
    """The jaxpr of the first `pallas_call` in `jaxpr`: the kernel's."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["jaxpr"]
        for x in _inner(eqn):
            found = _kernel_body(x)
            if found is not None:
                return found
    return None


@pytest.mark.parametrize("case", sorted(_JAXPR_SHA256))
def test_kernels_without_v128_are_the_parents(case, one_chip):
    import hashlib

    import jax

    eng, *jaxprs = _kernel_jaxprs(case)
    assert not eng.img.has_simd and eng.ctrl_width == 16
    assert tuple(hashlib.sha256(str(j).encode()).hexdigest()
                 for j in jaxprs) == _JAXPR_SHA256[case]
    assert tuple(hashlib.sha256(str(_kernel_body(j.jaxpr)).encode())
                 .hexdigest() for j in jaxprs) == _KERNEL_SHA256[case]
    # the ctrl row the kernel hands back is sixteen columns wide
    nblk = LANES // eng._geom[3]
    assert jax.eval_shape(eng._fn, *eng._arg_specs())[0].shape == (nblk, 16)


def test_a_v128_kernel_counts_in_a_seventeenth_column(one_chip):
    import jax

    eng = _kernel_jaxprs("chacha20-auto")[0]
    assert eng.img.has_simd and eng.ctrl_width == 17
    specs = eng._arg_specs()
    assert specs[len(eng._tables)].shape == (1, 17)
    for fn in (eng._fn, eng._fn_careful()):
        assert jax.eval_shape(fn, *specs)[0].shape == (1, 17)


def _holds(jaxpr, name):
    return any(e.primitive.name == name
               or any(_holds(x, name) for x in _inner(e))
               for e in jaxpr.eqns)


def _conds(jaxpr):
    return [e for e in jaxpr.eqns if e.primitive.name == "cond"]


def _block_leaf(eng, is_block):
    """(the jaxpr of the dispatch tree's leaf for the one block shape
    that `is_block` picks, scalars in the loop's carry): down from the
    kernel's loop along kernel_dispatch_plan, as dispatch() builds it
    (`hid < mid` is the cond's second branch)."""
    import jax

    from wasmedge_tpu.batch.pallas_engine import (
        H_BLOCK_BASE, kernel_dispatch_plan)

    def whiles(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "while":
                yield e
            for x in _inner(e):
                yield from whiles(x)

    loop = max(whiles(jax.make_jaxpr(eng._fn)(*eng._arg_specs()).jaxpr),
               key=lambda e: len(e.outvars))
    (shape,) = [i for i, s in enumerate(eng._kargs[17]) if is_block(s)]
    want = list(eng._kargs[0]).index(H_BLOCK_BASE + shape)
    node, _depths = kernel_dispatch_plan(eng._hid_weights, True)
    jaxpr = loop.params["body_jaxpr"].jaxpr
    while not isinstance(node, int):
        (cond,) = _conds(jaxpr)
        mid, left, right = node
        node = left if want < mid else right
        jaxpr = cond.params["branches"][int(want < mid)].jaxpr
    assert node == want
    return jaxpr, len(loop.outvars)


@pytest.mark.parametrize("op", ["loadi", "storei"])
def test_windowed_access_walks_one_cold_region_and_one_exit(op, one_chip):
    """The structure of a windowed access in a fused block of the
    memory guest (its store loop, its load loop): where the access
    stands, ONE region holds every DMA (the miss: canary, restore,
    write-backs, snapshot, fill), and its other branch, the hit, is a
    select; ONE further region yields the carry (the exit), and what
    follows the access is a branch of that one: it touches state one
    region below the access, where rollback and bail are two below."""
    eng = _pallas_engine(_memory_wasm(), 128, 64)
    leaf, ncarry = _block_leaf(
        eng, lambda s: sum(o[0] in ("loadi", "storei") for o in s) == 1
        and any(o[0] == op for o in s))
    # through the regions that yield the whole carry (the loop's exit
    # guard) to the level the access stands at
    while True:
        dma = [e for e in _conds(leaf) if any(
            _holds(x, "dma_start") for x in _inner(e))]
        if len(dma) == 1 and len(dma[0].outvars) == ncarry:
            (leaf,) = [x for x in _inner(dma[0])
                       if _holds(x, "dma_start")]
            continue
        break
    (miss,) = dma
    assert 0 < len(miss.outvars) < ncarry
    hit = min(_inner(miss), key=lambda x: len(x.eqns))
    assert len(hit.eqns) <= 1 and not _conds(hit)
    (leave,) = [e for e in _conds(leaf) if len(e.outvars) == ncarry]
    assert leave is leaf.eqns[-1]

    def writes_here(jaxpr):
        """A state write at this level, or under a pl.when of it."""
        return any(e.primitive.name == "swap"
                   or (e.primitive.name == "cond" and not e.outvars
                       and any(_holds(x, "swap") for x in _inner(e)))
                   for e in jaxpr.eqns)

    rare, go = sorted(_inner(leave), key=writes_here)
    assert writes_here(go) and not writes_here(rare)
    # the rare side: rolled back, or lane 0 out of bounds
    (which,) = _conds(rare)
    assert len(which.outvars) == ncarry
    assert not any(_conds(x) for x in _inner(which))


@pytest.mark.parametrize("case", ["fib", "memory-auto"])
def test_exported_kernel_compiles_for_v5e(case, one_chip):
    """The warm-start path (_with_export_cache): export for the TPU,
    serialize, deserialize, compile what came back in the launch the
    engine makes of it, which donates the memory plane and its shadow
    (PR 42): the launch aliases those two in place, as the in-process
    build does, where `jax.jit(back.call)` aliases none and XLA copies
    the memory plane around the kernel; the planes it does not donate
    it copies into VMEM for the kernel (`S(1)`), as it did when the
    stripe remap's relayouts made those copies."""
    import jax
    import jax.export as jexport

    from wasmedge_tpu.batch import jit_in_place
    from wasmedge_tpu.batch.pallas_engine import (
        _DONATED_PLANES,
        _PLANE_ARG0,
        donated_planes,
    )

    wasm, depth, cdepth, _expect = _DEPTHS[case]
    eng = _pallas_engine(wasm(), depth, cdepth)
    specs = eng._arg_specs()
    exp = jexport.export(eng._fn, platforms=["tpu"])(*specs)
    back = jexport.deserialize(bytearray(exp.serialize()))
    launch = jit_in_place(back.call, *_DONATED_PLANES)
    assert donated_planes(launch, specs) == eng.donated_planes == 2
    assert len(specs) - _PLANE_ARG0 == 12
    mem_bytes = sum(4 * int(np.prod(specs[i].shape)) for i in _DONATED_PLANES)
    aliased = []
    for fn in (jax.jit(back.call), launch, eng._fn):
        compiled = fn.lower(*_on(one_chip, specs)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        # the name survives the export: the benchmark's batch cell runs
        # this
        assert _kernel_event_names(compiled.as_text()) == [
            "%wasm_kernel_optimistic.1"]
        aliased.append(compiled.memory_analysis().alias_size_in_bytes)
        # the kernel reads every plane but the memory plane from VMEM
        assert set(_hbm_planes(compiled.as_text())) <= {_PLANE_ARG0 + 4}
    assert aliased[0] == 0 and aliased[1] == aliased[2] == mem_bytes


@pytest.fixture(scope="module")
def served(one_chip):
    """The gateway's serving generation for fib at 4096 lanes: what
    `wasmedge-tpu gateway fib.wasm --lanes 4096` builds."""
    from wasmedge_tpu.common.configure import Configure, HostRegistration
    from wasmedge_tpu.gateway import GatewayService

    conf = Configure()
    conf.host_registrations.add(HostRegistration.Wasi)
    svc = GatewayService(conf=conf, lanes=LANES)
    try:
        svc.preload([("main", _fib_wasm())])
        gen = svc.current
        rec = gen.server.recycler
        fidx = rec.func_idx("fib")
        yield gen.engine, rec, fidx, _on(one_chip, rec.idle_state(fidx))
    finally:
        svc.shutdown(drain=False)


def test_served_simt_chunk_compiles_for_v5e(served, one_chip):
    import jax

    engine, _rec, _fidx, state = served
    engine._build()
    tt = jax.ShapeDtypeStruct((2, 2), np.int32, sharding=one_chip)
    compiled = engine._run_chunk.lower(state, tt).compile()
    # the step's operations carry their scope into the chip's program
    assert "wasm_simt_step" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the whole carried state is donated: the chunk runs in place
    assert mem.alias_size_in_bytes >= mem.argument_size_in_bytes - 1024


@pytest.mark.parametrize("width", [1, 64, LANES])
def test_recycler_install_compiles_for_v5e(served, one_chip, width):
    import jax

    _engine, rec, fidx, state = served
    idx = jax.ShapeDtypeStruct((width,), np.int32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((1, width), np.int32, sharding=one_chip)
    rec._install_fn(fidx, 1).lower(state, idx, rows, rows).compile()


def test_shard_drive_chunk_compiles_for_four_v5e(topo, one_chip):
    """The --devices 4 path (parallel/shard_drive.py): ONE program over
    a lane mesh of the four described chips, every lane plane sharded."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from wasmedge_tpu.batch.engine import BatchEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.parallel.mesh import lane_mesh, state_shardings

    conf = Configure()
    _ex, store, inst = instantiate(_fib_wasm(), conf)
    mesh = lane_mesh(devices=list(topo.devices))
    eng = BatchEngine(inst, store=store, conf=conf, lanes=LANES, mesh=mesh)
    eng._build()
    state = eng.initial_state(0, [])
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, state_shardings(mesh, state))
    tt = jax.ShapeDtypeStruct(
        (2, 2), np.int32, sharding=NamedSharding(mesh, PartitionSpec()))
    compiled = eng._run_chunk.lower(state, tt).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= mem.argument_size_in_bytes - 1024


# guest -> (wasm, value stack, call stack): the planes one 4096-lane
# block holds, which the block scheduler's two surgery programs read
# and write (batch/scheduler.py _surgery_fns; PR 33)
_SURGERY = {
    "fib": (_fib_wasm, 256, 256),
    "memory-auto": (_memory_wasm, 128, 64),
    "v128": (_simd_wasm, 64, 16),
}


@pytest.mark.parametrize("case", sorted(_SURGERY))
def test_block_surgery_compiles_for_v5e(case, one_chip):
    """A child of 280 lanes (512 columns wide) gathered out of the
    planes and set into the block's columns: both programs compile, and
    the install writes the donated planes in place."""
    import jax
    import jax.numpy as jnp

    from wasmedge_tpu.batch.scheduler import (
        _PLANE_IDX, _PLANE_IDX_SIMD, _pad_width, _surgery_fns)

    wasm, depth, cdepth = _SURGERY[case]
    eng = _pallas_engine(wasm(), depth, cdepth)
    lblk = eng._geom[3]
    state = eng._arg_specs()[len(eng._tables):]   # ctrl, frames, planes
    idx = _PLANE_IDX_SIMD if eng.img.has_simd else _PLANE_IDX
    planes = tuple(state[i] for i in idx.values())
    width = _pad_width(280, lblk)
    assert (lblk, width) == (LANES, 512)
    extract, install = _surgery_fns()
    col_idx = jax.ShapeDtypeStruct((width,), jnp.int32)
    cols = jax.eval_shape(extract, planes, col_idx)
    assert [c.shape for c in cols] == [(p.shape[0], width) for p in planes]
    extract.lower(*_on(one_chip, (planes, col_idx))).compile()
    compiled = install.lower(*_on(one_chip, (
        planes, cols, jax.ShapeDtypeStruct((lblk,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)))).compile()
    mem = compiled.memory_analysis()
    plane_bytes = [4 * int(np.prod(p.shape)) for p in planes]
    assert mem.alias_size_in_bytes == sum(plane_bytes)
    # at most the widened child of the largest plane beside them
    assert mem.temp_size_in_bytes <= max(plane_bytes) + (1 << 20)


# cell -> (wasm, value stack, call stack, blocks, lane block): the
# geometry of the block scheduler's inner engine in each benchmark
# cell, whose pass record one compiled program packs behind the kernel
# (batch/pallas_engine.py _pass_record_fn; PR 37)
_RECORDS = {
    "fib-1x4096": (_fib_wasm, 256, 256, 1, LANES),
    "fib-11x512": (_fib_wasm, 256, 256, 11, 512),
    "memory-1x4096": (_memory_wasm, 128, 64, 1, LANES),
    "gemm-1x4096": (_gemm_wasm, 128, 64, 1, LANES),
    "chacha20-1x4096": (_chacha20_wasm, 64, 16, 1, LANES),
}


@pytest.mark.parametrize("case", sorted(_RECORDS))
def test_pass_record_compiles_for_v5e(case, one_chip):
    """ctrl, frames, the trap row and one result row of each stack laid
    end to end: the pack compiles for the chip at each cell's geometry,
    and its output is a buffer of its own that aliases no plane."""
    import jax
    import jax.numpy as jnp

    from wasmedge_tpu.batch.pallas_engine import _pass_record_fn

    wasm, depth, cdepth, nblk, lblk = _RECORDS[case]
    eng = _pallas_engine(wasm(), depth, cdepth,
                         blk_cap=None if lblk == LANES else lblk)
    D, CD, _W, Lblk = eng._geom
    assert (D, CD, Lblk) == (depth, cdepth, lblk)
    lanes = nblk * lblk

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    ctrl_w = eng.ctrl_width     # 16; 17 for an image with v128
    assert ctrl_w == (17 if case.startswith("chacha20") else 16)
    # the planes in the kernel's layout (`plane_shape`): striped at
    # one block of 4096 lanes, as they are at 512
    specs = (i32(nblk, ctrl_w), i32(nblk, 3, CD), i32(*eng._plane(1, lanes)),
             i32(*eng._plane(D, lanes)), i32(*eng._plane(D, lanes)))
    if lanes == LANES:
        # what the kernel hands on: ctrl, frames, stacks, the trap plane
        state = eng._arg_specs()[len(eng._tables):]
        assert [s.shape for s in specs] == \
            [state[i].shape for i in (0, 1, 7, 2, 3)]
    pack = _pass_record_fn()
    words = nblk * (ctrl_w + 3 * CD) + 3 * lanes
    assert jax.eval_shape(pack, *specs, 1).shape == (words,)
    compiled = pack.lower(*_on(one_chip, specs), 1).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert 4 * words <= mem.output_size_in_bytes <= 4 * words + 4096
    assert mem.temp_size_in_bytes <= 1 << 20
