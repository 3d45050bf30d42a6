"""v128 on the batch (SIMT) engine: lane-parallel parity vs the scalar
oracle; and `i8x16.shuffle` in the Pallas kernel's fused blocks, whose
mask is read at build time where it moves whole 32-bit lanes.

BASELINE config 3's requirement ("v128 lane ops in the *batched* numeric
path").  The op bodies are GENERATED from batch/simdops.py's supported-op
tables, so any op added to the batch subset is automatically parity-
checked here; each module chains every op of a family and folds the
results into one i64 accumulator, so one compile covers the family.
Those sweeps are minutes-scale and carry `pytest.mark.slow` one by one;
the Pallas shuffle tests at the end are seconds each and run in tier 1."""

import numpy as np
import pytest

from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.batch.simdops import (
    V1_NAMES,
    V2_NAMES,
    VSHIFT_NAMES,
    VSPLAT_NAMES,
    VTEST_NAMES,
)
from wasmedge_tpu.utils.builder import ModuleBuilder
from tests.helpers import instantiate

LANES = 8


def fold(acc_local, av128_expr):
    """acc ^= e0 ^ (e1 * 3) of the v128 in local `av128_expr` position."""
    return av128_expr + [
        ("local.tee", 3),
        ("i64x2.extract_lane", 0),
        ("local.get", acc_local), "i64.xor",
        ("local.get", 3), ("i64x2.extract_lane", 1),
        ("i64.const", 3), "i64.mul", "i64.xor",
        ("local.set", acc_local),
    ]


def build_sweep(op_bodies):
    """f(x: i64, y: i64) -> i64 chaining per-op bodies over v128 locals.

    locals: 2=a(v128 built from x), 3=scratch v128, 4=acc(i64),
            5=b(v128 built from y)"""
    b = ModuleBuilder()
    body = [
        ("local.get", 0), "i64x2.splat",
        ("local.get", 0), ("i64.const", 0x9E3779B97F4A7C15 - 2**64),
        "i64.mul", ("i64x2.replace_lane", 1),
        ("local.set", 2),
        ("local.get", 1), "i64x2.splat",
        ("local.get", 1), ("i64.const", 0xC2B2AE3D27D4EB4F - 2**64),
        "i64.xor", ("i64x2.replace_lane", 1),
        ("local.set", 5),
    ]
    for op_body in op_bodies:
        body += fold(4, op_body)
    body += [("local.get", 4)]
    b.add_function(["i64", "i64"], ["i64"], ["v128", "v128", "i64", "v128"],
                   body, export="f")
    return b.build()


def check_parity(data, args_list):
    from wasmedge_tpu.batch import BatchEngine

    conf = Configure()
    conf.batch.steps_per_launch = 50_000
    ex, store, inst = instantiate(data, conf)
    eng = BatchEngine(inst, store=store, conf=conf, lanes=LANES)
    assert eng.img.has_simd
    args = [np.asarray(a, np.int64) for a in args_list]
    res = eng.run("f", args, max_steps=500_000)
    for lane in range(LANES):
        s_ex, s_store, s_inst = instantiate(data, Configure())
        expect = s_ex.invoke(s_store, s_inst.find_func("f"),
                             [int(a[lane]) for a in args])
        assert res.trap[lane] == -1, f"lane {lane} trapped {res.trap[lane]}"
        got = int(res.results[0][lane]) & (2**64 - 1)
        want = int(expect[0]) & (2**64 - 1)
        assert got == want, f"lane {lane}: {got:#x} != {want:#x}"
    return res


def rand_args(seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(-2**63, 2**63 - 1, LANES, np.int64),
            rng.integers(-2**63, 2**63 - 1, LANES, np.int64)]


# f32 arithmetic on the batch path inherits the scalar batch ALU's one
# documented divergence: XLA flushes f32 subnormals (the spec corpus
# likewise skips 'subnormal' files for the batched run).  Random 64-bit
# patterns hit that, so these ops are parity-checked with normal-range
# float inputs in test_float_family_parity instead.
_F32_FTZ_SENSITIVE = {"f32x4.add", "f32x4.sub", "f32x4.mul", "f32x4.div",
                      "f32x4.sqrt", "f32x4.demote_f64x2_zero"}


# The family sweeps are CHUNKED: one module per ~20 ops.  A single
# module chaining all ~230 ops makes one enormous XLA step function
# (the f64 softfloat subgraphs alone are huge) whose compile dominates
# the suite; smaller modules compile in seconds each.
_CHUNK = 20


def _chunks(names):
    names = [n for n in names if n not in _F32_FTZ_SENSITIVE]
    return [names[i:i + _CHUNK] for i in range(0, len(names), _CHUNK)]


@pytest.mark.slow
@pytest.mark.parametrize("ops", _chunks(V2_NAMES),
                         ids=lambda c: c[0].replace(".", "_"))
def test_v2_family_parity(ops):
    bodies = [[("local.get", 2), ("local.get", 5), op] for op in ops]
    check_parity(build_sweep(bodies), rand_args(1))


@pytest.mark.slow
@pytest.mark.parametrize("ops", _chunks(V1_NAMES),
                         ids=lambda c: c[0].replace(".", "_"))
def test_v1_family_parity(ops):
    bodies = [[("local.get", 2), op] for op in ops]
    check_parity(build_sweep(bodies), rand_args(2))


@pytest.mark.slow
def test_vtest_family_parity():
    # vtest produce i32: wrap into a splat so fold() sees a v128
    bodies = [[("local.get", 2), op, "i32x4.splat"] for op in VTEST_NAMES]
    bodies += [[("local.get", 5), op, "i32x4.splat"] for op in VTEST_NAMES]
    check_parity(build_sweep(bodies), rand_args(2))


def _float_args(seed, f64=False):
    """i64 lane args packing normal-range floats (exponents near 1.0):
    no subnormal inputs and no subnormal-producing products/sums."""
    rng = np.random.default_rng(seed)
    if f64:
        vals = rng.uniform(-8.0, 8.0, LANES)
        vals[vals == 0] = 1.5
        return [np.asarray([np.float64(v).view(np.int64) for v in vals],
                           np.int64)]
    lo = np.asarray([np.float32(v).view(np.int32) for v in
                     rng.uniform(-8.0, 8.0, LANES)], np.int64) & 0xFFFFFFFF
    hi = np.asarray([np.float32(v).view(np.int32) for v in
                     rng.uniform(0.1, 4.0, LANES)], np.int64) & 0xFFFFFFFF
    return [lo | (hi << 32)]


def build_float_sweep(op_bodies):
    """Like build_sweep but v128 locals are built WITHOUT bit scrambling
    (splat keeps the packed normal floats intact)."""
    b = ModuleBuilder()
    body = [
        ("local.get", 0), "i64x2.splat", ("local.set", 2),
        ("local.get", 1), "i64x2.splat", ("local.set", 5),
    ]
    for op_body in op_bodies:
        body += fold(4, op_body)
    body += [("local.get", 4)]
    b.add_function(["i64", "i64"], ["i64"], ["v128", "v128", "i64", "v128"],
                   body, export="f")
    return b.build()


@pytest.mark.slow
def test_float_f32_family_parity():
    """Every f32x4 op (incl. the FTZ-sensitive arithmetic) with
    normal-range inputs, bit-exact against the scalar oracle."""
    f32_v2 = [n for n in V2_NAMES if n.startswith("f32x4.")]
    f32_v1 = [n for n in V1_NAMES if n.startswith("f32x4.")
              and "convert" not in n and "demote" not in n]
    bodies = [[("local.get", 2), ("local.get", 5), op]
              for op in f32_v2]
    bodies += [[("local.get", 2), op] for op in f32_v1]
    bodies += [[("local.get", 2), "f64x2.promote_low_f32x4",
                "f32x4.demote_f64x2_zero"]]
    a32 = _float_args(11)[0]
    b32 = _float_args(12)[0]
    check_parity(build_float_sweep(bodies), [a32, b32])


@pytest.mark.slow
@pytest.mark.parametrize("half", [0, 1])
def test_float_f64_family_parity(half):
    f64_v2 = [n for n in V2_NAMES if n.startswith("f64x2.")]
    f64_v1 = [n for n in V1_NAMES if n.startswith("f64x2.")
              and "convert" not in n and "promote" not in n]
    ops = (f64_v2 + f64_v1)
    ops = ops[:len(ops) // 2] if half == 0 else ops[len(ops) // 2:]
    bodies = []
    for op in ops:
        if op in {n for n in V2_NAMES}:
            bodies.append([("local.get", 2), ("local.get", 5), op])
        else:
            bodies.append([("local.get", 2), op])
    a64 = _float_args(13, f64=True)[0]
    b64 = _float_args(14, f64=True)[0]
    check_parity(build_float_sweep(bodies), [a64, b64])


@pytest.mark.slow
def test_shift_and_splat_family_parity():
    bodies = []
    for i, op in enumerate(VSHIFT_NAMES):
        bodies.append([("local.get", 2),
                       ("local.get", 1), "i32.wrap_i64",
                       ("i32.const", i), "i32.add", op])
    for op in VSPLAT_NAMES:
        if op.startswith("i64x2"):
            bodies.append([("local.get", 0), op])
        elif op.startswith("f64x2"):
            bodies.append([("local.get", 0), "f64.reinterpret_i64", op])
        elif op.startswith("f32x4"):
            bodies.append([("local.get", 0), "i32.wrap_i64",
                           "f32.reinterpret_i32", op])
        else:
            bodies.append([("local.get", 0), "i32.wrap_i64", op])
    check_parity(build_sweep(bodies), rand_args(3))


@pytest.mark.slow
def test_lane_ops_shuffle_swizzle_bitselect_parity():
    k1 = int.from_bytes(bytes(range(16)), "little")
    shuf = [0, 17, 2, 19, 4, 21, 6, 23, 8, 25, 10, 27, 12, 29, 14, 31]
    bodies = [
        # extract/replace at several lanes and widths
        [("local.get", 2),
         ("local.get", 2), ("i8x16.extract_lane_s", 3), ("i32.const", 1),
         "i32.add", ("i8x16.replace_lane", 9)],
        [("local.get", 2),
         ("local.get", 5), ("i8x16.extract_lane_u", 15),
         ("i16x8.replace_lane", 2)],
        [("local.get", 2),
         ("local.get", 5), ("i16x8.extract_lane_s", 5), ("i32.const", 7),
         "i32.mul", ("i32x4.replace_lane", 1)],
        [("local.get", 2),
         ("local.get", 5), ("i16x8.extract_lane_u", 7),
         ("i32x4.replace_lane", 3)],
        [("local.get", 2),
         ("local.get", 5), ("i32x4.extract_lane", 2),
         ("i8x16.replace_lane", 0)],
        # bitselect and constant masks
        [("local.get", 2), ("local.get", 5), ("v128.const", k1),
         "v128.bitselect"],
        # static shuffle interleaving both operands, then swizzle
        [("local.get", 2), ("local.get", 5), ("i8x16.shuffle", shuf)],
        [("local.get", 2), ("local.get", 5), "i8x16.swizzle"],
        [("v128.const", k1)],
    ]
    check_parity(build_sweep(bodies), rand_args(4))


@pytest.mark.slow
def test_v128_memory_roundtrip_parity():
    b = ModuleBuilder()
    b.add_memory(1, 1)
    body = [
        # build a vector from both params, store at unaligned + aligned
        ("local.get", 0), "i64x2.splat",
        ("local.get", 1), ("i64x2.replace_lane", 1), ("local.set", 2),
        ("i32.const", 16), ("local.get", 2), ("v128.store", 0, 0),
        ("i32.const", 37), ("local.get", 2), ("v128.store", 0, 0),
        # reload both, xor, fold to i64
        ("i32.const", 16), ("v128.load", 0, 0),
        ("i32.const", 37), ("v128.load", 0, 0),
        "v128.xor",
        ("i32.const", 33), ("v128.load", 0, 0),
        "v128.and",
        ("local.tee", 3),
        ("i64x2.extract_lane", 0),
        ("local.get", 3), ("i64x2.extract_lane", 1),
        "i64.xor",
    ]
    b.add_function(["i64", "i64"], ["i64"], ["v128", "v128"], body,
                   export="f")
    check_parity(b.build(), rand_args(5))


@pytest.mark.slow
def test_v128_oob_load_traps():
    b = ModuleBuilder()
    b.add_memory(1, 1)
    body = [
        ("local.get", 0), "i32.wrap_i64", ("v128.load", 0, 0),
        ("i64x2.extract_lane", 0),
    ]
    b.add_function(["i64", "i64"], ["i64"], [], body, export="f")
    from wasmedge_tpu.batch import BatchEngine
    from wasmedge_tpu.common.errors import ErrCode

    conf = Configure()
    conf.batch.steps_per_launch = 10_000
    ex, store, inst = instantiate(b.build(), conf)
    eng = BatchEngine(inst, store=store, conf=conf, lanes=LANES)
    addrs = np.asarray([0, 65521, 65528, 8, 65535, 16, 70000, 60000],
                       np.int64)
    res = eng.run("f", [addrs, np.zeros(LANES, np.int64)],
                  max_steps=100_000)
    oob = (addrs + 16 > 65536)
    assert (res.trap[oob] == int(ErrCode.MemoryOutOfBounds)).all()
    assert (res.trap[~oob] == -1).all()


@pytest.mark.slow
def test_simd_module_falls_off_pallas_to_simt():
    from wasmedge_tpu.batch.uniform import UniformBatchEngine

    b = ModuleBuilder()
    body = [("local.get", 0), "i32.wrap_i64", "i32x4.splat",
            ("i32x4.extract_lane", 2), "i64.extend_i32_s"]
    b.add_function(["i64", "i64"], ["i64"], [], body, export="f")
    conf = Configure()
    conf.batch.interpret = True
    conf.batch.steps_per_launch = 10_000
    ex, store, inst = instantiate(b.build(), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    xs = np.arange(LANES, dtype=np.int64) - 3
    res = eng.run("f", [xs, xs], max_steps=10_000)
    assert (res.trap == -1).all()
    assert (np.asarray(res.results[0]) ==
            np.asarray([int(np.int32(x)) for x in xs])).all()


# -- i8x16.shuffle in the Pallas kernel's fused blocks ----------------------

def _lanes_left(n):
    """programs.py's mask: 32-bit lane i + n of one operand to lane i."""
    return [4 * ((i + n) % 4) + k for i in range(4) for k in range(4)]


def _words(*sel):
    return [4 * s + k for s in sel for k in range(4)]


# mask -> the selectors fuse_blocks must read from it (None: the mask
# is byte-granular and stays on vshuffle_dyn)
_SHUFFLE_MASKS = {
    "identity": (list(range(16)), (0, 1, 2, 3)),
    "lanes-left-1": (_lanes_left(1), (1, 2, 3, 0)),
    "lanes-left-2": (_lanes_left(2), (2, 3, 0, 1)),
    "lanes-left-3": (_lanes_left(3), (3, 0, 1, 2)),
    "word-interleave": (_words(0, 4, 1, 5), (0, 4, 1, 5)),
    "word-broadcast": (_words(5, 5, 5, 5), (5, 5, 5, 5)),
    # the interleave of test_lane_ops_shuffle_swizzle_bitselect_parity
    "byte-interleave": ([0, 17, 2, 19, 4, 21, 6, 23,
                         8, 25, 10, 27, 12, 29, 14, 31], None),
    # a rotate by 16 bits within each word, as a pshufb build has it
    "rotate16-in-words": ([2, 3, 0, 1, 6, 7, 4, 5,
                           10, 11, 8, 9, 14, 15, 12, 13], None),
}


def _shuffle_ops(shapes):
    return [op for shape in shapes for op in shape
            if op[0] in ("vshuffle", "vshufflew")]


@pytest.mark.parametrize("optimistic", [True, False],
                         ids=["optimistic", "careful"])
@pytest.mark.parametrize("name", sorted(_SHUFFLE_MASKS))
def test_pallas_shuffle_parity_and_lowering(name, optimistic):
    """Both Pallas kernels against the scalar engine, bit for bit, with
    the lowering fuse_blocks chose for the mask and the static count."""
    from wasmedge_tpu.batch.pallas_engine import fuse_blocks, hid_plane
    from wasmedge_tpu.batch.uniform import UniformBatchEngine

    mask, words = _SHUFFLE_MASKS[name]
    data = build_sweep([[("local.get", 2), ("local.get", 5),
                         ("i8x16.shuffle", mask)]])
    conf = Configure()
    conf.batch.interpret = True
    conf.batch.optimistic = optimistic
    conf.batch.steps_per_launch = 50_000
    conf.batch.value_stack_depth = 32
    conf.batch.call_stack_depth = 8
    _ex, store, inst = instantiate(data, conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    args = rand_args(6)
    res = eng.run("f", args, max_steps=100_000)
    assert not eng.fell_back_to_simt and eng.pallas.optimistic is optimistic
    assert np.all(np.asarray(res.trap) == -1)
    s_ex, s_store, s_inst = instantiate(data, Configure())
    for lane in range(LANES):
        (want,) = s_ex.invoke(s_store, s_inst.find_func("f"),
                              [int(a[lane]) for a in args])
        assert int(res.results[0][lane]) & (2**64 - 1) == \
            int(want) & (2**64 - 1), lane
    img = eng.pallas.img
    _hid, shapes = fuse_blocks(hid_plane(img), img)
    if words is None:
        assert _shuffle_ops(shapes) == [("vshuffle",)]
        assert eng.pallas.shuffle_sites == {"word": 0, "dynamic": 1}
    else:
        assert _shuffle_ops(shapes) == [("vshufflew", words)]
        assert eng.pallas.shuffle_sites == {"word": 1, "dynamic": 0}


def test_blocks_equal_but_for_a_word_mask_get_a_shape_each():
    """The selectors are part of a block's shape as an ALU sub is: two
    blocks that differ in a word-granular mask alone are two shapes,
    with the same mask one, and fuse_blocks is a function of the image."""
    from wasmedge_tpu.batch.pallas_engine import (
        H_BLOCK_BASE, fuse_blocks, hid_plane, shuffle_sites)
    from wasmedge_tpu.batch.uniform import UniformBatchEngine

    def module(mask_f, mask_g):
        b = ModuleBuilder()
        for export, mask in (("f", mask_f), ("g", mask_g)):
            b.add_function(["i64", "i64"], ["i64"], [], [
                ("local.get", 0), "i64x2.splat",
                ("local.get", 1), "i64x2.splat",
                ("i8x16.shuffle", mask), ("i64x2.extract_lane", 1),
            ], export=export)
        return b.build()

    def fused(data):
        conf = Configure()
        conf.batch.interpret = True    # a Pallas engine on the CPU; none runs
        _ex, store, inst = instantiate(data, conf)
        img = UniformBatchEngine(inst, store=store, conf=conf,
                                 lanes=LANES).pallas.img
        hid, shapes = fuse_blocks(hid_plane(img), img)
        again, shapes_again = fuse_blocks(hid_plane(img), img)
        assert np.array_equal(hid, again) and shapes == shapes_again
        heads = [int(hid[int(pc)]) for pc in img.f_entry]
        assert all(h >= H_BLOCK_BASE for h in heads)
        return heads, shapes, shuffle_sites(hid, shapes, img)

    (f, g), shapes, sites = fused(module(_lanes_left(1), _lanes_left(3)))
    assert f != g and sites == {"word": 2, "dynamic": 0}
    assert _shuffle_ops(shapes) == [("vshufflew", (1, 2, 3, 0)),
                                    ("vshufflew", (3, 0, 1, 2))]
    (f, g), shapes, sites = fused(module(_lanes_left(1), _lanes_left(1)))
    assert f == g and len(_shuffle_ops(shapes)) == 1
    assert sites == {"word": 2, "dynamic": 0}
    # byte-granular masks are data: one shape whatever they hold
    (f, g), shapes, sites = fused(module(
        _SHUFFLE_MASKS["byte-interleave"][0],
        _SHUFFLE_MASKS["rotate16-in-words"][0]))
    assert f == g and _shuffle_ops(shapes) == [("vshuffle",)]
    assert sites == {"word": 0, "dynamic": 2}
