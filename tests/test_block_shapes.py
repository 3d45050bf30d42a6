"""Which heads get one of the kernel's MAX_BLOCK_SHAPES block shapes.

Where an image's heads want no more shapes than that, fuse_blocks'
plane is the one the code-order rule made (every guest of a listed cell
but CoreMark); where they want more, the shapes kept are those of the
deepest loops.  The dispatch counts are replayed from the pc of every
instruction CoreMark retires on the converged engine, through
walk_shape, as the kernel would dispatch them."""

import numpy as np
import pytest

from tests.helpers import instantiate
from wasmedge_tpu.batch import pallas_engine as pe
from wasmedge_tpu.batch.pallas_engine import (
    CLS_BR, CLS_BRNZ, CLS_BRZ, H_BLOCK_BASE, hid_plane, walk_shape)
from wasmedge_tpu.common.configure import Configure

LANES = 8
REHEARSAL = 420     # TOTAL_DATA_SIZE of the cell's rehearsal


class _CodeOrder:
    """The admission rule before the loop-depth rule, as a `keep` for
    `_fuse_blocks`: a shape is taken if it was taken before or while
    fewer than MAX_BLOCK_SHAPES were."""

    def __init__(self):
        self.taken = set()

    def __contains__(self, shape):
        if shape in self.taken or len(self.taken) < pe.MAX_BLOCK_SHAPES:
            self.taken.add(shape)
            return True
        return False


def code_order_plane(img):
    """(hid, shapes) as ids went out in code order, the inline accesses
    counted on that plane."""
    def plane(inline_mem):
        return pe._fuse_blocks(hid_plane(img), img, inline_mem,
                               _CodeOrder())[:2]

    hid, shapes = plane(True)
    if sum(1 for shape in shapes for op in shape
           if op[0] in ("loadi", "storei")) > pe.MAX_INLINE_ACCESSES:
        return plane(False)
    return hid, shapes


def image(wasm):
    from wasmedge_tpu.host.wasi import WasiModule

    conf = Configure()
    _ex, store, inst = instantiate(wasm, conf, imports=[WasiModule()])
    return pe.PallasUniformEngine(inst, store=store, conf=conf, lanes=LANES,
                                  interpret=True).img


def _guests():
    from wasmedge_tpu.models import build_fib, build_memory_batch
    from wasmedge_tpu.models.programs import (
        build_chacha20, build_chacha20_wasi, build_polybench_gemm)

    return {"fib": build_fib, "memory": build_memory_batch,
            "gemm": build_polybench_gemm, "chacha20": build_chacha20,
            "chacha20-wasi": build_chacha20_wasi}


@pytest.mark.parametrize("guest,wanted", [
    ("fib", 3), ("memory", 5), ("gemm", 14), ("chacha20", 27),
    ("chacha20-wasi", 32)])
def test_a_guest_under_the_budget_keeps_the_code_order_plane(guest, wanted):
    img = image(_guests()[guest]())
    hid, shapes, counts = pe.fuse_blocks_counted(hid_plane(img), img)
    assert counts == {"kept": wanted, "wanted": wanted}
    want_hid, want_shapes = code_order_plane(img)
    assert np.array_equal(hid, want_hid) and shapes == want_shapes
    fused, fshapes = pe.fuse_blocks(hid_plane(img), img)
    assert np.array_equal(fused, hid) and fshapes == shapes


def test_loop_depth_counts_the_backward_branches_around_a_slot():
    from wasmedge_tpu.models.programs import build_coremark

    img = image(build_coremark(REHEARSAL))
    depth = pe.loop_depths(img)
    back = [s for s in range(img.code_len)
            if int(img.cls[s]) in (CLS_BR, CLS_BRZ, CLS_BRNZ)
            and int(img.a[s]) <= s]
    for pc in range(0, img.code_len, 7):
        assert depth[pc] == sum(int(img.a[s]) <= pc <= s for s in back)
    assert depth.max() == 5


def _functions(img):
    """[lo, hi) of every function's code, by function index."""
    entries = [int(x) for x in img.f_entry]
    ends = sorted(entries) + [img.code_len]
    return [(lo, ends[ends.index(lo) + 1]) for lo in entries]


def _heads_in_deepest_loop(hid, img, func):
    lo, hi = _functions(img)[func]
    depth = pe.loop_depths(img)[lo:hi]
    return int(np.count_nonzero((hid[lo:hi] >= H_BLOCK_BASE)
                                & (depth == depth.max())))


def test_coremark_keeps_the_shapes_of_its_deepest_loops():
    """CoreMark's heads want 177 shapes.  In code order the 96 went to
    the functions that come first in its file; by loop depth the heads
    of core_state_transition's loop (function 28) and of
    matrix_mul_matrix_bitextract's depth-3 loop (function 25) hold
    blocks.  No load or store is fused inline on either plane."""
    from wasmedge_tpu.models.programs import build_coremark

    img = image(build_coremark())
    hid, shapes, counts = pe.fuse_blocks_counted(hid_plane(img), img)
    assert counts == {"kept": 96, "wanted": 177}
    assert len(shapes) == len(set(shapes)) == 96
    assert sorted(set(int(h) for h in hid[hid >= H_BLOCK_BASE])) == \
        list(range(H_BLOCK_BASE, H_BLOCK_BASE + 96))
    old_hid, old_shapes = code_order_plane(img)
    assert len(old_shapes) == 96
    for plane in (shapes, old_shapes):
        assert not any(op[0] in ("loadi", "storei")
                       for shape in plane for op in shape)
    assert [_heads_in_deepest_loop(old_hid, img, f) for f in (25, 28)] \
        == [0, 18]
    assert [_heads_in_deepest_loop(hid, img, f) for f in (25, 28)] \
        == [2, 38]
    # a function of the image alone
    again, again_shapes = pe.fuse_blocks(hid_plane(img), img)
    assert np.array_equal(again, hid) and again_shapes == shapes


def pc_trace(size):
    """The pc of every instruction coremark(1) retires at
    TOTAL_DATA_SIZE `size`, in order, on the converged engine (8 lanes,
    one pc)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from wasmedge_tpu.batch.uniform import (
        ST_DONE, ST_RUNNING, UniformBatchEngine, make_uniform_step)
    from wasmedge_tpu.models.programs import build_coremark

    conf = Configure()
    conf.batch.use_pallas = False
    conf.batch.value_stack_depth = 128
    conf.batch.call_stack_depth = 64
    _ex, store, inst = instantiate(build_coremark(size), conf)
    eng = UniformBatchEngine(inst, store=store, conf=conf, lanes=LANES)
    state = eng._initial_uniform_state(inst.exports["coremark"][1],
                                       [np.ones(LANES, np.int64)])
    step = make_uniform_step(eng.img, eng.cfg, LANES)
    clock = jnp.zeros((2, 2), jnp.int32)
    cap = 1_000_000

    def run(st):
        def cond(carry):
            i, s, _trace = carry
            return (i < cap) & (s.status == ST_RUNNING)

        def body(carry):
            i, s, trace = carry
            trace = lax.dynamic_update_slice(trace, s.pc[None], (i,))
            return i + 1, step(s, clock), trace

        return lax.while_loop(cond, body, (jnp.int32(0), st,
                                           jnp.zeros(cap, jnp.int32)))

    n, state, trace = jax.jit(run)(state)
    assert int(state.status) == ST_DONE
    return eng.img, np.asarray(trace)[:int(n)]


def dispatches(trace, hid, shapes, img):
    """How many dispatches the kernel makes of `trace` on the plane
    (hid, shapes): an unfused slot is one; a block runs along the trace
    through walk_shape's ops, into a guard's tail where the guard is
    taken, to its terminal, its end, or a taken guard with no tail."""
    n = len(trace)
    count = i = 0
    while i < n:
        head = int(trace[i])
        count += 1
        if hid[head] < H_BLOCK_BASE:
            i += 1
            continue
        in_taken_tail = False
        for op, slot, in_tail in walk_shape(
                shapes[int(hid[head]) - H_BLOCK_BASE], head, img):
            if in_tail and not in_taken_tail:
                continue        # the tail of a guard that fell through
            if op[0] == "end":
                break
            assert trace[i] == slot
            i += 1
            if op[0] == "term":
                break
            target = int(img.a[slot])
            if op[0] in ("guardz", "guardnz") and i < n and \
                    trace[i] == target != slot + 1:
                if not op[1]:
                    break
                in_taken_tail = True
    return count


@pytest.mark.parametrize("size,retired,before,after", [
    (REHEARSAL, 81_797, 38_895, 28_504),
    (2000, 834_415, 570_333, 275_878)], ids=["rehearsal", "2k"])
def test_replayed_dispatches(size, retired, before, after):
    """One iteration.  The rehearsal's count on the code-order plane is
    what the interpret-mode kernel counted there on that plane (38,895),
    and the 2K run's is 1.4630 instructions a dispatch, where a v5e
    counted 1.4636 at 20 iterations.  At 2K the loop-depth rule more than
    doubles the instructions a dispatch (1.463 -> 3.025); the
    rehearsal's block is too small to show it so (2.103 -> 2.870)."""
    img, trace = pc_trace(size)
    assert len(trace) == retired
    hid, shapes = pe.fuse_blocks(hid_plane(img), img)
    old_hid, old_shapes = code_order_plane(img)
    assert dispatches(trace, old_hid, old_shapes, img) == before
    assert dispatches(trace, hid, shapes, img) == after
    if size == 2000:
        assert retired / after >= 2 * retired / before
