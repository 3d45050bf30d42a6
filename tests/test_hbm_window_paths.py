"""The path of a windowed access (PR 31), interpret mode on the CPU.

Straight-line guests that steer the Pallas kernel's two-way HBM window
through the cases a sweep never meets.  A window is 128 rows (words);
the first miss of a launch fills way 1, the next way 0 (the victim is
the way not used last).  Every case: all lanes against the scalar
engine bit for bit, the window's DMA counts against what the kernel of
PR 30 counted for the same guest, and `window_accesses` against the
guest's own loads and stores (a rollback, or lane 0 out of bounds,
changes that count, to the one the case gives).

A file of its own because tests/conftest.py marks every test of
tests/test_pallas_hbm.py slow, and tier-1 runs `-m 'not slow'`.
"""

import numpy as np
import pytest

import tests.test_pallas_engine as tpe
from tests.test_pallas_hbm import _HbmConfigure


def _i32(v):
    return v - (1 << 32) if v >= 1 << 31 else v


def _straddle_guest():
    """(a, b): word 0 first, so way 1 holds rows 0..127; then an
    unaligned i32 at byte a and an unaligned i64 at byte b, read back
    with loads of the other width."""
    b = tpe.ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32", "i32"), ("i64",), (), [
        ("i32.const", 0), ("i32.const", 0x01020304), ("i32.store", 2, 0),
        ("local.get", 0), ("i32.const", _i32(0xA1B2C3D4)),
        ("i32.store", 0, 0),
        ("local.get", 1), ("i64.const", 0x1122334455667788),
        ("i64.store", 0, 0),
        ("local.get", 0), ("i64.load", 0, 0),
        ("local.get", 1), ("i32.load", 0, 4), ("i64.extend_i32_u",),
        "i64.xor",
        ("i32.const", 0), ("i64.load32_u", 2, 0), "i64.add",
    ], export="f")
    return b.build()


def _two_ways_guest():
    """Way 1 takes row 5, way 0 row 1000, both stored to, so both are
    live and dirty; then a store that hits way 0 and loads that hit
    way 1 and way 0 in turn."""
    b = tpe.ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32",), ("i32",), (), [
        ("i32.const", 20), ("local.get", 0), ("i32.store", 2, 0),
        ("i32.const", 4000), ("i32.const", 77), ("i32.store", 2, 0),
        ("i32.const", 4040), ("local.get", 0), ("i32.const", 3),
        "i32.mul", ("i32.store", 2, 0),
        ("i32.const", 20), ("i32.load", 2, 0),
        ("i32.const", 4040), ("i32.load", 2, 0), "i32.add",
        ("i32.const", 4000), ("i32.load", 2, 0), "i32.add",
    ], export="f")
    return b.build()


def _overlap_guest():
    """Way 1 holds rows 0..127 and way 0 rows 1000..1127, both dirty and
    way 0 used last.  The i32 at row 1127 wants row 1128 too: a miss
    whose window (from row 1120) replaces way 1 and overlaps way 0, so
    both are written back and way 0 is dropped (a row lives in one way
    at most).  Rows 1000 and 5 are then read back through new fills."""
    b = tpe.ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32",), ("i32",), (), [
        ("i32.const", 20), ("local.get", 0), ("i32.store", 2, 0),
        ("i32.const", 4000), ("i32.const", 77), ("i32.store", 2, 0),
        ("i32.const", 4508), ("i32.const", 4242), ("i32.store", 2, 0),
        ("i32.const", 4508), ("i32.load", 2, 0),
        ("i32.const", 4000), ("i32.load", 2, 0), "i32.add",
        ("i32.const", 20), ("i32.load", 2, 0), "i32.add",
    ], export="f")
    return b.build()


def _rollback_guest():
    """Every lane stores at an address of its own (lane 0's decides, the
    canary marks the rest), then three more windows are stored to: the
    third miss evicts a dirty way, finds the canary dirty and rolls the
    block back to its entry, and the careful kernel runs the guest
    again against windows the rollback left invalid."""
    b = tpe.ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32",), ("i32",), (), [
        ("local.get", 0), ("local.get", 0), ("i32.const", 1), "i32.add",
        ("i32.store", 2, 0),
        ("i32.const", 16000), ("i32.const", 5), ("i32.store", 2, 0),
        ("i32.const", 32000), ("i32.const", 6), ("i32.store", 2, 0),
        ("local.get", 0), ("i32.load", 2, 0),
        ("i32.const", 16000), ("i32.load", 2, 0), "i32.add",
        ("i32.const", 32000), ("i32.load", 2, 0), "i32.add",
    ], export="f")
    return b.build()


def _oob_in_block_guest():
    """A store, then a load at the argument inside the same fused block:
    out of bounds it traps, and the store before it stays done."""
    b = tpe.ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32",), ("i32",), (), [
        ("i32.const", 64), ("i32.const", 9), ("i32.store", 2, 0),
        ("local.get", 0), ("i32.load", 2, 0),
        ("i32.const", 64), ("i32.load", 2, 0), "i32.add",
    ], export="f")
    return b.build()


def _every_shift_guest():
    """Stores of every width at every byte shift over 640 bytes of
    ones, so that a byte too many or too few shows, folded by i64
    loads: the words a store touches follow from its width and shift.
    Three loops (160 stores, 4 x 4 stores, 80 loads)."""
    def loop(n, body):
        return [("i32.const", 0), ("local.set", 1), ("block", None),
                ("loop", None),
                ("local.get", 1), ("i32.const", n), "i32.ge_u", ("br_if", 1),
                *body,
                ("local.get", 1), ("i32.const", 1), "i32.add",
                ("local.set", 1), ("br", 0), "end", "end"]

    def at(stride):     # x + i * stride
        return [("local.get", 0), ("local.get", 1), ("i32.const", stride),
                "i32.mul", "i32.add"]

    b = tpe.ModuleBuilder()
    b.add_memory(1, 1)
    b.add_function(("i32",), ("i64",), ("i32", "i64"), [
        *loop(160, [*at(4), ("i32.const", -1), ("i32.store", 2, 0)]),
        # shift i: 16 bytes on, and i bytes in
        *loop(4, [*at(17), ("i32.const", 0x5A), ("i32.store8", 0, 64),
                  *at(17), ("i32.const", 0x6B7C), ("i32.store16", 0, 128),
                  *at(17), ("i32.const", 0x1D2E3F40), ("i32.store", 0, 256),
                  *at(17), ("i64.const", 0x0123456789ABCDEF),
                  ("i64.store", 0, 384)]),
        *loop(80, [("local.get", 2), *at(8), ("i64.load", 3, 0), "i64.add",
                   ("i64.const", 31), "i64.rotl", ("local.set", 2)]),
        ("local.get", 2),
    ], export="f")
    return b.build()


# case -> (guest, arguments (one value for every lane, or a list),
#          (fills, write-backs) of PR 30's kernel, window accesses,
#          rollbacks?)
_WINDOW_CASES = {
    # the i32 covers rows 127 and 128, one past the first window
    "i32-straddles-the-last-row": (
        _straddle_guest, [127 * 4 + 2, 140 * 4],
        (3, 2), 6, False),
    # the i64 covers rows 254..256 of the window filled at row 128
    "i64-straddles-the-last-row": (
        _straddle_guest, [130 * 4, 254 * 4 + 1],
        (6, 3), 6, False),
    "store-way0-load-way1-both-dirty": (
        _two_ways_guest, [1234], (2, 2), 6, False),
    "miss-overlaps-the-other-dirty-way": (
        _overlap_guest, [99], (5, 3), 6, False),
    "dirty-canary-rolls-back-to-careful": (
        _rollback_guest, [[0, 8, 16, 24, 4, 12, 20, 28]],
        # three stores up to the rollback, then all six on the careful
        # kernel
        (8, 3), 9, True),
    "stores-of-every-width-at-every-shift": (
        _every_shift_guest, [8], (5, 4), 160 + 16 + 80, False),
    "oob-in-a-fused-block-all-lanes": (
        # the store and the load that leaves; SIMT runs the rest
        _oob_in_block_guest, [0x10000], (2, 1), 2, False),
    "oob-in-a-fused-block-some-lanes": (
        _oob_in_block_guest,
        [[0, 4, 0x10000, 8, 0xFFFFF0, 12, 16, 64]],
        # the store, the load that leaves, and the store again after the
        # exit's validation rolled the block back
        (2, 1), 3, True),
}


@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_window_paths(case):
    guest, spec, dmas, accesses, rolls_back = _WINDOW_CASES[case]
    args = [np.full(tpe.LANES, v, np.int64) if np.isscalar(v)
            else np.asarray(v, np.int64) for v in spec]
    eng, res = tpe.check_parity(guest(), "f", args, conf=_HbmConfigure())
    assert eng._mem_mode() is True
    # lane 0 out of bounds hands the block on un-advanced, as before
    assert eng.fell_back_to_simt == ("oob" in case)
    assert (eng.recheck_rounds > 0) == rolls_back
    assert (eng.window_fills, eng.window_writebacks) == dmas
    assert eng.window_accesses == accesses
    assert eng.window_hit_share == 1 - eng.window_fills / accesses
    if "oob" in case:
        assert (res.trap != -1).any()
