"""SoA-vectorized WASI implementations for the batch outcall channel.

Tier 1 of the three-tier hostcall pipeline (batch/hostcall.py): when the
batch engines drain parked lanes, lanes are grouped by hostcall id and
each group of a recognized WASI function is served by ONE vectorized
NumPy implementation over the [words, lanes] memory plane — replacing
the per-lane Python loop through host/wasi/wasifunc.py that materialized
a 64 KiB bytearray per lane per call.  Semantics mirror the scalar
functions (same errno surface, same pointer-fault behavior: a bad guest
pointer is EFAULT, matching WasiHostFunction's TrapError translation).

Implementations receive:
  env   the group's WasiEnviron (per-tenant in multi-tenant batches)
  view  a MemView over the group's lane columns (vectorized byte access)
  args  int64 [nargs, n] raw argument cells

and return (results [nres, n] int64, trap_codes [n] int32).  Raising
NotVectorizable routes the whole group to the per-lane fallback loop
(e.g. sockets, oversized iovec arrays).
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict

import numpy as np

from wasmedge_tpu.host.wasi.environ import WasiEnviron, WasiError
from wasmedge_tpu.host.wasi.wasi_abi import Errno, Rights

MASK32 = 0xFFFFFFFF

# iovec arrays longer than this are rare enough that the per-lane loop
# is fine (and keeps the vectorized path's word gathers bounded)
MAX_VEC_IOVS = 8


class NotVectorizable(Exception):
    """Group cannot be served vectorized; use the per-lane loop."""


class MemView:
    """Vectorized byte accessor over a word-major int32 plane restricted
    to a set of lane columns.

    `_words` / per-lane byte stores are the only backend-specific
    primitives: SoAMemView indexes a NumPy plane directly (SIMT serve),
    CachedPlaneView (batch/hostcall.py) goes through the chunked device
    cache so only touched 4 KiB windows cross the host link."""

    def __init__(self, lanes, pages):
        self.lanes = np.asarray(lanes, np.int64)
        self.n = int(self.lanes.size)
        self.pages = np.broadcast_to(
            np.asarray(pages, np.int64), (self.n,))

    # -- backend primitives -------------------------------------------------
    def _words(self, widx: np.ndarray) -> np.ndarray:
        """Gather int32 words: widx [k, n] row indices -> [k, n]."""
        raise NotImplementedError

    def _store_bytes_one(self, i: int, off: int, data: bytes):
        """Store bytes into view-lane i's memory at byte offset off."""
        raise NotImplementedError

    def _row_span(self, w0: int, k: int, lane_major: bool = False):
        """Words of rows [w0, w0 + k) of every view lane: [k, n], or
        with `lane_major` [n, k] contiguous.  What `_words` gives for a
        row index that is the same in every lane, without building the
        index; a backend may slice, or transpose where it is cheap."""
        words = self._words(np.broadcast_to(
            np.arange(w0, w0 + k, dtype=np.int64)[:, None], (k, self.n)))
        return np.ascontiguousarray(words.T) if lane_major else words

    def _store_words(self, widx: np.ndarray, vals: np.ndarray, sel):
        """Store int32 words: widx, vals [k, m] for the view lanes `sel`
        [m], one aligned word each.  A backend may scatter."""
        for j, i in enumerate(sel):
            for r in range(widx.shape[0]):
                self._store_bytes_one(
                    int(i), 4 * int(widx[r, j]),
                    int(np.uint32(vals[r, j])).to_bytes(4, "little"))

    # -- shared vectorized layer --------------------------------------------
    def bounds_ok(self, off, ln) -> np.ndarray:
        off = np.asarray(off, np.uint64)
        ln = np.broadcast_to(np.asarray(ln, np.uint64), off.shape)
        end = off + ln
        return (end >= off) & (end <= self.pages.astype(np.uint64)
                               * np.uint64(65536))

    def _unaligned_u32(self, off, count: int):
        """`count` u32 from byte `off` on, a lane: uint32 [count, n]
        (count + 1 words a lane; rows, not an index, where every lane
        reads at one address)."""
        off = np.asarray(off, np.int64)
        w0 = off >> 2
        if self.n and int(off.min()) == int(off.max()):
            ws = self._row_span(int(w0[0]), count + 1)
        else:
            ws = self._words(np.stack([w0 + i for i in range(count + 1)]))
        w = [x.view(np.uint32).astype(np.uint64) for x in ws]
        sh = ((off & 3) * 8).astype(np.uint64)
        return [((w[i] | (w[i + 1] << np.uint64(32))) >> sh).astype(
            np.uint32) for i in range(count)]

    def load_u32(self, off) -> np.ndarray:
        return self._unaligned_u32(off, 1)[0]

    def load_u32x2(self, off):
        """(u32 at off, u32 at off + 4): an iovec's pointer and length,
        or any two adjacent words, in one gather."""
        return tuple(self._unaligned_u32(off, 2))

    def gather_bytes(self, off, ln) -> list:
        """Per-lane bytes objects for ranges [off, off+ln); caller has
        bounds-checked.  One fancy gather covers every lane."""
        off = np.asarray(off, np.int64)
        ln = np.asarray(ln, np.int64)
        if self.n == 0:
            return []
        maxb = int(((off & 3) + ln).max(initial=0))
        if maxb == 0:
            return [b""] * self.n
        maxw = (maxb + 3) // 4
        raw = self._lane_major(off, maxw)               # [n, maxw*4]
        out = []
        for i in range(self.n):
            s = int(off[i] & 3)
            out.append(raw[i, s:s + int(ln[i])].tobytes())
        return out

    def _lane_major(self, off, maxw: int) -> np.ndarray:
        """uint8 [n, 4 * maxw]: lane i's `maxw` words from the word that
        holds byte `off[i]`.  One fancy gather covers every lane; where
        every lane reads at the same offset it is a slice of rows."""
        w0 = off >> 2
        if int(w0.min()) == int(w0.max()):
            return self._row_span(int(w0[0]), maxw, True).view(np.uint8)
        words = self._words(
            w0[None, :] + np.arange(maxw, dtype=np.int64)[:, None])
        return np.ascontiguousarray(words.T).view(np.uint8)

    def gather_matrix(self, off, ln):
        """uint8 [n, ln] of the ranges [off, off + ln) where every lane
        reads the same, non-zero length (caller has bounds-checked): the
        lanes' bytes end to end, which is what one write of them in
        lane order hands to an fd.  None where the lengths differ."""
        off = np.asarray(off, np.int64)
        ln = np.asarray(ln, np.int64)
        if self.n == 0 or int(ln.min()) != int(ln.max()) or int(ln[0]) == 0:
            return None
        n1 = int(ln[0])
        s0 = off & 3
        raw = self._lane_major(off, (int(s0.max()) + n1 + 3) // 4)
        if not s0.any():
            return raw[:, :n1]
        cols = s0[:, None] + np.arange(n1, dtype=np.int64)[None, :]
        return np.take_along_axis(raw, cols, axis=1)

    def store_u32(self, off, vals, mask=None):
        self._store_scalar(off, np.asarray(vals, np.uint64), 4, mask)

    def store_u64(self, off, vals, mask=None):
        self._store_scalar(off, np.asarray(vals, np.uint64), 8, mask)

    def _store_scalar(self, off, vals, nbytes, mask):
        off = np.asarray(off, np.int64)
        m = np.ones(self.n, bool) if mask is None \
            else np.asarray(mask, bool).copy()
        m &= np.asarray(self.bounds_ok(off, nbytes))
        sel = np.nonzero(m)[0]
        if sel.size and not (off[sel] & 3).any():
            # aligned: whole words, one scatter for every lane
            k = nbytes // 4
            v = np.asarray(vals, np.uint64)[sel]
            words = np.stack([
                ((v >> np.uint64(32 * r)) & np.uint64(MASK32)).astype(
                    np.uint32).view(np.int32) for r in range(k)])
            self._store_words(
                (off[sel] >> 2)[None, :]
                + np.arange(k, dtype=np.int64)[:, None], words, sel)
            return
        for i in sel:
            self._store_bytes_one(
                int(i), int(off[i]),
                int(vals[i]).to_bytes(nbytes, "little"))

    def store_bytes(self, off, datas, mask=None):
        off = np.asarray(off, np.int64)
        m = np.ones(self.n, bool) if mask is None else np.asarray(mask, bool)
        for i in np.nonzero(m)[0]:
            if datas[i]:
                self._store_bytes_one(int(i), int(off[i]), datas[i])


class SoAMemView(MemView):
    """MemView over a host-resident NumPy [W, L] plane (mutated in
    place; the SIMT serve uploads the plane back once per round)."""

    def __init__(self, plane: np.ndarray, lanes, pages):
        super().__init__(lanes, pages)
        self.plane = plane
        self.W = int(plane.shape[0])
        self.dirty = False

    def _words(self, widx):
        w = np.clip(widx, 0, self.W - 1)
        return self.plane[w, self.lanes[None, :]]

    def _store_words(self, widx, vals, sel):
        self.plane[widx, self.lanes[sel][None, :]] = vals
        self.dirty = True

    def _store_bytes_one(self, i, off, data):
        lane = int(self.lanes[i])
        w0 = off >> 2
        w1 = (off + len(data) - 1) >> 2
        cur = bytearray(
            np.ascontiguousarray(self.plane[w0:w1 + 1, lane]).tobytes())
        s = off & 3
        cur[s:s + len(data)] = data
        self.plane[w0:w1 + 1, lane] = np.frombuffer(bytes(cur), np.int32)
        self.dirty = True


# ---------------------------------------------------------------------------
# vectorized implementations
# ---------------------------------------------------------------------------
VEC_WASI: Dict[str, Callable] = {}

# Flight recorder the tier-1 drain reports per-hostcall-kind latency
# histograms into (obs/recorder.py).  Installed by the serving loops
# (batch/hostcall.py serve_batch_state, pallas_engine's block serve)
# for the duration of one drain round; None when observability is off,
# so the registered implementations run with zero timing overhead.
# THREAD-LOCAL: concurrent serves (mesh per-device threads, multiple
# VMs in one process) each install/restore their own engine's recorder
# without clobbering another thread's attribution.
_DRAIN = threading.local()


def set_drain_recorder(rec):
    """Install this thread's recorder for the drain round (None = off);
    returns the previous one so callers can restore it."""
    prev = getattr(_DRAIN, "rec", None)
    _DRAIN.rec = rec if (rec is not None
                         and getattr(rec, "enabled", False)) else None
    return prev


def _vec(name: str):
    def deco(fn):
        def timed(env, view, args):
            rec = getattr(_DRAIN, "rec", None)
            if rec is None:
                return fn(env, view, args)
            t0 = rec.now()
            # NotVectorizable propagates untimed: the group re-runs on
            # the per-lane loop, which records its own observation
            out = fn(env, view, args)
            rec.hostcall(name, rec.now() - t0, lanes=view.n,
                         vectorized=True)
            return out

        timed.__name__ = f"vec_{name}"
        timed.inner = fn
        VEC_WASI[name] = timed
        return fn
    return deco


def _zeros_res(n: int, nres: int = 1):
    return np.zeros((nres, n), np.int64), np.zeros(n, np.int32)


@_vec("sched_yield")
def vec_sched_yield(env: WasiEnviron, view: MemView, args):
    os.sched_yield()
    return _zeros_res(view.n)


@_vec("proc_exit")
def vec_proc_exit(env: WasiEnviron, view: MemView, args):
    """Every lane in the group terminates (ErrCode.Terminated); the
    environ records the last lane's code like the scalar path records
    the (single) instance's."""
    from wasmedge_tpu.common.errors import ErrCode

    env.exit_code = int(args[0][-1] & MASK32)
    env.exited = True
    res = np.zeros((0, view.n), np.int64)
    return res, np.full(view.n, int(ErrCode.Terminated), np.int32)


@_vec("clock_time_get")
def vec_clock_time_get(env: WasiEnviron, view: MemView, args):
    n = view.n
    ids = (args[0] & MASK32).astype(np.int64)
    ptrs = (args[2] & MASK32).astype(np.int64)
    res = np.zeros(n, np.int64)
    ok = np.ones(n, bool)
    times = np.zeros(n, np.uint64)
    for cid in np.unique(ids):
        m = ids == cid
        try:
            times[m] = np.uint64(env.clock_time(int(cid)))
        except WasiError as werr:
            res[m] = int(werr.errno)
            ok[m] = False
    bok = view.bounds_ok(ptrs, 8)
    res[ok & ~bok] = int(Errno.FAULT)
    view.store_u64(ptrs, times, ok & bok)
    return res.reshape(1, n), np.zeros(n, np.int32)


@_vec("random_get")
def vec_random_get(env: WasiEnviron, view: MemView, args):
    n = view.n
    bufs = (args[0] & MASK32).astype(np.int64)
    lens = (args[1] & MASK32).astype(np.int64)
    bok = np.asarray(view.bounds_ok(bufs, lens))
    res = np.where(bok, 0, int(Errno.FAULT)).astype(np.int64)
    total = int(lens[bok].sum())
    blob = os.urandom(total)
    datas = [b""] * n
    pos = 0
    for i in np.nonzero(bok)[0]:
        ln = int(lens[i])
        datas[i] = blob[pos:pos + ln]
        pos += ln
    view.store_bytes(bufs, datas, bok)
    return res.reshape(1, n), np.zeros(n, np.int32)


@_vec("fd_write")
def vec_fd_write(env: WasiEnviron, view: MemView, args):
    n = view.n
    fds = (args[0] & MASK32).astype(np.int64)
    iovs = (args[1] & MASK32).astype(np.int64)
    cnt = (args[2] & MASK32).astype(np.int64)
    nwp = (args[3] & MASK32).astype(np.int64)
    if int(cnt.max(initial=0)) > MAX_VEC_IOVS:
        raise NotVectorizable("iovec array too long")
    res = np.zeros(n, np.int64)
    live = np.ones(n, bool)

    # resolve fds once per distinct value; sockets keep scalar semantics
    entries = {}
    for fd in np.unique(fds):
        try:
            e = env.get_fd(int(fd), Rights.FD_WRITE)
        except WasiError as werr:
            m = fds == fd
            res[m] = int(werr.errno)
            live[m] = False
            continue
        if e.kind == "socket":
            raise NotVectorizable("socket write")
        entries[int(fd)] = e

    # iovec array bounds (scalar: _read_iovs check_bounds -> EFAULT)
    arr_ok = np.asarray(view.bounds_ok(iovs, 8 * cnt))
    res[live & ~arr_ok] = int(Errno.FAULT)
    live &= arr_ok

    # iovec j of every lane: a [n, len] byte matrix where every lane
    # hands over the same length (a buffered writer's block: the usual
    # case, and then no byte is touched lane by lane), else a list
    pieces = []
    total = np.zeros(n, np.int64)
    for j in range(int(cnt.max(initial=0))):
        has = live & (j < cnt)
        if not has.any():
            break
        bufs, lens = (x.astype(np.int64)
                      for x in view.load_u32x2(iovs + 8 * j))
        lens = np.where(has, lens, 0)
        dok = np.asarray(view.bounds_ok(bufs, lens))
        bad = has & ~dok
        # scalar: load_bytes faults -> EFAULT; earlier iovecs were
        # already written (same here: collected chunks still go out)
        res[bad] = int(Errno.FAULT)
        live &= dok | ~has
        lens = np.where(has & dok, lens, 0)
        piece = view.gather_matrix(bufs, lens)
        if piece is None:
            piece = view.gather_bytes(bufs, lens)
        pieces.append(piece)
        total += lens

    # one write per fd, lane-ascending (matches per-lane serve order)
    for fd, e in sorted(entries.items()):
        sel = np.nonzero(fds == fd)[0]
        if len(pieces) == 1 and isinstance(pieces[0], np.ndarray):
            block = pieces[0] if sel.size == n else pieces[0][sel]
            env.bytes_written += _write_all(
                e, np.ascontiguousarray(block).reshape(-1))
            continue
        rows = [p if isinstance(p, list) else
                [memoryview(r) for r in p] for p in pieces]
        env.bytes_written += _write_all(
            e, b"".join(r[i] for i in sel for r in rows))

    wrote = total.astype(np.uint64)
    np_ok = np.asarray(view.bounds_ok(nwp, 4))
    res[live & ~np_ok] = int(Errno.FAULT)
    view.store_u32(nwp, wrote, live & np_ok)
    return res.reshape(1, n), np.zeros(n, np.int32)


def _write_all(entry, data):
    """All of `data` (bytes, or a flat uint8 array: no copy) to the fd;
    the environ counts what its fds were handed."""
    off = 0
    while off < len(data):
        off += os.write(entry.os_fd, data[off:])
    return off
