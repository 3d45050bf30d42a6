"""Device→host outcall channel: batched host-function (WASI) calls.

This is the TPU-native analog of the reference's AOT intrinsics escape
(/root/reference/lib/executor/engine/proxy.cpp:45-71) designed in
SURVEY.md §5.8: a lane that calls an imported host function parks at a
synthetic HOSTCALL stub (batch/image.py appends one per import) with its
frame already pushed, the engine marks it waiting (TRAP_HOSTCALL in the
trap plane / ST_HOSTCALL block status), and the host step-loop drains the
waiting lanes through the ordinary Python host-function layer
(runtime/hostfunc.py — the same WASI functions the scalar engine calls),
writes results and memory effects back into the SoA state, and re-arms
the lanes while the rest of the batch keeps stepping.

Sandbox model: lanes of ONE engine share that engine's instance's host
modules (one WASI environ / fd table), like threads of one OS process;
per-lane data (args, results, linear memory) is fully isolated.  Tenants
are stronger: each tenant instance carries its own registered host
modules — its own WASI environ, preopens, and fd table (the per-VM
WASI::Environ model, reference environ.h:38-1156) — and the multi-tenant
scheduler serves every tenant's outcalls through its own instance, so
tenant A can never reach tenant B's preopens
(tests/test_multitenant.py::test_per_tenant_wasi_isolation).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from wasmedge_tpu.common.errors import ErrCode, TrapError
from wasmedge_tpu.host.wasi.environ import WasiExit
from wasmedge_tpu.runtime.instance import MemoryInstance

MASK32 = 0xFFFFFFFF


class _LaneMemory(MemoryInstance):
    """MemoryInstance view over one lane's column of the [W, lanes] plane.

    `page_limit` must be the plane's static capacity (img.mem_pages_max):
    a host function growing memory mid-outcall then stays inside the
    [W, lanes] allocation, and the serving loop writes the new page count
    back into the state's mem_pages plane (`pages` is derived from the
    bytearray length, so growth is visible to the caller)."""

    def __init__(self, data: bytearray, max_pages: Optional[int],
                 page_limit: int):
        # bypass MemoryInstance.__init__ (no ast.MemoryType at hand)
        self.min = len(data) // 65536
        self.max = max_pages
        self.page_limit = page_limit
        self.data = data


def lane_memory_bytes(mem_plane: np.ndarray, lane: int, pages: int) -> bytearray:
    """Extract one lane's linear memory as bytes (word-major plane)."""
    col = np.ascontiguousarray(mem_plane[:, lane])
    return bytearray(col.view(np.uint8)[: pages * 65536].tobytes())


def store_lane_memory(mem_plane: np.ndarray, lane: int, data: bytearray):
    nwords = min((len(data) + 3) // 4, mem_plane.shape[0])
    raw = np.frombuffer(bytes(data) + b"\x00" * 3, dtype=np.int32,
                        count=nwords)
    mem_plane[:nwords, lane] = raw


def serve_one(fi, args_cells: List[int],
              lane_mem: Optional[_LaneMemory]) -> Tuple[List[int], int]:
    """Run one lane's host call. Returns (result_cells, trap_code)."""
    if fi.kind != "host":
        return [], int(ErrCode.ExecutionFailed)
    try:
        out = fi.host.run(lane_mem, list(args_cells))
        return out, 0
    except TrapError as te:
        return [], int(te.code)
    except WasiExit:
        # proc_exit through the per-lane path: the lane terminates
        # (vectorized groups go through vec_proc_exit instead)
        return [], int(ErrCode.Terminated)


def wasi_env_of(engine):
    """The instance's WasiEnviron, found through any registered WASI
    host function (per-tenant instances carry per-tenant environs)."""
    inst = getattr(engine, "inst", None)
    for f in getattr(inst, "funcs", None) or []:
        if getattr(f, "kind", None) == "host":
            env = getattr(getattr(f, "host", None), "_env", None)
            if env is not None and hasattr(env, "get_fd"):
                return env
    return None


def vec_impl_for(fi):
    """(vectorized_fn, environ) for a WASI host function with a tier-1
    SoA implementation, else (None, None)."""
    host = getattr(fi, "host", None)
    env = getattr(host, "_env", None)
    if env is None or not hasattr(env, "get_fd"):
        return None, None
    from wasmedge_tpu.host.wasi.vectorized import VEC_WASI

    return VEC_WASI.get(getattr(host, "name", None)), env


def hostcall_kind(fi) -> str:
    """Stable label for a host function in the drain-latency histograms
    (the WASI function name when known, else the import pair)."""
    host = getattr(fi, "host", None)
    name = getattr(host, "name", None)
    if name:
        return str(name)
    mod = getattr(fi, "import_module", "") or "host"
    imp = getattr(fi, "import_name", "") or "?"
    return f"{mod}.{imp}"


def gather_arg_cells(stack_lo, stack_hi, fp, lanes, nargs) -> np.ndarray:
    """Raw 64-bit argument cells [nargs, n] for a lane group (one fancy
    gather, no per-lane loop)."""
    n = int(lanes.size)
    if nargs == 0:
        return np.zeros((0, n), np.int64)
    rows = np.asarray(fp[lanes], np.int64)[None, :] + \
        np.arange(nargs, dtype=np.int64)[:, None]
    lo = stack_lo[rows, lanes[None, :]].view(np.uint32).astype(np.uint64)
    hi = stack_hi[rows, lanes[None, :]].view(np.uint32).astype(np.uint64)
    return (lo | (hi << np.uint64(32))).view(np.int64)


def _stdout_cursor(engine, lanes: int):
    """Per-lane stdout stream cursor backing exactly-once flushing
    across restores (ROADMAP r7 open item).

    `pos[lane]` is the lane's LOGICAL stream position: total payload
    bytes its tier-0 fd_write records have reached in this run's
    deterministic replay order.  `hw[lane]` is the high-water mark of
    bytes actually written to the host fds.  A restore rewinds `pos`
    (checkpoint.load journals it; a restore to the initial state zeroes
    it) while `hw` survives on the engine — so replayed records are
    skipped up to the high-water mark instead of re-written."""
    cur = getattr(engine, "_stdout_cursor", None)
    if cur is None or cur[0].size != lanes:
        cur = (np.zeros(lanes, np.int64), np.zeros(lanes, np.int64))
        engine._stdout_cursor = cur
    return cur


def stdout_cursor_reset(engine, keep_highwater: bool = False):
    """Reset the logical stream position (a fresh run, or a restore to
    the initial state).  `keep_highwater=True` preserves the written
    high-water mark so a from-scratch REPLAY of the same run suppresses
    output it already flushed; False starts a genuinely new stream."""
    cur = getattr(engine, "_stdout_cursor", None)
    if cur is None:
        return
    cur[0][:] = 0
    if not keep_highwater:
        cur[1][:] = 0


def _tap_tier1_stdout(eff, engine, cache, slab_lo, slab_hi, fp, pages,
                      lanes, max_pages, plane_cap):
    """Mirror a tier-1 fd_write group's fd-1 bytes into the owning
    requests' stream buffers (effects/stream.py) before the host drain
    writes the real fds.

    Concatenated multi-module images carry no t0kind plane
    (batch/multitenant.py), so a gateway guest's stdout arrives HERE
    rather than through the tier-0 record buffer.  The same per-lane
    logical cursor advances (parked sessions journal it as stdout_pos,
    checkpoints carry it), and the same high-water mark suppresses
    re-streaming a restored round's deterministic replay — one cursor,
    whichever tier carried the bytes."""
    pos, hw = _stdout_cursor(engine, int(np.asarray(fp).size))
    for lane in lanes:
        base = int(fp[lane])

        def arg(i):
            lo = int(np.uint32(slab_lo[base + i, int(lane)]))
            hi = int(np.uint32(slab_hi[base + i, int(lane)]))
            return lo | (hi << 32)

        if (arg(0) & MASK32) != 1:
            continue
        mem = _CachedLaneMemory(cache, int(lane), int(pages[lane]),
                                max_pages, plane_cap)
        try:
            iovs = arg(1) & MASK32
            n = arg(2) & MASK32
            mem.check_bounds(iovs, 8 * n)
            data = b""
            for k in range(n):
                buf = mem.load(iovs + 8 * k, 4, False)
                ln = mem.load((iovs + 8 * k + 4) & MASK32, 4, False)
                if ln:
                    data += mem.load_bytes(buf & MASK32, ln)
        except TrapError:
            continue   # malformed iovs: the host fn reports the errno
        if not data:
            continue
        p = int(pos[lane])
        skip = min(max(int(hw[lane]) - p, 0), len(data))
        rid = eff.lane_rids.get(int(lane))
        if rid is not None and skip < len(data):
            eff.stream_append(rid, p + skip, data[skip:])
        pos[lane] = p + len(data)
        hw[lane] = max(int(hw[lane]), p + len(data))


def flush_stdout_buffers(engine, state):
    """Drain the tier-0 in-device stdout record buffers to the WASI
    environ's fds (one download, one write per fd) and reset the
    per-lane offsets.  Runs at harvest and before any tier-1 serve so
    per-lane output ordering is preserved.

    Exactly-once across restores: each lane's records advance a logical
    stream cursor; bytes at positions below the engine's written
    high-water mark are a deterministic replay of output a previous
    attempt already flushed and are skipped (see _stdout_cursor).  The
    guarantee assumes deterministic payloads — a guest that embeds
    wall-clock values in its output regenerates different bytes and the
    suppression degrades to at-least-once for the replayed window."""
    if getattr(state, "so_buf", None) is None:
        return state
    so_off = np.asarray(state.so_off)
    if not (so_off > 0).any():
        return state
    import jax.numpy as jnp

    buf = np.asarray(state.so_buf)
    env = wasi_env_of(engine)
    pos, hw = _stdout_cursor(engine, so_off.size)
    # r23 stream seam: fresh stdout record bytes also feed the owning
    # request's StreamBuf (effects/stream.py) with their logical stream
    # position, so gateway /stream subscribers follow the same
    # exactly-once cursor the host fds do
    eff = getattr(engine, "_effects", None)
    per_fd = {}
    nbytes = 0
    for lane in np.nonzero(so_off > 0)[0]:
        end = int(so_off[lane])
        col = buf[:end, lane]
        p = int(pos[lane])
        h = int(hw[lane])
        off = 0
        while off < end:
            hdr = int(np.uint32(col[off]))
            fd = hdr >> 28
            ln = hdr & 0x0FFFFFFF
            nw = (ln + 3) // 4
            skip = min(max(h - p, 0), ln)
            if skip < ln:
                data = np.ascontiguousarray(
                    col[off + 1:off + 1 + nw]).tobytes()[:ln]
                per_fd.setdefault(fd, []).append(data[skip:])
                nbytes += ln - skip
                if eff is not None and fd == 1:
                    rid = eff.lane_rids.get(int(lane))
                    if rid is not None:
                        eff.stream_append(rid, p + skip, data[skip:])
            p += ln
            off += 1 + nw
        pos[lane] = p
        hw[lane] = max(h, p)
    from wasmedge_tpu.host.wasi.vectorized import _write_all

    for fd in sorted(per_fd):
        e = env.fds.get(fd) if env is not None else None
        if e is None or e.os_fd < 0:
            continue  # fd vanished (tier-0 gating makes this unreachable)
        data = b"".join(per_fd[fd])
        if data:
            env.bytes_written += _write_all(e, data)
    stats = getattr(engine, "hostcall_stats", None)
    if stats is not None:
        stats["stdout_flushes"] += 1
        stats["stdout_bytes"] += nbytes
    return state._replace(so_off=jnp.zeros_like(state.so_off))


def serve_batch_state(engine, state):
    """Serve all TRAP_HOSTCALL lanes of a SIMT BatchState; returns the
    updated state (device arrays refreshed only where touched).

    Tier-1 vectorized drain: lanes are grouped by hostcall id and each
    group with a SoA implementation (host/wasi/vectorized.py) is served
    in one vectorized call over the memory plane — no per-lane 64 KiB
    materialization.  Groups without one (custom host functions,
    sockets, oversized iovec arrays) fall back to the per-lane loop,
    itself backed by the same chunked cache (no full-plane copies).

    Transfer discipline: argument rows ride as ONE slab download,
    guest memory as 4 KiB-row all-lane chunks fetched on touch and
    written back dirty-only, results/trap/sp/pc as row/vector updates —
    never a whole [W, lanes] plane round trip per serve."""
    import jax.numpy as jnp

    from wasmedge_tpu.batch.image import TRAP_HOSTCALL
    from wasmedge_tpu.host.wasi.vectorized import NotVectorizable

    img = engine.img
    trap = np.asarray(state.trap)
    waiting = np.nonzero(trap == TRAP_HOSTCALL)[0]
    if waiting.size == 0:
        return state
    # buffered tier-0 stdout must land before any tier-1 call can
    # observe fd state (per-lane write ordering)
    state = flush_stdout_buffers(engine, state)
    stats = getattr(engine, "hostcall_stats", None)
    if stats is not None:
        stats["serve_rounds"] += 1
        stats["tier1_calls"] += int(waiting.size)
    pc = np.asarray(state.pc)
    fp = np.asarray(state.fp)
    opbase = np.asarray(state.opbase)
    sp = np.asarray(state.sp).copy()
    pages = np.asarray(state.mem_pages).copy()
    has_mem = img.has_memory
    cache = PlaneMemoryCache(state.mem) if has_mem else None
    plane_cap = (int(state.mem.shape[0]) // (65536 // 4)) if has_mem else 0
    max_pages = img.mem_pages_max if img.mem_pages_max > 0 else None
    new_trap = trap.copy()
    new_pc = pc.copy()
    use_vec = bool(getattr(engine.cfg, "vectorized_hostcalls", True))

    ks = img.a[pc[waiting]]
    nargs_by_k = {int(k): len(engine.resolve_func(int(k)).functype.params)
                  for k in np.unique(ks)}
    nargs_arr = np.array([nargs_by_k[int(k)] for k in ks], np.int64)
    max_row = int((fp[waiting] + nargs_arr).max(initial=0))
    slab_lo = np.asarray(state.stack_lo[:max_row]) if max_row else \
        np.zeros((0, trap.size), np.int32)
    slab_hi = np.asarray(state.stack_hi[:max_row]) if max_row else \
        np.zeros((0, trap.size), np.int32)

    obs = getattr(engine, "obs", None)
    # per-kind drain-latency seam: vectorized implementations time
    # themselves (host/wasi/vectorized.py), the per-lane fallback is
    # timed below; restored after the group loop even when a host
    # function raises mid-drain
    from wasmedge_tpu.host.wasi.vectorized import set_drain_recorder

    prev_rec = set_drain_recorder(obs)
    stack_sets = []  # (rows [nres, n], lanes [n], lo [nres, n], hi)
    # r23 effect lowering: blocking hostcalls (await_event, pure-clock
    # poll_oneoff) either complete from pending wake state or mark
    # their lane TRAP_PARKED for the boundary park — either way they
    # leave the normal host drain below
    eff = getattr(engine, "_effects", None)
    if eff is not None:
        consumed = eff.intercept(engine, waiting, ks, slab_lo, slab_hi,
                                 fp, pc, opbase, sp, cache, new_trap,
                                 new_pc, stack_sets)
        if consumed:
            keep = np.array([int(lane) not in consumed
                             for lane in waiting], bool)
            waiting = waiting[keep]
            ks = ks[keep]
    try:
        for k in np.unique(ks):
            lanes = waiting[ks == k]
            fi = engine.resolve_func(int(k))
            nargs = nargs_by_k[int(k)]
            if eff is not None and has_mem and nargs >= 3 \
                    and getattr(getattr(fi, "host", None), "name",
                                None) == "fd_write":
                _tap_tier1_stdout(eff, engine, cache, slab_lo, slab_hi,
                                  fp, pages, lanes, max_pages,
                                  plane_cap)
            cells = codes = None
            if use_vec and has_mem and getattr(fi, "kind", None) == "host":
                vecfn, env = vec_impl_for(fi)
                if vecfn is not None:
                    args = gather_arg_cells(slab_lo, slab_hi, fp, lanes,
                                            nargs)
                    view = make_cached_view(cache, lanes, pages[lanes])
                    try:
                        cells, codes = vecfn(env, view, args)
                    except NotVectorizable:
                        cells = codes = None
            if cells is not None:
                if stats is not None:
                    stats["tier1_vectorized"] += int(lanes.size)
                ok = codes == 0
                okl = lanes[ok]
                nres = cells.shape[0]
                if okl.size and nres:
                    cu = cells[:, ok].astype(np.uint64)
                    obk = np.asarray(opbase[okl], np.int64)
                    rows = obk[None, :] + np.arange(nres,
                                                    dtype=np.int64)[:, None]
                    lo_v = (cu & np.uint64(MASK32)).astype(
                        np.uint32).view(np.int32)
                    hi_v = (cu >> np.uint64(32)).astype(
                        np.uint32).view(np.int32)
                    stack_sets.append((rows, okl, lo_v, hi_v))
                sp[okl] = opbase[okl] + nres
                new_trap[lanes] = np.where(ok, 0, codes)
                new_pc[okl] = pc[okl] + 1  # resume at the stub's RETURN
                continue
            # ---- per-lane fallback (chunk-cached lane memory views) ----
            # restart the drain timer: the histogram's vectorized=False
            # observation must measure the fallback loop alone, not a
            # failed NotVectorizable attempt above it
            t_drain = obs.now() if obs is not None else 0.0
            g_rows, g_lanes, g_lo, g_hi = [], [], [], []
            for lane in lanes:
                base = int(fp[lane])
                args1 = []
                for i in range(nargs):
                    lo = int(np.uint32(slab_lo[base + i, lane]))
                    hi = int(np.uint32(slab_hi[base + i, lane]))
                    args1.append(lo | (hi << 32))
                lane_mem = None
                if has_mem:
                    lane_mem = _CachedLaneMemory(
                        cache, int(lane), int(pages[lane]), max_pages,
                        plane_cap)
                out, code = serve_one(fi, args1, lane_mem)
                if code:
                    new_trap[lane] = code
                    continue
                ob = int(opbase[lane])
                for i, cell in enumerate(out):
                    g_rows.append(ob + i)
                    g_lanes.append(int(lane))
                    g_lo.append(np.int32(np.uint32(cell & MASK32)))
                    g_hi.append(np.int32(np.uint32((cell >> 32) & MASK32)))
                sp[lane] = ob + len(out)
                if has_mem:
                    pages[lane] = lane_mem.pages  # host fn may have grown
                new_trap[lane] = 0
                new_pc[lane] = pc[lane] + 1  # resume at the stub's RETURN
            if obs is not None and obs.enabled:
                obs.hostcall(hostcall_kind(fi), obs.now() - t_drain,
                             lanes=int(lanes.size), vectorized=False)
            if g_rows:
                stack_sets.append((np.asarray(g_rows, np.int64)[None, :],
                                   np.asarray(g_lanes, np.int64),
                                   np.asarray(g_lo, np.int32)[None, :],
                                   np.asarray(g_hi, np.int32)[None, :]))

    finally:
        set_drain_recorder(prev_rec)
    new_stack_lo = state.stack_lo
    new_stack_hi = state.stack_hi
    for rows, lanes_w, lo_v, hi_v in stack_sets:
        rj = jnp.asarray(rows)
        lj = jnp.asarray(np.broadcast_to(lanes_w[None, :], rows.shape))
        new_stack_lo = new_stack_lo.at[rj, lj].set(jnp.asarray(lo_v))
        new_stack_hi = new_stack_hi.at[rj, lj].set(jnp.asarray(hi_v))
    kw = dict(
        pc=jnp.asarray(new_pc), sp=jnp.asarray(sp),
        trap=jnp.asarray(new_trap),
        stack_lo=new_stack_lo, stack_hi=new_stack_hi,
    )
    if has_mem:
        kw["mem"] = cache.flush()  # dirty chunks only
        kw["mem_pages"] = jnp.asarray(pages)
    return state._replace(**kw)


# ---------------------------------------------------------------------------
# round-cached serving: vectorized memory views over the device plane
# ---------------------------------------------------------------------------
class PlaneMemoryCache:
    """Row-chunked host cache over a device [W, lanes] memory plane for
    one serve round.

    The host link (every transfer pays a fixed latency, however small)
    must never carry per-lane traffic: chunks of guest memory are
    downloaded for ALL lanes at once (one transfer per touched 4 KiB window, however
    many lanes read it), per-lane views slice columns out of the cached
    slabs, and dirty chunks are written back in one device update per
    chunk at flush.  A serve round that only READS guest memory (the
    common WASI shape: fd_write, path_open, clock, random) uploads
    nothing at all."""

    CHUNK_ROWS = 1024  # 4 KiB of guest memory per chunk

    def __init__(self, mem_dev, d2h=None, read_rows=None):
        self.dev = mem_dev
        self.W = int(mem_dev.shape[0])
        self.L = int(mem_dev.shape[1])
        # how a chunk comes down: `HostLink.d2h` where the serve is on a
        # link (the Pallas block serve), else a plain download
        self._d2h = d2h
        # read_rows(w0, k, lane_major) -> rows [w0, w0 + k) of every
        # lane cut (and transposed) on the device, [k, L] or [L, k]: the
        # way around the chunks for an access at ONE address in every
        # lane (the Pallas block serve); None: chunks only
        self._read_rows = read_rows
        self._chunks = {}
        self._dirty = {}     # chunk -> [first, last + 1) rows written
        self._patches = {}   # row -> int32[L] written whole, chunk not here
        self._writes = {}    # lane -> [(off, n)] for pad-lane replay
        self._vec_writes = []  # (lanes, offs, n) of the vector stores

    def _chunk(self, ci: int, write: bool = False) -> np.ndarray:
        c = self._chunks.get(ci)
        if c is None:
            lo = ci * self.CHUNK_ROWS
            hi = min(lo + self.CHUNK_ROWS, self.W)
            # one all-lane download; a backend may hand out its own
            # read-only buffer, copied only if the round writes to it
            c = self._chunks[ci] = \
                np.asarray(self.dev[lo:hi, :]) if self._d2h is None \
                else self._d2h("mem_chunk", self.dev, np.s_[lo:hi])
            for r in [r for r in self._patches if lo <= r < hi]:
                # a row written whole before its chunk was here
                write = True
                self._touch(ci, r - lo, r - lo + 1)
                self._own(ci)[r - lo] = self._patches.pop(r)
        return self._own(ci) if write else self._chunks[ci]

    def _own(self, ci: int) -> np.ndarray:
        """Chunk `ci` as the cache's own, writable copy."""
        c = self._chunks[ci]
        if not (c.flags.owndata and c.flags.writeable):
            c = self._chunks[ci] = c.copy()
        return c

    def _touch(self, ci: int, lo: int, hi: int):
        """Rows [lo, hi) of chunk `ci` were written."""
        d = self._dirty.get(ci)
        self._dirty[ci] = (lo, hi) if d is None \
            else (min(d[0], lo), max(d[1], hi))

    def _chunks_of(self, w0: int, k: int):
        return range(w0 // self.CHUNK_ROWS,
                     (w0 + k - 1) // self.CHUNK_ROWS + 1)

    def row_span(self, w0: int, k: int, lane_major: bool = False):
        """Rows [w0, w0 + k) of every lane: [k, L], or [L, k]
        contiguous.  Cut on the device where that is offered, the rows'
        chunks are not all here already and none of them was written
        this round (a patched row is laid over what comes down); out of
        the chunks otherwise."""
        covering = self._chunks_of(w0, k)
        if self._read_rows is not None \
                and not all(ci in self._chunks for ci in covering) \
                and not any(ci in self._dirty for ci in covering):
            out = self._read_rows(w0, k, lane_major)
            patched = [r for r in self._patches if w0 <= r < w0 + k]
            if patched:     # rows written whole this round lie over it
                out = np.array(out)
                for r in patched:
                    out[(slice(None), r - w0) if lane_major
                        else r - w0] = self._patches[r]
            return out
        out = np.empty((self.L, k) if lane_major else (k, self.L),
                       np.int32)
        cr = self.CHUNK_ROWS
        for ci in covering:
            chunk = self._chunk(ci)
            a = max(w0, ci * cr)
            b = min(w0 + k, ci * cr + chunk.shape[0])
            rows = chunk[a - ci * cr:b - ci * cr]
            if lane_major:
                out[:, a - w0:b - w0] = rows.T
            else:
                out[a - w0:b - w0] = rows
        return out

    def store_words(self, widx, vals, lanes):
        """Scatter int32 words: plane[widx[r, j], lanes[j]] = vals[r, j]
        for widx, vals [k, m], lanes [m] (one numpy assignment a
        touched chunk, not a call a lane).  Where every lane stores at
        ONE address and its chunk is not here, the rows are kept whole
        as patches and no chunk comes down for them."""
        cr = self.CHUNK_ROWS
        lanes = np.asarray(lanes, np.int64)
        k = widx.shape[0]
        self._vec_writes.append((lanes, 4 * widx[0], 4 * k))
        w0 = int(widx[0, 0])
        if self._read_rows is not None and w0 + k <= self.W \
                and bool((widx == widx[:, :1]).all()) \
                and not any(ci in self._chunks
                            for ci in self._chunks_of(w0, k)):
            whole = lanes.size == self.L and \
                bool((lanes == np.arange(self.L)).all())
            for i in range(k):
                row = self._patches.get(w0 + i)
                if row is None and not whole:
                    row = np.array(self._read_rows(w0 + i, 1, False)[0])
                if row is None:
                    row = np.array(vals[i], np.int32)
                else:
                    row[lanes] = vals[i]
                self._patches[w0 + i] = row
            return
        cis = widx // cr
        cols = np.broadcast_to(lanes[None, :], widx.shape)
        for ci in np.unique(cis):
            ci = int(ci)
            m = cis == ci
            rows = widx[m] - ci * cr
            self._chunk(ci, write=True)[rows, cols[m]] = vals[m]
            self._touch(ci, int(rows.min()), int(rows.max()) + 1)

    def dirty_rows(self):
        """[(first plane row, int32[n, L])] to write back: each dirty
        chunk's written rows, widened to an aligned power-of-two count
        (so the program that sets them compiles for few shapes), and
        each run of patched rows as it is."""
        out = []
        for ci in sorted(self._dirty):
            lo, hi = self._dirty[ci]
            n = 1
            while (lo // n) * n + n < hi:
                n *= 2
            chunk = self._chunks[ci]
            n = min(n, chunk.shape[0])
            a = (lo // n) * n
            out.append((ci * self.CHUNK_ROWS + a, chunk[a:a + n]))
        rows = np.array(sorted(self._patches), np.int64)
        for run in np.split(rows, np.flatnonzero(np.diff(rows) != 1) + 1):
            if run.size:
                out.append((int(run[0]), np.stack(
                    [self._patches[int(r)] for r in run])))
        return out

    def read_bytes(self, lane: int, off: int, n: int) -> bytes:
        if n == 0:
            return b""
        w0 = off // 4
        w1 = (off + n - 1) // 4
        words = np.empty(w1 - w0 + 1, np.int32)
        w = w0
        while w <= w1:
            ci = w // self.CHUNK_ROWS
            base = ci * self.CHUNK_ROWS
            chunk = self._chunk(ci)
            upto = min(w1 + 1, base + chunk.shape[0])
            words[w - w0:upto - w0] = chunk[w - base:upto - base, lane]
            w = upto
        raw = words.tobytes()
        start = off - w0 * 4
        return raw[start:start + n]

    def writes_of(self, lane: int):
        """(off, n) write extents recorded for a lane this round."""
        out = list(self._writes.get(lane, ()))
        for lanes, offs, n in self._vec_writes:
            out += [(int(o), n) for o in offs[lanes == lane]]
        return out

    def write_bytes(self, lane: int, off: int, data: bytes):
        n = len(data)
        if n == 0:
            return
        self._writes.setdefault(lane, []).append((off, n))
        w0 = off // 4
        w1 = (off + n - 1) // 4
        cur = bytearray(self.read_bytes(lane, w0 * 4,
                                        (w1 - w0 + 1) * 4))
        start = off - w0 * 4
        cur[start:start + n] = data
        words = np.frombuffer(bytes(cur), dtype=np.int32)
        w = w0
        while w <= w1:
            ci = w // self.CHUNK_ROWS
            base = ci * self.CHUNK_ROWS
            chunk = self._chunk(ci, write=True)
            upto = min(w1 + 1, base + chunk.shape[0])
            chunk[w - base:upto - base, lane] = words[w - w0:upto - w0]
            self._touch(ci, w - base, upto - base)
            w = upto

    def flush(self):
        """Apply dirty chunks device-side; returns the updated array."""
        assert not self._patches    # only with read_rows, not here
        dev = self.dev
        for ci in sorted(self._dirty):
            lo = ci * self.CHUNK_ROWS
            chunk = self._chunks[ci]
            dev = dev.at[lo:lo + chunk.shape[0], :].set(chunk)
        self._dirty.clear()
        return dev


class _CachedLaneMemory(MemoryInstance):
    """MemoryInstance view over one lane's column of a PlaneMemoryCache.

    Byte accesses hit the cache's all-lane slabs; `page_limit` is the
    plane's row capacity, so in-place growth stays inside the
    allocation (rows beyond the current page count are zero)."""

    def __init__(self, cache: PlaneMemoryCache, lane: int, pages: int,
                 max_pages: Optional[int], page_limit: int):
        self._cache = cache
        self._lane = lane
        self._pages = pages
        self.min = pages
        self.max = max_pages
        self.page_limit = page_limit

    @property
    def pages(self) -> int:
        return self._pages

    def _nbytes(self) -> int:
        return self._pages * 65536

    def check_bounds(self, off: int, length: int):
        if off < 0 or off + length > self._nbytes():
            raise TrapError(ErrCode.MemoryOutOfBounds)

    def grow(self, delta: int) -> int:
        old = self._pages
        new = old + delta
        # KNOWN ENGINE DIVERGENCE: growth past the plane's row capacity
        # (page_limit, watermark-sized = mem_pages_init) fails with -1
        # here, while the same grow issued from *guest* code gets
        # ST_REGROW and re-executes on a bigger-plane engine, and the
        # SIMT/scalar engines succeed up to the declared max.  Spec-legal
        # (memory.grow may fail at any size) and covered by
        # tests/test_hostcall.py; routing host-driven growth through the
        # ST_REGROW handoff would require parking the whole block
        # mid-serve.  Revisit if a real WASI workload hits it.
        limit = self.page_limit
        if self.max is not None:
            limit = min(limit, self.max)
        if delta < 0 or new > limit or new > 65536:
            return -1
        self._pages = new
        return old

    def load(self, off: int, nbytes: int, signed: bool) -> int:
        self.check_bounds(off, nbytes)
        return int.from_bytes(
            self._cache.read_bytes(self._lane, off, nbytes), "little",
            signed=signed)

    def store(self, off: int, nbytes: int, value: int):
        self.check_bounds(off, nbytes)
        self._cache.write_bytes(
            self._lane, off,
            (value & ((1 << (8 * nbytes)) - 1)).to_bytes(nbytes, "little"))

    def load_bytes(self, off: int, n: int) -> bytes:
        self.check_bounds(off, n)
        return self._cache.read_bytes(self._lane, off, n)

    def store_bytes(self, off: int, data: bytes):
        self.check_bounds(off, len(data))
        self._cache.write_bytes(self._lane, off, bytes(data))

    def as_numpy(self) -> np.ndarray:
        return np.frombuffer(
            self._cache.read_bytes(self._lane, 0, self._nbytes()),
            dtype=np.uint8)


def make_cached_view(cache: PlaneMemoryCache, lanes, pages):
    """MemView over a PlaneMemoryCache for the Pallas block serve: word
    gathers assemble from the cache's 4 KiB all-lane chunks
    (download-on-touch); byte stores go through cache.write_bytes so
    dirty-chunk flushing and pad-lane write replay keep working."""
    from wasmedge_tpu.host.wasi.vectorized import MemView

    class _CachedPlaneView(MemView):
        def __init__(self):
            super().__init__(lanes, pages)
            self.cache = cache
            # the view's lanes are the plane's columns, in order
            self._every_lane = self.n == cache.L and bool(
                (self.lanes == np.arange(cache.L)).all())

        def _words(self, widx):
            widx = np.clip(np.asarray(widx, np.int64), 0, cache.W - 1)
            out = np.empty(widx.shape, np.int32)
            cr = PlaneMemoryCache.CHUNK_ROWS
            cis = widx // cr
            cols = np.broadcast_to(self.lanes[None, :], widx.shape) \
                if widx.ndim == 2 else self.lanes
            for ci in np.unique(cis):
                chunk = cache._chunk(int(ci))
                m = cis == ci
                out[m] = chunk[widx[m] - int(ci) * cr, cols[m]]
            return out

        def _row_span(self, w0, k, lane_major=False):
            if w0 + k > cache.W:    # past the plane: clipped, as _words
                return super()._row_span(w0, k, lane_major)
            out = cache.row_span(w0, k, lane_major)
            if self._every_lane:
                return out
            return np.ascontiguousarray(out[self.lanes]) if lane_major \
                else out[:, self.lanes]

        def _store_words(self, widx, vals, sel):
            cache.store_words(widx, vals, self.lanes[sel])

        def _store_bytes_one(self, i, off, data):
            cache.write_bytes(int(self.lanes[i]), off, bytes(data))

    return _CachedPlaneView()
