"""Uniform-mode engine: converged-lane lockstep with scalar control state.

When every lane runs the same module from the same entry with data that
resolves branches identically (BASELINE config 1/2: N copies of fib(30) /
CoreMark), pc/sp/fp/call_depth are lane-uniform. This engine keeps them as
*scalars*: instruction fetch is a scalar table read, dispatch is a scalar
`lax.switch` (one handler per step, not all handlers masked), and every
stack/memory access is a `dynamic_slice` / `dynamic_update_slice` row op of
[lanes] elements — the access pattern the TPU loves, no gathers at all.

Divergence (a data-dependent branch or trap disagreeing across lanes) is
detected on-device; the engine stops with `diverged=1` and the host falls
back to the SIMT engine (batch/engine.py), which shares the same state
layout. This is the PC-voting design from SURVEY.md §7 step 4 with vote =
"all lanes agree or bail".

Per-lane *data* still diverges freely (different args are fine as long as
branches resolve the same way); per-lane traps are only divergence when
they differ across lanes.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from wasmedge_tpu.common.errors import ErrCode
from wasmedge_tpu.batch.image import (
    ALU1_SUB,
    CLS_ALU1,
    CLS_ALU2,
    CLS_BR,
    CLS_BR_TABLE,
    CLS_BRNZ,
    CLS_BRZ,
    CLS_CALL,
    CLS_CALL_INDIRECT,
    CLS_CONST,
    CLS_DROP,
    CLS_GLOBAL_GET,
    CLS_GLOBAL_SET,
    CLS_HOSTCALL,
    CLS_LOAD,
    CLS_LOCAL_GET,
    CLS_LOCAL_SET,
    CLS_LOCAL_TEE,
    CLS_MEMCOPY,
    CLS_MEMFILL,
    CLS_MEMGROW,
    CLS_MEMSIZE,
    CLS_NOP,
    CLS_RETURN,
    CLS_SELECT,
    CLS_STORE,
    CLS_TRAP,
    NUM_CLASSES,
    TRAP_DONE,
    DeviceImage,
    _F32_BIN,
    _I32_BIN,
    ALU2_I32_BASE,
    ALU2_I64_BASE,
    ALU2_F32_BASE,
)


class UniformState(NamedTuple):
    # scalar (lane-uniform) control
    pc: object
    sp: object
    fp: object
    opbase: object
    call_depth: object
    status: object  # 0 running, 1 done, 2 diverged->SIMT, >2 trap code+16
    steps: object
    mem_pages: object
    # vector data planes
    stack_lo: object  # [D, L]
    stack_hi: object
    fr_ret_pc: object  # [CD] scalar frames! (uniform control)
    fr_fp: object
    fr_opbase: object
    glob_lo: object  # [NG, L]
    glob_hi: object
    mem: object  # [W, L]
    trap: object  # [L] per-lane pending trap (uniform or lane diverges)
    # tier-0 hostcall planes (same discipline as BatchState; present
    # only when the engine services tier-0 in-kernel).  A divergence
    # handoff carries them INTO the SIMT state — calls already retired
    # here must not lose their buffered output or counter positions.
    t0_ctr: object = None   # [4, L]
    so_buf: object = None   # [SW, L]
    so_off: object = None   # [L]


ST_RUNNING = 0
ST_DONE = 1
ST_DIVERGED = 2
ST_TRAPPED_BASE = 16  # status = 16 + ErrCode when ALL lanes trap identically


def make_uniform_step(img: DeviceImage, cfg, lanes: int, t0kinds=None):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from wasmedge_tpu.batch import laneops as lo_ops

    I32 = jnp.int32
    D = cfg.value_stack_depth
    CD = cfg.call_stack_depth

    cls_t = jnp.asarray(img.cls)
    sub_t = jnp.asarray(img.sub)
    a_t = jnp.asarray(img.a)
    b_t = jnp.asarray(img.b)
    c_t = jnp.asarray(img.c)
    ilo_t = jnp.asarray(img.imm_lo)
    ihi_t = jnp.asarray(img.imm_hi)
    brt_t = jnp.asarray(img.br_table)
    f_entry = jnp.asarray(img.f_entry)
    f_nparams = jnp.asarray(img.f_nparams)
    f_nlocals = jnp.asarray(img.f_nlocals)
    f_frame_top = jnp.asarray(img.f_frame_top)
    f_type = jnp.asarray(img.f_type)
    table0 = jnp.asarray(img.table0)

    S_I32 = {n: ALU2_I32_BASE + i for i, n in enumerate(_I32_BIN)}
    S_I64 = {n: ALU2_I64_BASE + i for i, n in enumerate(_I32_BIN)}
    S_F32 = {n: ALU2_F32_BASE + i for i, n in enumerate(_F32_BIN)}
    A1 = ALU1_SUB
    b2i = lo_ops.b2i
    u_lt = lo_ops.u_lt

    def row(plane, i):
        """plane[i] via dynamic_slice (scalar i) -> [L]."""
        i = jnp.clip(i, 0, plane.shape[0] - 1)
        return lax.dynamic_slice_in_dim(plane, i, 1, 0)[0]

    def setrow(plane, i, vals):
        i = jnp.clip(i, 0, plane.shape[0] - 1)
        return lax.dynamic_update_slice_in_dim(plane, vals[None, :], i, 0)

    def sget(arr, i):
        i = jnp.clip(i, 0, arr.shape[0] - 1)
        return lax.dynamic_slice_in_dim(arr, i, 1, 0)[0]

    def sset(arr, i, v):
        i = jnp.clip(i, 0, arr.shape[0] - 1)
        return lax.dynamic_update_slice_in_dim(arr, v[None], i, 0)

    def halt(st, status):
        return st._replace(status=status)

    # ---------------- class handlers (each: (st, fetch) -> st) -----------
    # fetch = (sub, a, b, c, ilo, ihi) scalars

    def h_nop(st, f):
        return st._replace(pc=st.pc + 1)

    def h_const(st, f):
        sub, a, b, c, ilo, ihi = f
        sl = setrow(st.stack_lo, st.sp, jnp.full((lanes,), ilo, I32))
        sh = setrow(st.stack_hi, st.sp, jnp.full((lanes,), ihi, I32))
        return st._replace(pc=st.pc + 1, sp=st.sp + 1, stack_lo=sl, stack_hi=sh)

    def h_local_get(st, f):
        sub, a, b, c, ilo, ihi = f
        vl = row(st.stack_lo, st.fp + a)
        vh = row(st.stack_hi, st.fp + a)
        sl = setrow(st.stack_lo, st.sp, vl)
        sh = setrow(st.stack_hi, st.sp, vh)
        return st._replace(pc=st.pc + 1, sp=st.sp + 1, stack_lo=sl, stack_hi=sh)

    def h_local_set(st, f):
        sub, a, b, c, ilo, ihi = f
        vl = row(st.stack_lo, st.sp - 1)
        vh = row(st.stack_hi, st.sp - 1)
        sl = setrow(st.stack_lo, st.fp + a, vl)
        sh = setrow(st.stack_hi, st.fp + a, vh)
        return st._replace(pc=st.pc + 1, sp=st.sp - 1, stack_lo=sl, stack_hi=sh)

    def h_local_tee(st, f):
        sub, a, b, c, ilo, ihi = f
        vl = row(st.stack_lo, st.sp - 1)
        vh = row(st.stack_hi, st.sp - 1)
        sl = setrow(st.stack_lo, st.fp + a, vl)
        sh = setrow(st.stack_hi, st.fp + a, vh)
        return st._replace(pc=st.pc + 1, stack_lo=sl, stack_hi=sh)

    def h_global_get(st, f):
        sub, a, b, c, ilo, ihi = f
        vl = row(st.glob_lo, a)
        vh = row(st.glob_hi, a)
        sl = setrow(st.stack_lo, st.sp, vl)
        sh = setrow(st.stack_hi, st.sp, vh)
        return st._replace(pc=st.pc + 1, sp=st.sp + 1, stack_lo=sl, stack_hi=sh)

    def h_global_set(st, f):
        sub, a, b, c, ilo, ihi = f
        vl = row(st.stack_lo, st.sp - 1)
        vh = row(st.stack_hi, st.sp - 1)
        gl = setrow(st.glob_lo, a, vl)
        gh = setrow(st.glob_hi, a, vh)
        return st._replace(pc=st.pc + 1, sp=st.sp - 1, glob_lo=gl, glob_hi=gh)

    def h_drop(st, f):
        return st._replace(pc=st.pc + 1, sp=st.sp - 1)

    def h_select(st, f):
        cond = row(st.stack_lo, st.sp - 1)
        v1l = row(st.stack_lo, st.sp - 2)
        v1h = row(st.stack_hi, st.sp - 2)
        v2l = row(st.stack_lo, st.sp - 3)
        v2h = row(st.stack_hi, st.sp - 3)
        rl = jnp.where(cond == 0, v1l, v2l)
        rh = jnp.where(cond == 0, v1h, v2h)
        sl = setrow(st.stack_lo, st.sp - 3, rl)
        sh = setrow(st.stack_hi, st.sp - 3, rh)
        return st._replace(pc=st.pc + 1, sp=st.sp - 2, stack_lo=sl, stack_hi=sh)

    A2 = lo_ops.alu2_fns()

    def _alu_result(sub, xl, xh, yl, yh):
        """Scalar-sub dispatch over the shared ALU table (laneops.alu2_fns,
        the single source of ALU semantics for all batch engines)."""
        n_subs = max(A2) + 1

        def mk(i):
            f = A2.get(i)
            if f is None:
                return lambda: (xl, xh)
            return lambda: f(xl, xh, yl, yh)

        fns = [mk(i) for i in range(n_subs)]
        return lax.switch(jnp.clip(sub, 0, n_subs - 1), fns)

    def h_alu2(st, f):
        sub, a, b, c, ilo, ihi = f
        xl = row(st.stack_lo, st.sp - 2)
        xh = row(st.stack_hi, st.sp - 2)
        yl = row(st.stack_lo, st.sp - 1)
        yh = row(st.stack_hi, st.sp - 1)
        rl, rh = _alu_result(sub, xl, xh, yl, yh)
        # div-by-zero / overflow traps (uniform check later via trap plane)
        is_div32 = (sub == S_I32["div_s"]) | (sub == S_I32["div_u"]) | \
            (sub == S_I32["rem_s"]) | (sub == S_I32["rem_u"])
        is_div64 = (sub == S_I64["div_s"]) | (sub == S_I64["div_u"]) | \
            (sub == S_I64["rem_s"]) | (sub == S_I64["rem_u"])
        dz = (is_div32 & (yl == 0)) | (is_div64 & ((yl | yh) == 0))
        ovf = ((sub == S_I32["div_s"]) & (xl == jnp.int32(-0x80000000)) & (yl == -1)) | \
              ((sub == S_I64["div_s"]) & (xl == 0) & (xh == jnp.int32(-0x80000000))
               & (yl == -1) & (yh == -1))
        lane_trap = jnp.where(dz, int(ErrCode.DivideByZero),
                              jnp.where(ovf, int(ErrCode.IntegerOverflow), 0))
        sl = setrow(st.stack_lo, st.sp - 2, rl)
        sh = setrow(st.stack_hi, st.sp - 2, rh)
        return st._replace(pc=st.pc + 1, sp=st.sp - 1, stack_lo=sl, stack_hi=sh,
                           trap=jnp.where(lane_trap != 0, lane_trap, st.trap))

    A1F = lo_ops.alu1_fns()
    A1T = lo_ops.alu1_trap_fns()

    def h_alu1(st, f):
        sub, a, b, c, ilo, ihi = f
        wl = row(st.stack_lo, st.sp - 1)
        wh = row(st.stack_hi, st.sp - 1)
        n_subs = max(A1F) + 1

        def mk(i):
            f1 = A1F.get(i)
            if f1 is None:
                return lambda: (wl, wh)
            return lambda: f1(wl, wh)

        fns = [mk(i) for i in range(n_subs)]
        rl, rh = lax.switch(jnp.clip(sub, 0, n_subs - 1), fns)

        def mk_trap(i):
            t1 = A1T.get(i)
            if t1 is None:
                return lambda: (jnp.zeros_like(wl) != 0, jnp.zeros_like(wl))
            return lambda: t1(wl, wh)

        tfns = [mk_trap(i) for i in range(n_subs)]
        bad, codes = lax.switch(jnp.clip(sub, 0, n_subs - 1), tfns)
        lane_trap = jnp.where(bad, codes, jnp.int32(0))
        sl = setrow(st.stack_lo, st.sp - 1, rl)
        sh = setrow(st.stack_hi, st.sp - 1, rh)
        return st._replace(pc=st.pc + 1, stack_lo=sl, stack_hi=sh,
                           trap=jnp.where(lane_trap != 0, lane_trap, st.trap))

    def h_br(st, f):
        sub, a, b, c, ilo, ihi = f
        vl = row(st.stack_lo, st.sp - 1)
        vh = row(st.stack_hi, st.sp - 1)
        tgt_sp = st.opbase + c
        sl = jnp.where(b == 1, setrow(st.stack_lo, tgt_sp, vl), st.stack_lo)
        sh = jnp.where(b == 1, setrow(st.stack_hi, tgt_sp, vh), st.stack_hi)
        return st._replace(pc=a, sp=tgt_sp + b, stack_lo=sl, stack_hi=sh)

    def h_brz(st, f):
        sub, a, b, c, ilo, ihi = f
        cond = row(st.stack_lo, st.sp - 1)
        taken = cond == 0
        return _uniform_branch(st, f, taken, a, keep=0, cut=False)

    def h_brnz(st, f):
        sub, a, b, c, ilo, ihi = f
        cond = row(st.stack_lo, st.sp - 1)
        taken = cond != 0
        return _uniform_branch(st, f, taken, a, keep=b, cut=True)

    def _uniform_branch(st, f, taken_vec, target, keep, cut):
        sub, a, b, c, ilo, ihi = f
        t0 = taken_vec[0]
        agree = jnp.all(taken_vec == t0)
        # kept value sits just below the popped condition
        vl = row(st.stack_lo, st.sp - 2)
        vh = row(st.stack_hi, st.sp - 2)
        sp_pop = st.sp - 1

        def take(st):
            if cut:
                tgt_sp = st.opbase + c
                sl = jnp.where(keep == 1, setrow(st.stack_lo, tgt_sp, vl),
                               st.stack_lo)
                sh = jnp.where(keep == 1, setrow(st.stack_hi, tgt_sp, vh),
                               st.stack_hi)
                return st._replace(pc=target, sp=tgt_sp + keep,
                                   stack_lo=sl, stack_hi=sh)
            return st._replace(pc=target, sp=sp_pop)

        def fall(st):
            return st._replace(pc=st.pc + 1, sp=sp_pop)

        new_st = lax.cond(t0, take, fall, st)
        return lax.cond(agree, lambda s: s,
                        lambda s: halt(st, jnp.int32(ST_DIVERGED)), new_st)

    def h_br_table(st, f):
        sub, a, b, c, ilo, ihi = f
        idx = row(st.stack_lo, st.sp - 1)
        i0 = idx[0]
        agree = jnp.all(idx == i0)
        ii = jnp.where(u_lt(b, i0), b, i0)
        e = jnp.clip(a + ii, 0, brt_t.shape[0] - 1)
        tgt = brt_t[e, 0]
        keep = brt_t[e, 1]
        pop_to = brt_t[e, 2]
        vl = row(st.stack_lo, st.sp - 2)
        vh = row(st.stack_hi, st.sp - 2)
        tgt_sp = st.opbase + pop_to
        sl = jnp.where(keep == 1, setrow(st.stack_lo, tgt_sp, vl), st.stack_lo)
        sh = jnp.where(keep == 1, setrow(st.stack_hi, tgt_sp, vh), st.stack_hi)
        new_st = st._replace(pc=tgt, sp=tgt_sp + keep, stack_lo=sl, stack_hi=sh)
        return lax.cond(agree, lambda s: s,
                        lambda s: halt(st, jnp.int32(ST_DIVERGED)), new_st)

    def h_return(st, f):
        sub, a, b, c, ilo, ihi = f
        vl = row(st.stack_lo, st.sp - 1)
        vh = row(st.stack_hi, st.sp - 1)
        sl = jnp.where(b == 1, setrow(st.stack_lo, st.fp, vl), st.stack_lo)
        sh = jnp.where(b == 1, setrow(st.stack_hi, st.fp, vh), st.stack_hi)
        done = st.call_depth == 0
        rd = jnp.clip(st.call_depth - 1, 0, CD - 1)
        r_pc = sget(st.fr_ret_pc, rd)
        r_fp = sget(st.fr_fp, rd)
        r_ob = sget(st.fr_opbase, rd)
        new_sp = st.fp + b
        st2 = st._replace(stack_lo=sl, stack_hi=sh, sp=new_sp)
        return lax.cond(
            done,
            lambda s: s._replace(status=jnp.int32(ST_DONE)),
            lambda s: s._replace(pc=r_pc, fp=r_fp, opbase=r_ob,
                                 call_depth=s.call_depth - 1),
            st2)

    def h_call(st, f):
        sub, a, b, c, ilo, ihi = f
        return _do_call(st, a, st.sp)

    def h_call_indirect(st, f):
        sub, a, b, c, ilo, ihi = f
        idx = row(st.stack_lo, st.sp - 1)
        i0 = idx[0]
        agree = jnp.all(idx == i0)
        tsize = table0.shape[0]
        oob = ~u_lt(i0, b)  # unsigned idx < size; b == 0 is always oob
        h = table0[jnp.clip(c + jnp.clip(i0, 0, jnp.maximum(b - 1, 0)),
                            0, tsize - 1)]
        null = h == 0
        callee = jnp.clip(h - 1, 0, f_entry.shape[0] - 1)
        sig_bad = f_type[callee] != a

        def bad(st):
            code = jnp.where(oob, int(ErrCode.UndefinedElement),
                             jnp.where(null, int(ErrCode.UninitializedElement),
                                       int(ErrCode.IndirectCallTypeMismatch)))
            return st._replace(trap=jnp.full((lanes,), code, I32),
                               status=jnp.int32(ST_TRAPPED_BASE) + code)

        def good(st):
            return _do_call(st._replace(sp=st.sp - 1), callee, st.sp - 1)

        new_st = lax.cond(oob | null | sig_bad, bad, good, st)
        return lax.cond(agree, lambda s: s,
                        lambda s: halt(st, jnp.int32(ST_DIVERGED)), new_st)

    def _do_call(st, callee, sp_eff):
        callee = jnp.clip(callee, 0, f_entry.shape[0] - 1)
        nargs = sget(f_nparams, callee)
        nloc = sget(f_nlocals, callee)
        ftop = sget(f_frame_top, callee)
        fp_new = sp_eff - nargs
        ob_new = fp_new + nloc
        ovf = (st.call_depth >= CD - 1) | (fp_new + ftop > D)

        def trap(st):
            code = jnp.where(st.call_depth >= CD - 1,
                             int(ErrCode.CallStackExhausted),
                             int(ErrCode.StackOverflow))
            return st._replace(trap=jnp.full((lanes,), code, I32),
                               status=jnp.int32(ST_TRAPPED_BASE) + code)

        def go(st):
            frp = sset(st.fr_ret_pc, st.call_depth, st.pc + 1)
            frf = sset(st.fr_fp, st.call_depth, st.fp)
            fro = sset(st.fr_opbase, st.call_depth, st.opbase)
            sl, sh = st.stack_lo, st.stack_hi
            zrow = jnp.zeros((lanes,), I32)
            for k in range(img.max_local_zeros):
                do = k < (nloc - nargs)
                sl = jnp.where(do, setrow(sl, fp_new + nargs + k, zrow), sl)
                sh = jnp.where(do, setrow(sh, fp_new + nargs + k, zrow), sh)
            return st._replace(pc=sget(f_entry, callee), fp=fp_new,
                               opbase=ob_new, sp=ob_new, call_depth=st.call_depth + 1,
                               fr_ret_pc=frp, fr_fp=frf, fr_opbase=fro,
                               stack_lo=sl, stack_hi=sh)

        return lax.cond(ovf, trap, go, st)

    def h_load(st, f):
        sub, a, b, c, ilo, ihi = f
        addr = row(st.stack_lo, st.sp - 1)
        ea = addr + a
        carry = u_lt(ea, addr) | u_lt(ea, jnp.full((lanes,), a, I32))
        mem_bytes = st.mem_pages * jnp.int32(65536)
        end = ea + b
        oob = carry | u_lt(end, ea) | u_lt(jnp.full((lanes,), mem_bytes, I32), end)
        widx = lax.shift_right_logical(ea, 2)
        shB = (ea & 3) * 8
        # per-lane word gather — addresses diverge, but memory rows are
        # lane-major so this is a [W, L] gather; uniform-address fast path
        # would need address agreement, data usually differs
        mw0 = _mem_gather(st.mem, widx)
        mw1 = _mem_gather(st.mem, widx + 1)
        mw2 = _mem_gather(st.mem, widx + 2)
        inv = (32 - shB) & 31
        hi_or = jnp.where(shB == 0, 0, -1)
        raw_lo = lax.shift_right_logical(mw0, shB) | (lax.shift_left(mw1, inv) & hi_or)
        raw_hi = lax.shift_right_logical(mw1, shB) | (lax.shift_left(mw2, inv) & hi_or)
        signed = (c & 1) != 0
        is64 = (c & 2) != 0
        b1 = b == 1
        b2_ = b == 2
        lraw = jnp.where(b1, raw_lo & 0xFF, jnp.where(b2_, raw_lo & 0xFFFF, raw_lo))
        lsext = jnp.where(b1, lax.shift_right_arithmetic(lax.shift_left(raw_lo, 24), 24),
                          jnp.where(b2_, lax.shift_right_arithmetic(lax.shift_left(raw_lo, 16), 16),
                                    raw_lo))
        ll = jnp.where(signed, lsext, lraw)
        lh = jnp.where(is64, jnp.where(b == 8, raw_hi,
                                       jnp.where(signed, lax.shift_right_arithmetic(ll, 31), 0)),
                       jnp.int32(0))
        any_oob = jnp.any(oob)
        sl = setrow(st.stack_lo, st.sp - 1, ll)
        sh = setrow(st.stack_hi, st.sp - 1, lh)
        new_st = st._replace(pc=st.pc + 1, stack_lo=sl, stack_hi=sh)
        return lax.cond(
            any_oob,
            lambda s: s._replace(
                trap=jnp.where(oob, int(ErrCode.MemoryOutOfBounds), s.trap),
                status=jnp.int32(ST_DIVERGED)),
            lambda s: s, new_st)

    def _mem_gather(mem, widx):
        import jax.numpy as jnp
        widx = jnp.clip(widx, 0, mem.shape[0] - 1)
        return jnp.take_along_axis(mem, widx[None, :], axis=0)[0]

    def h_store(st, f):
        sub, a, b, c, ilo, ihi = f
        vl = row(st.stack_lo, st.sp - 1)
        vh = row(st.stack_hi, st.sp - 1)
        addr = row(st.stack_lo, st.sp - 2)
        ea = addr + a
        carry = u_lt(ea, addr) | u_lt(ea, jnp.full((lanes,), a, I32))
        mem_bytes = st.mem_pages * jnp.int32(65536)
        end = ea + b
        oob = carry | u_lt(end, ea) | u_lt(jnp.full((lanes,), mem_bytes, I32), end)
        widx = lax.shift_right_logical(ea, 2)
        shB = (ea & 3) * 8
        b1 = b == 1
        b2_ = b == 2
        full_lo = jnp.where(b1, 0xFF, jnp.where(b2_, 0xFFFF, jnp.int32(-1)))
        full_hi = jnp.where(b == 8, jnp.int32(-1), 0)
        full_lo = jnp.broadcast_to(full_lo, (lanes,))
        full_hi = jnp.broadcast_to(full_hi, (lanes,))
        sm0, sm1 = lo_ops.shl64(full_lo, full_hi, shB)
        sm2 = jnp.where(shB == 0, 0, lo_ops.shr64_u(full_lo, full_hi, 64 - shB)[0])
        sv0, sv1 = lo_ops.shl64(vl, vh, shB)
        sv2 = jnp.where(shB == 0, 0, lo_ops.shr64_u(vl, vh, 64 - shB)[0])
        mem = st.mem
        mem = _mem_rmw(mem, widx, sm0, sv0, ~oob)
        mem = _mem_rmw(mem, widx + 1, sm1, sv1, ~oob)
        mem = _mem_rmw(mem, widx + 2, sm2, sv2, ~oob)
        any_oob = jnp.any(oob)
        new_st = st._replace(pc=st.pc + 1, sp=st.sp - 2, mem=mem)
        return lax.cond(
            any_oob,
            lambda s: s._replace(
                trap=jnp.where(oob, int(ErrCode.MemoryOutOfBounds), s.trap),
                status=jnp.int32(ST_DIVERGED)),
            lambda s: s, new_st)

    def _mem_rmw(mem, widx, m, v, ok):
        import jax.numpy as jnp
        lane_iota = jnp.arange(lanes, dtype=jnp.int32)
        widx = jnp.clip(widx, 0, mem.shape[0] - 1)
        cur = jnp.take_along_axis(mem, widx[None, :], axis=0)[0]
        new = jnp.where(ok & (m != 0), (cur & ~m) | (v & m), cur)
        return mem.at[widx, lane_iota].set(new)

    def h_memsize(st, f):
        sl = setrow(st.stack_lo, st.sp, jnp.full((lanes,), st.mem_pages, I32))
        sh = setrow(st.stack_hi, st.sp, jnp.zeros((lanes,), I32))
        return st._replace(pc=st.pc + 1, sp=st.sp + 1, stack_lo=sl, stack_hi=sh)

    def h_memgrow(st, f):
        delta_v = row(st.stack_lo, st.sp - 1)
        d0 = delta_v[0]
        agree = jnp.all(delta_v == d0)
        ok = (d0 >= 0) & ((st.mem_pages + d0) <= img.mem_pages_max) & \
            ((st.mem_pages + d0) >= st.mem_pages)
        res = jnp.where(ok, st.mem_pages, jnp.int32(-1))
        sl = setrow(st.stack_lo, st.sp - 1, jnp.full((lanes,), res, I32))
        sh = setrow(st.stack_hi, st.sp - 1, jnp.zeros((lanes,), I32))
        new_st = st._replace(pc=st.pc + 1, stack_lo=sl, stack_hi=sh,
                             mem_pages=jnp.where(ok, st.mem_pages + d0, st.mem_pages))
        return lax.cond(agree, lambda s: s,
                        lambda s: halt(st, jnp.int32(ST_DIVERGED)), new_st)

    def _bulk(st, is_copy):
        n = row(st.stack_lo, st.sp - 1)
        src_or_val = row(st.stack_lo, st.sp - 2)
        dst = row(st.stack_lo, st.sp - 3)
        mem_bytes = st.mem_pages * jnp.int32(65536)
        end = dst + n
        s_end = src_or_val + n
        oob = u_lt(end, dst) | u_lt(mem_bytes, end)
        if is_copy:
            oob = oob | u_lt(s_end, src_or_val) | u_lt(mem_bytes, s_end)
        go = ~oob & (n != 0)
        copy_lanes = jnp.ones_like(dst, bool) if is_copy else None
        mem = lo_ops.plane_fill_copy(st.mem, dst, end, src_or_val, go,
                                     copy_lanes=copy_lanes)
        any_oob = jnp.any(oob)
        new_st = st._replace(pc=st.pc + 1, sp=st.sp - 3, mem=mem)
        return lax.cond(
            any_oob,
            lambda s: s._replace(
                trap=jnp.where(oob, int(ErrCode.MemoryOutOfBounds), s.trap),
                status=jnp.int32(ST_DIVERGED)),
            lambda s: s, new_st)

    def h_memfill(st, f):
        return _bulk(st, False)

    def h_memcopy(st, f):
        return _bulk(st, True)

    def h_trap(st, f):
        sub, a, b, c, ilo, ihi = f
        return st._replace(trap=jnp.full((lanes,), a, I32),
                           status=jnp.int32(ST_TRAPPED_BASE) + a)

    # ---------------- tier-0 hostcalls on the converged path ----------
    # The stub pc is lane-uniform here, so the call KIND is scalar and
    # dispatch is a scalar cond chain; arguments/results stay per-lane
    # vectors.  Shapes the fast path cannot retire (cputime clocks,
    # oversized buffers, non-uniform stdout record sizes) hand off
    # un-advanced — the SIMT engine re-executes the stub and its own
    # tier 0 / the outcall channel takes over, with no double effects
    # (nothing is committed on the bail path).
    from wasmedge_tpu.batch.image import (
        T0_CLOCK_TIME_GET, T0_FD_WRITE, T0_PROC_EXIT, T0_RANDOM_GET,
        T0_SCHED_YIELD)
    from wasmedge_tpu.common.errors import ErrCode as _EC

    HAS_T0 = t0kinds is not None
    if HAS_T0:
        from wasmedge_tpu.batch.tier0 import (
            t0_clock_value, t0_masked_store, t0_random_fill,
            t0_rng_seq_hash, t0_shifted_src_word, t0_statics)

        t0k_t = jnp.asarray(np.asarray(t0kinds, np.int32))
        T0_PRESENT = sorted(set(int(k) for k in np.unique(t0kinds))
                            - {0})
        _t0s = t0_statics(cfg)
        RMAX_W = _t0s["RMAX_W"]
        WMAX_W = _t0s["WMAX_W"]
        RNG_SEED = jnp.asarray(_t0s["RNG_SEED"])
        _E_INVAL = _t0s["E_INVAL"]
        _E_FAULT = _t0s["E_FAULT"]
        lane_iota = jnp.arange(lanes, dtype=I32)
        zlv = jnp.zeros((lanes,), I32)

        def t0_retire(st2, res_vec):
            sl = setrow(st2.stack_lo, st2.opbase, res_vec)
            sh = setrow(st2.stack_hi, st2.opbase, zlv)
            return st2._replace(pc=st2.pc + 1, sp=st2.opbase + 1,
                                stack_lo=sl, stack_hi=sh)

        def t0_yield(st):
            return t0_retire(
                st._replace(t0_ctr=st.t0_ctr.at[3].add(1)), zlv)

        def t0_exit(st):
            code = row(st.stack_lo, st.fp)
            sl = setrow(st.stack_lo, st.opbase, code)
            return st._replace(
                stack_lo=sl,
                trap=jnp.full((lanes,), int(_EC.Terminated), I32),
                status=jnp.int32(ST_TRAPPED_BASE + int(_EC.Terminated)),
                t0_ctr=st.t0_ctr.at[3].add(1))

        def t0_clock(st, t0_time):
            cid = row(st.stack_lo, st.fp)
            tptr = row(st.stack_lo, st.fp + 2)
            hard = (cid == 2) | (cid == 3)     # cputime: tier 1
            bad = u_lt(jnp.int32(3), cid)
            mem_bytes = jnp.full((lanes,), st.mem_pages, I32) * \
                jnp.int32(65536)
            tend = tptr + 8
            oob = u_lt(tend, tptr) | u_lt(mem_bytes, tend)
            ctr = st.t0_ctr[0]
            tv_lo, tv_hi = t0_clock_value(t0_time, cid, ctr)
            wr = ~bad & ~oob & ~hard
            mem = t0_masked_store(_mem_rmw, st.mem, tptr, tv_lo, tv_hi,
                                  8, wr)
            res = jnp.where(bad, jnp.int32(_E_INVAL),
                            jnp.where(oob, jnp.int32(_E_FAULT), 0))
            st2 = t0_retire(
                st._replace(mem=mem, t0_ctr=st.t0_ctr.at[0].set(
                    jnp.where(wr, ctr + 1, ctr))), res)
            return lax.cond(jnp.any(hard),
                            lambda s: halt(st, jnp.int32(ST_DIVERGED)),
                            lambda s: s, st2)

        def t0_random(st):
            rbuf = row(st.stack_lo, st.fp)
            rlen = row(st.stack_lo, st.fp + 1)
            fits = ~u_lt(jnp.int32(RMAX_W * 4), rlen)
            mem_bytes = jnp.full((lanes,), st.mem_pages, I32) * \
                jnp.int32(65536)
            rend = rbuf + rlen
            oob = u_lt(rend, rbuf) | u_lt(mem_bytes, rend)
            ctr = st.t0_ctr[1]
            seq_h = t0_rng_seq_hash(RNG_SEED, lane_iota, ctr)
            wr = fits & ~oob & (rlen != 0)
            mem = t0_random_fill(_mem_rmw, st.mem, rbuf, rend, wr,
                                 seq_h, RMAX_W, zlv)
            res = jnp.where(oob, jnp.int32(_E_FAULT), 0)
            st2 = t0_retire(
                st._replace(mem=mem, t0_ctr=st.t0_ctr.at[1].set(
                    jnp.where(wr, ctr + 1, ctr))), res)
            return lax.cond(jnp.any(~fits),
                            lambda s: halt(st, jnp.int32(ST_DIVERGED)),
                            lambda s: s, st2)

        def t0_fdw(st):
            SW = st.so_buf.shape[0]
            wfd = row(st.stack_lo, st.fp)
            wiovs = row(st.stack_lo, st.fp + 1)
            wcnt = row(st.stack_lo, st.fp + 2)
            wnp = row(st.stack_lo, st.fp + 3)
            mem_bytes = jnp.full((lanes,), st.mem_pages, I32) * \
                jnp.int32(65536)
            iov_end = wiovs + 8
            iov_ok = ~(u_lt(iov_end, wiovs) | u_lt(mem_bytes, iov_end))
            iw = lax.shift_right_logical(wiovs, 2)
            wbuf = _mem_gather(st.mem, iw)
            wlen = _mem_gather(st.mem, iw + 1)
            fits = ~u_lt(jnp.int32(WMAX_W * 4), wlen)
            nwords = lax.shift_right_logical(wlen + 3, 2)
            npend = wnp + 4
            np_ok = ~(u_lt(npend, wnp) | u_lt(mem_bytes, npend))
            handled = ((wfd == 1) | (wfd == 2)) & (wcnt == 1) \
                & ((wiovs & 3) == 0) & iov_ok & fits & np_ok
            # the stdout record buffer is row-addressed: all lanes must
            # append the same number of rows from the same offset
            so0 = st.so_off[0]
            nw0 = nwords[0]
            uniform_rec = jnp.all(st.so_off == so0) & \
                jnp.all(jnp.where(handled, nwords, nw0) == nw0)
            space = ~u_lt(jnp.int32(SW), so0 + 1 + nw0)
            bail = jnp.any(~handled) | ~uniform_rec | ~space
            dend = wbuf + wlen
            d_oob = u_lt(dend, wbuf) | u_lt(mem_bytes, dend)
            wr = handled & ~d_oob
            shB = (wbuf & 3) * 8
            inv = (32 - shB) & 31
            hi_or = jnp.where(shB == 0, 0, -1)
            wsrc0 = lax.shift_right_logical(wbuf, 2)

            def commit(st):
                hdr = wlen | lax.shift_left(wfd, 28)
                cur = row(st.so_buf, so0)
                sob = setrow(st.so_buf, so0, jnp.where(wr, hdr, cur))
                for j in range(WMAX_W):
                    v = t0_shifted_src_word(_mem_gather, st.mem, wsrc0,
                                            j, shB, inv, hi_or)
                    mrow = wr & (jnp.int32(j) < nw0) & \
                        (jnp.int32(j * 4) < wlen)
                    curj = row(sob, so0 + 1 + j)
                    sob = setrow(sob, so0 + 1 + j,
                                 jnp.where(mrow, v, curj))
                mem = t0_masked_store(_mem_rmw, st.mem, wnp, wlen, zlv,
                                      4, wr)
                res = jnp.where(d_oob, jnp.int32(_E_FAULT), 0)
                ctr = st.t0_ctr[2]
                return t0_retire(st._replace(
                    mem=mem, so_buf=sob,
                    so_off=jnp.where(wr, st.so_off + 1 + nwords,
                                     st.so_off),
                    t0_ctr=st.t0_ctr.at[2].set(
                        jnp.where(wr, ctr + 1, ctr))), res)

            return lax.cond(bail,
                            lambda s: halt(s, jnp.int32(ST_DIVERGED)),
                            commit, st)

        _T0_HANDLERS = {
            T0_SCHED_YIELD: lambda st, tt: t0_yield(st),
            T0_PROC_EXIT: lambda st, tt: t0_exit(st),
            T0_CLOCK_TIME_GET: t0_clock,
            T0_RANDOM_GET: lambda st, tt: t0_random(st),
            T0_FD_WRITE: lambda st, tt: t0_fdw(st),
        }
        if not img.has_memory:
            for k in (T0_CLOCK_TIME_GET, T0_RANDOM_GET, T0_FD_WRITE):
                _T0_HANDLERS.pop(k, None)

    def h_hostcall(st, f, t0_time=None):
        # host outcalls: tier-0 kinds retire right here on the fast
        # path; everything else hands off un-advanced so the SIMT
        # engine re-executes the stub and parks the lanes
        if not HAS_T0:
            return halt(st, jnp.int32(ST_DIVERGED))
        kind = t0k_t[jnp.clip(st.pc, 0, img.code_len - 1)]

        def fall(s):
            return halt(s, jnp.int32(ST_DIVERGED))

        fn = fall
        for K in T0_PRESENT:
            h = _T0_HANDLERS.get(K)
            if h is None:
                continue
            fn = (lambda s, K=K, h=h, nxt=fn: lax.cond(
                kind == jnp.int32(K),
                lambda s2: h(s2, t0_time), nxt, s))
        return fn(st)

    handlers = [None] * NUM_CLASSES
    handlers[CLS_HOSTCALL] = h_hostcall
    handlers[CLS_NOP] = h_nop
    handlers[CLS_CONST] = h_const
    handlers[CLS_LOCAL_GET] = h_local_get
    handlers[CLS_LOCAL_SET] = h_local_set
    handlers[CLS_LOCAL_TEE] = h_local_tee
    handlers[CLS_GLOBAL_GET] = h_global_get
    handlers[CLS_GLOBAL_SET] = h_global_set
    handlers[CLS_ALU1] = h_alu1
    handlers[CLS_ALU2] = h_alu2
    handlers[CLS_SELECT] = h_select
    handlers[CLS_DROP] = h_drop
    handlers[CLS_BR] = h_br
    handlers[CLS_BRZ] = h_brz
    handlers[CLS_BRNZ] = h_brnz
    handlers[CLS_BR_TABLE] = h_br_table
    handlers[CLS_RETURN] = h_return
    handlers[CLS_CALL] = h_call
    handlers[CLS_CALL_INDIRECT] = h_call_indirect
    handlers[CLS_LOAD] = h_load
    handlers[CLS_STORE] = h_store
    handlers[CLS_MEMSIZE] = h_memsize
    handlers[CLS_MEMFILL] = h_memfill
    handlers[CLS_MEMCOPY] = h_memcopy
    handlers[CLS_MEMGROW] = h_memgrow
    handlers[CLS_TRAP] = h_trap

    # classes this converged engine does not execute (the v128 family
    # lives on the SIMT engine's 4-plane cells): divergence-bail stubs.
    # UniformBatchEngine.run routes has_simd modules to SIMT up front,
    # so these fire only as a safety net.
    def h_unsupported(st, f):
        return halt(st, jnp.int32(ST_DIVERGED))

    for k in range(NUM_CLASSES):
        if handlers[k] is None:
            handlers[k] = h_unsupported

    def step(st: UniformState, t0_time=None) -> UniformState:
        pc = jnp.clip(st.pc, 0, img.code_len - 1)
        fetch = (sub_t[pc], a_t[pc], b_t[pc], c_t[pc], ilo_t[pc], ihi_t[pc])
        cls = cls_t[pc]
        hs = list(handlers)
        hs[CLS_HOSTCALL] = (lambda s, f, tt=t0_time: h_hostcall(s, f, tt))
        new_st = lax.switch(cls, [
            (lambda s, f=fetch, h=h: h(s, f)) for h in hs
        ], st)
        # per-lane trap divergence check: if some (not all) lanes trapped in
        # an ALU, bail to SIMT; if all trapped identically, halt with code
        t = new_st.trap
        t0 = t[0]
        all_same = jnp.all(t == t0)
        any_trap = jnp.any(t != 0)

        def resolve(s):
            return lax.cond(
                all_same & (t0 != 0),
                lambda s: s._replace(status=jnp.int32(ST_TRAPPED_BASE) + t0),
                lambda s: lax.cond(
                    any_trap & (s.status == ST_RUNNING),
                    lambda s: s._replace(status=jnp.int32(ST_DIVERGED)),
                    lambda s: s, s),
                s)

        new_st = resolve(new_st)
        # A divergence handoff rewinds to the pre-step state: the SIMT engine
        # re-executes that instruction, so it must not count as a step here.
        counted = jnp.where(new_st.status == ST_DIVERGED, 0, 1)
        return new_st._replace(steps=new_st.steps + counted)

    return step


class UniformBatchEngine:
    """Converged-lane engine with automatic SIMT fallback on divergence.

    Chooses the fast path (scalar control, dynamic-slice stack rows) while
    lanes agree on control flow; hands the state over to the general SIMT
    engine (batch/engine.py) the moment they don't. This is the AUTO engine
    behavior for replicated workloads (BASELINE configs 1-2)."""

    def __init__(self, inst, store=None, conf=None, lanes=None, mesh=None):
        from wasmedge_tpu.batch.engine import BatchEngine

        self.simt = BatchEngine(inst, store=store, conf=conf, lanes=lanes,
                                mesh=mesh)
        self.inst = inst
        self.cfg = self.simt.cfg
        self.lanes = self.simt.lanes
        self.img = self.simt.img
        self.obs = self.simt.obs  # shared flight recorder (obs/)
        self._uchunk = None
        self.pallas = self._pick_pallas(inst, store, conf)

    def _pick_pallas(self, inst, store, conf):
        """The on-device Pallas dispatch loop is the fast path whenever the
        backend is TPU and the module fits the kernel geometry; the
        per-step XLA path below remains the CPU/testing vehicle and the
        fallback for oversized modules (conf.batch.use_pallas overrides)."""
        from wasmedge_tpu.batch.pallas_engine import (
            PallasUniformEngine, pallas_enabled)

        if not pallas_enabled(self.cfg):
            return None
        eng = PallasUniformEngine(inst, conf=conf, simt=self.simt,
                                  interpret=self.cfg.interpret or None)
        return eng if eng.eligible else None

    def _build_uniform(self):
        from wasmedge_tpu.batch import ensure_jax_backend, jit_in_place

        ensure_jax_backend()
        import jax.numpy as jnp
        from jax import lax

        step = make_uniform_step(self.img, self.cfg, self.lanes,
                                 t0kinds=getattr(self.simt, "_t0kinds",
                                                 None))
        chunk = self.cfg.steps_per_launch

        def run_chunk(st, t0_time):
            def cond(carry):
                i, s = carry
                return (i < chunk) & (s.status == ST_RUNNING)

            def body(carry):
                i, s = carry
                return i + 1, step(s, t0_time)

            _, st = lax.while_loop(cond, body, (jnp.int32(0), st))
            return st

        self._uchunk = jit_in_place(run_chunk, 0)

    def _initial_uniform_state(self, func_idx, args_lanes):
        import jax.numpy as jnp

        base = self.simt.initial_state(func_idx, args_lanes)
        CD = self.cfg.call_stack_depth
        return UniformState(
            pc=base.pc[0], sp=base.sp[0], fp=jnp.int32(0),
            opbase=base.opbase[0], call_depth=jnp.int32(0),
            status=jnp.int32(ST_RUNNING), steps=jnp.int32(0),
            mem_pages=base.mem_pages[0],
            stack_lo=base.stack_lo, stack_hi=base.stack_hi,
            fr_ret_pc=jnp.zeros((CD,), jnp.int32),
            fr_fp=jnp.zeros((CD,), jnp.int32),
            fr_opbase=jnp.zeros((CD,), jnp.int32),
            glob_lo=base.glob_lo, glob_hi=base.glob_hi,
            mem=base.mem, trap=base.trap,
            t0_ctr=base.t0_ctr, so_buf=base.so_buf, so_off=base.so_off,
        )

    def _to_simt_state(self, ust: "UniformState"):
        import jax.numpy as jnp

        from wasmedge_tpu.batch.engine import (
            BatchState, r05_state_planes, t0_state_planes)

        L = self.lanes
        full = lambda v: jnp.full((L,), v, jnp.int32)
        status = int(ust.status)
        trap = ust.trap
        if status == ST_DONE:
            trap = jnp.full((L,), TRAP_DONE, jnp.int32)
        elif status >= ST_TRAPPED_BASE:
            trap = jnp.where(trap == 0, jnp.int32(status - ST_TRAPPED_BASE), trap)
        cfg = self.cfg
        fuel0 = cfg.fuel_per_launch if cfg.fuel_per_launch is not None else 0
        return BatchState(
            pc=full(ust.pc), sp=full(ust.sp), fp=full(ust.fp),
            opbase=full(ust.opbase), call_depth=full(ust.call_depth),
            trap=trap, retired=full(ust.steps),
            fuel=full(max(fuel0 - int(ust.steps), 1) if fuel0 else 0),
            mem_pages=full(ust.mem_pages),
            stack_lo=ust.stack_lo, stack_hi=ust.stack_hi,
            fr_ret_pc=jnp.broadcast_to(ust.fr_ret_pc[:, None],
                                       (cfg.call_stack_depth, L)),
            fr_fp=jnp.broadcast_to(ust.fr_fp[:, None],
                                   (cfg.call_stack_depth, L)),
            fr_opbase=jnp.broadcast_to(ust.fr_opbase[:, None],
                                       (cfg.call_stack_depth, L)),
            glob_lo=ust.glob_lo, glob_hi=ust.glob_hi, mem=ust.mem,
            # r05 planes at their pristine values: the converged path
            # cannot execute the ops that mutate them (it bails first),
            # so a divergence handoff always starts from the initial
            # table/segment state
            **r05_state_planes(self.img, L),
            # tier-0 planes carry over VERBATIM: the converged path
            # retires tier-0 calls itself, so buffered stdout records
            # and counter positions must survive the handoff
            **(dict(t0_ctr=ust.t0_ctr, so_buf=ust.so_buf,
                    so_off=ust.so_off)
               if ust.t0_ctr is not None else
               t0_state_planes(self.img, cfg, L,
                               getattr(self.simt, "_t0kinds", None))),
        )

    def run(self, func_name, args_lanes, max_steps: int = 10_000_000):
        kernel = self._kernel_args()
        with self.obs.timed("batch/run", cat="engine", lanes=self.lanes,
                            **kernel) as span:
            res = self._run(func_name, args_lanes, max_steps)
            if not kernel:   # the first run is the one that builds
                span.set(**self._kernel_args())
            ipd = getattr(self.pallas, "instr_per_dispatch", None)
            if ipd is not None:
                span.set(instr_per_dispatch=round(ipd, 4))
            whs = getattr(self.pallas, "window_hit_share", None)
            if whs is not None:
                span.set(window_hit_share=round(whs, 6))
            sfs = getattr(self.pallas, "softfloat_share", None)
            if sfs is not None:
                span.set(softfloat_share=round(sfs, 6))
            sds = getattr(self.pallas, "simd_share", None)
            if sds is not None:
                span.set(simd_share=round(sds, 6))
            if getattr(self.pallas, "hostcall_rounds", 0):
                span.set(hostcall_rounds=self.pallas.hostcall_rounds)
            if getattr(self.pallas, "splits", 0):
                span.set(splits=self.pallas.splits,
                         launches=self.pallas.launches,
                         rechecks=self.pallas.rechecks,
                         surgery_programs=self.pallas.surgery_programs,
                         snap_restored=self.pallas.snap_restored)
            return res

    def _kernel_args(self):
        """What the Pallas kernel was built with, as span arguments:
        its dispatch tree, how it holds linear memory (`mem_mode`, and
        with a memory `lane_block` and the window's rows x ways), the
        plane arguments its launch donates, its block shapes
        ("kept/wanted") and, where the image holds an `i8x16.shuffle`,
        its slots by lowering ("word/dynamic"); nothing before a kernel
        exists."""
        depth = getattr(self.pallas, "dispatch_depth", None)
        if depth is None:
            return {}
        shapes = self.pallas.block_shapes
        args = {"dispatch_depth": f"{depth[0]:.2f}/{depth[1]}",
                **self.pallas.mem_static,
                "donated_planes": self.pallas.donated_planes,
                "block_shapes": f"{shapes['kept']}/{shapes['wanted']}"}
        sites = self.pallas.shuffle_sites
        if sites:
            args["shuffle_sites"] = f"{sites['word']}/{sites['dynamic']}"
        return args

    def _run(self, func_name, args_lanes, max_steps):
        import numpy as np

        from wasmedge_tpu.batch.engine import BatchResult

        ex = self.inst.exports.get(func_name)
        if ex is None or ex[0] != 0:
            raise KeyError(f"no exported function {func_name}")
        func_idx = ex[1]
        from wasmedge_tpu.batch.engine import new_hostcall_stats

        self.simt.hostcall_stats = new_hostcall_stats()
        from wasmedge_tpu.batch.hostcall import stdout_cursor_reset

        stdout_cursor_reset(self.simt)  # fresh run = fresh output stream
        # stale compaction mapping from a previous run must never leak
        # into this one (the handoff below re-arms when the knob is on)
        self.simt.compactor = None
        if self.pallas is not None:
            res = self.pallas.run(func_name, args_lanes, max_steps)
            self.fell_back_to_simt = self.pallas.fell_back_to_simt
            return res
        from wasmedge_tpu.batch.image import CLS_TABLE_GET

        if self.cfg.fuel_per_launch is not None or self.simt.mesh is not None \
                or getattr(self.img, "has_simd", False) \
                or bool((self.img.cls >= CLS_TABLE_GET).any()):
            # fuel accounting, mesh sharding, v128, and the r05 table/
            # segment/tail-call families live in the SIMT engine (the
            # converged single-pc path has neither 4-plane cells nor the
            # per-lane table planes)
            return self.simt.run(func_name, args_lanes, max_steps)
        if self._uchunk is None:
            self._build_uniform()
        import jax.numpy as jnp

        from wasmedge_tpu.batch.engine import t0_time_planes
        from wasmedge_tpu.batch.hostcall import flush_stdout_buffers

        ust = self._initial_uniform_state(func_idx, args_lanes)
        t0_active = ust.t0_ctr is not None
        dummy_time = np.zeros((2, 2), np.int32)
        fell_back = False
        obs = self.obs
        prev_steps = 0
        while int(ust.steps) < max_steps:
            tt = jnp.asarray(t0_time_planes() if t0_active
                             else dummy_time)
            t_launch = obs.now()
            with obs.timed("simt/chunk", cat="engine"):
                ust = self._uchunk(ust, tt)
                status = int(ust.status)    # waits for the chunk
            if obs.enabled:
                # converged path: every lane shares one pc, so
                # occupancy is all-or-nothing
                steps = int(ust.steps)
                obs.span("launch", t_launch, cat="engine",
                         track="uniform",
                         live_lanes=self.lanes if status == ST_RUNNING
                         else 0,
                         retired_delta=(steps - prev_steps) * self.lanes)
                prev_steps = steps
            if status == ST_RUNNING:
                continue
            if status == ST_DIVERGED:
                fell_back = True
            break
        self.fell_back_to_simt = fell_back
        if t0_active:
            # tier-0 retirements on the converged path (the SIMT
            # handoff below accounts only its own delta)
            ctr = np.asarray(ust.t0_ctr, np.int64).sum(axis=1)
            st_ = self.simt.hostcall_stats
            st_["tier0_clock"] += int(ctr[0])
            st_["tier0_random"] += int(ctr[1])
            st_["tier0_fd_write"] += int(ctr[2])
            st_["tier0_sys"] += int(ctr[3])
            st_["tier0_calls"] += int(ctr.sum())
        if fell_back:
            # migrate to SIMT and finish there (incl. host outcalls);
            # the divergence handoff is exactly where lane compaction
            # pays, so arm it for the SIMT leg (batch/compact.py)
            from wasmedge_tpu.batch.compact import arm

            arm(self.simt)
            state = self._to_simt_state(ust)
            state, total = self.simt.run_from_state(
                state, int(ust.steps), max_steps)
            return self._result_from_simt(func_idx, state, total)
        # uniform completion: drain the tier-0 stdout buffer
        state = self._to_simt_state(ust)
        state = flush_stdout_buffers(self.simt, state)
        return self._result_from_simt(func_idx, state, int(ust.steps))

    def _result_from_simt(self, func_idx, state, steps):
        import numpy as np

        from wasmedge_tpu.batch.engine import BatchResult

        nres = int(self.inst.lowered.funcs[func_idx].nresults)
        stack_lo = np.asarray(state.stack_lo)
        stack_hi = np.asarray(state.stack_hi)
        # the SIMT leg may have compacted (permuted) the lanes: gather
        # the result mirrors back to original lane order
        from wasmedge_tpu.batch.compact import restore_mirrors

        stack_lo, stack_hi, trap, retired = restore_mirrors(
            getattr(self.simt, "compactor", None), stack_lo, stack_hi,
            np.asarray(state.trap), np.asarray(state.retired))
        results = []
        for r in range(nres):
            lo = stack_lo[r].view(np.uint32).astype(np.uint64)
            hi = stack_hi[r].view(np.uint32).astype(np.uint64)
            results.append((lo | (hi << np.uint64(32))).view(np.int64))
        return BatchResult(results=results, trap=trap,
                           retired=retired, steps=steps)
