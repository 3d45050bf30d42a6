"""Multi-tenant heterogeneous batching: many modules, one lane batch.

BASELINE config 5 (the serverless mix) and SURVEY.md §7 step 8: different
tenants' modules run concurrently in one SIMT batch.  The design is pure
image concatenation — every tenant's DeviceImage is appended into one
super-image with its code/function/global/type/table/br-table index
spaces rebased, and each lane's control state is initialized at its own
tenant's entry pc.  The general SIMT engine is already per-lane-pc (its
dispatch gathers per-lane instruction words), so heterogeneous execution
needs no kernel changes; `call_indirect` reads its table window
(size/base) from the instruction, so each tenant's indirect calls stay
inside its own table.

Sandbox model matches batch/hostcall.py: per-lane data (stack, memory,
globals) is fully isolated per tenant; host modules are shared.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from wasmedge_tpu.batch.engine import BatchEngine, BatchResult, BatchState
from wasmedge_tpu.batch.image import (
    CLS_BR,
    CLS_BR_TABLE,
    CLS_BRNZ,
    CLS_BRZ,
    CLS_CALL,
    CLS_CALL_INDIRECT,
    CLS_DATA_DROP,
    CLS_ELEM_DROP,
    CLS_GLOBAL_GET,
    CLS_GLOBAL_SET,
    CLS_HOSTCALL,
    CLS_MEMINIT,
    CLS_REFFUNC,
    CLS_RETCALL,
    CLS_RETCALL_INDIRECT,
    CLS_TABLE_COPY,
    CLS_TABLE_FILL,
    CLS_TABLE_GET,
    CLS_TABLE_GROW,
    CLS_TABLE_INIT,
    CLS_TABLE_SET,
    CLS_TABLE_SIZE,
    DeviceImage,
)

_PAGE_WORDS = 65536 // 4

# merged fused-pattern table cap for concatenated images (fuse.py is
# numpy-only, so this import never pulls in the device stack)
from wasmedge_tpu.batch.fuse import CONCAT_MAX_PATTERNS \
    as _CONCAT_MAX_PATTERNS  # noqa: E402


@dataclasses.dataclass
class Tenant:
    """One module's share of the batch."""

    engine: BatchEngine      # per-module BatchEngine (provides the image)
    func_name: str
    args_lanes: List[np.ndarray]   # one array per param, [lanes] each
    lanes: int

    @property
    def inst(self):
        return self.engine.inst

    @property
    def img(self) -> DeviceImage:
        return self.engine.img


@dataclasses.dataclass
class Segment:
    """One tenant's fully-rebased contribution to a concatenated image.

    A segment is a pure function of (tenant image, index-space offsets,
    merged-fuse-pattern prefix): every array in it is already rebased
    into the super-image's coordinate space, so assembly is plain
    concatenation.  The imagestore SegmentCache keys on exactly those
    inputs — appending module N+1 leaves modules 1..N's offsets and
    pattern prefix untouched, so their segments replay from cache and
    only the new module is rebased."""

    base: dict                 # indirection row: per-index-space offsets
    planes: dict               # cls/sub/a/b/c/imm_lo/imm_hi/op_id
    brt: np.ndarray
    tbl: np.ndarray
    ef: np.ndarray
    eoff: np.ndarray
    elen: np.ndarray
    dwords: np.ndarray
    doff: np.ndarray
    dlen: np.ndarray
    flen: np.ndarray
    fpat: np.ndarray
    has_fuse: bool
    new_patterns: list         # fuse patterns novel vs. the entry prefix
    tfn: np.ndarray
    tfb: np.ndarray
    tier_fns: list             # rebased whole-function promotion entries
    has_tier: bool
    f_parts: dict              # f_entry/f_nparams/... (rebased)
    g_lo: np.ndarray
    g_hi: np.ndarray
    v128: np.ndarray
    advance: dict              # per-index-space deltas for the next seg


def build_segment(t: Tenant, off: dict, pat_state: tuple) -> Segment:
    """Rebase one tenant's DeviceImage at the given index-space offsets.

    `off` carries the running offsets (pc/func/glob/type/brt/table/v128/
    eseg/eflat/dseg/dbyte/tier_slot); `pat_state` is the tuple of fused
    patterns merged before this tenant.  Pure — reads only the tenant
    image and its arguments, which is what makes segments cacheable."""
    from wasmedge_tpu.batch.image import CLS_VCONST, CLS_VSHUFFLE

    img = t.img
    pc_b = off["pc"]
    fn_b = off["func"]
    gl_b = off["glob"]
    ty_b = off["type"]
    brt_b = off["brt"]
    tbl_b = off["table"]
    v128_b = off["v128"]
    eseg_b = off["eseg"]
    eflat_b = off["eflat"]
    dseg_b = off["dseg"]
    dbyte_b = off["dbyte"]
    tier_slot_b = off["tier_slot"]
    base = dict(pc=pc_b, func=fn_b, glob=gl_b, type=ty_b, brt=brt_b,
                table=tbl_b, eseg=eseg_b, dseg=dseg_b)
    a = img.a.copy()
    b = img.b.copy()
    c = img.c.copy()
    cls = img.cls
    is_branch = (cls == CLS_BR) | (cls == CLS_BRZ) | (cls == CLS_BRNZ)
    a[is_branch] += pc_b
    a[cls == CLS_CALL] += fn_b
    a[cls == CLS_RETCALL] += fn_b
    a[cls == CLS_HOSTCALL] += fn_b
    a[(cls == CLS_GLOBAL_GET) | (cls == CLS_GLOBAL_SET)] += gl_b
    is_ci = (cls == CLS_CALL_INDIRECT) | (cls == CLS_RETCALL_INDIRECT)
    a[is_ci] += ty_b
    c[is_ci] += tbl_b
    a[cls == CLS_BR_TABLE] += brt_b
    a[(cls == CLS_VCONST) | (cls == CLS_VSHUFFLE)] += v128_b
    # table ops address the tenant's slot [tbl_b, tbl_b + slot) in
    # the concatenated plane; ref.func pushes rebase with the
    # function index space
    is_tb = np.isin(cls, (CLS_TABLE_GET, CLS_TABLE_SET, CLS_TABLE_SIZE,
                          CLS_TABLE_GROW, CLS_TABLE_FILL,
                          CLS_TABLE_COPY, CLS_TABLE_INIT))
    c[is_tb] += tbl_b
    a[(cls == CLS_TABLE_INIT) | (cls == CLS_ELEM_DROP)] += eseg_b
    a[(cls == CLS_MEMINIT) | (cls == CLS_DATA_DROP)] += dseg_b
    a[cls == CLS_REFFUNC] += fn_b
    planes = dict(
        cls=cls, sub=img.sub, a=a, b=b, c=c,
        imm_lo=img.imm_lo, imm_hi=img.imm_hi,
        op_id=(img.op_id if img.op_id is not None
               else np.zeros(img.code_len, np.int32)))
    brt = img.br_table.copy()
    brt[:, 0] += pc_b
    # each tenant's table slot is its table_cap rows (grow room);
    # per-instruction capacity (b of CLS_TABLE_GROW) is already the
    # slot size, so growth can never cross into a neighbour's slot
    slot = max(int(img.table_cap or img.table0.shape[0]),
               img.table0.shape[0])
    tbl = np.zeros(slot, img.table0.dtype)
    tbl[:img.table0.shape[0]] = img.table0
    tbl[tbl != 0] += fn_b
    # segment snapshots: flat entries rebase with the function index
    # space (funcref domain), offsets with the flat concatenation
    ef = img.elem_flat.copy() if img.elem_flat is not None \
        else np.zeros(1, np.int32)
    ef[ef != 0] += fn_b
    eoff = (img.elem_off if img.elem_off is not None
            else np.zeros(1, np.int32)) + eflat_b
    elen = (img.elem_len if img.elem_len is not None
            else np.zeros(1, np.int32))
    dwords = (img.data_words if img.data_words is not None
              else np.zeros(1, np.int32))
    doff = (img.data_off if img.data_off is not None
            else np.zeros(1, np.int32)) + dbyte_b
    dlen = (img.data_len if img.data_len is not None
            else np.zeros(1, np.int32))
    # superinstruction fusion planes (batch/fuse.py): per-tenant runs
    # concatenate with NO pc rebasing needed beyond the plane offset
    # (runs are block-local); pattern ids remap into one deduped table
    t_flen = getattr(img, "fuse_len", None)
    new_patterns: list = []
    if t_flen is None:
        has_fuse = False
        flen = np.zeros(img.code_len, np.int32)
        fpat = np.full(img.code_len, -1, np.int32)
    else:
        has_fuse = True
        pat_map = {key: i for i, key in enumerate(pat_state)}
        remap = {}
        for ki, key in enumerate(img.fuse_patterns or ()):
            k2 = pat_map.get(key)
            if k2 is None:
                k2 = len(pat_map)
                pat_map[key] = k2
                new_patterns.append(key)
            remap[ki] = k2
        flen = np.asarray(t_flen, np.int32).copy()
        fpat = np.full(img.code_len, -1, np.int32)
        for p in np.nonzero(flen >= 2)[0]:
            k2 = remap.get(int(img.fuse_pat[p]), -1)
            if 0 <= k2 < _CONCAT_MAX_PATTERNS:
                fpat[p] = k2
            else:
                flen[p] = 0  # beyond the merged cap: stay per-op
    # whole-function promotion planes (batch/tierup.py): entry pcs,
    # block lists and branch targets all rebase by the plane offset,
    # slots by the running promoted count — the compiled bodies read
    # the CONCATENATED planes at the rebased static pcs, which match
    # the tenant planes verbatim (cls/sub/b/c/imms copy; `a` rebases
    # identically for branches on both sides)
    t_tfn = getattr(img, "tier_fn", None)
    tier_fns: list = []
    if t_tfn is None:
        has_tier = False
        tfn = np.full(img.code_len, -1, np.int32)
        tfb = np.zeros(img.code_len, np.int32)
        ntier = 0
    else:
        has_tier = True
        tfn = np.asarray(t_tfn, np.int32).copy()
        tfn[tfn >= 0] += tier_slot_b
        tfb = np.asarray(img.tier_fuel_bound, np.int32)
        for p in img.tier_fns:
            tier_fns.append(dict(
                p,
                slot=p["slot"] + tier_slot_b,
                entry_pc=p["entry_pc"] + pc_b,
                end_pc=p["end_pc"] + pc_b,
                blocks=[dict(bk, start=bk["start"] + pc_b,
                             end=bk["end"] + pc_b,
                             succ=tuple(s + pc_b
                                        for s in bk["succ"]))
                        for bk in p["blocks"]],
            ))
        ntier = len(img.tier_fns)
    f_parts = dict(
        f_entry=img.f_entry + pc_b,
        f_nparams=img.f_nparams,
        f_nlocals=img.f_nlocals,
        f_nresults=img.f_nresults,
        f_frame_top=img.f_frame_top,
        f_type=img.f_type + ty_b,
    )
    v128 = img.v128 if img.v128 is not None else np.zeros((1, 4), np.int32)
    advance = dict(
        pc=img.code_len,
        func=len(img.f_entry),
        glob=img.globals_lo.shape[0],
        type=int(img.f_type.max(initial=0)) + 1,
        brt=img.br_table.shape[0],
        table=slot,
        v128=v128.shape[0],
        eseg=elen.shape[0],
        eflat=ef.shape[0],
        dseg=dlen.shape[0],
        dbyte=4 * dwords.shape[0],
        tier_slot=ntier,
    )
    return Segment(base=base, planes=planes, brt=brt, tbl=tbl, ef=ef,
                   eoff=eoff, elen=elen, dwords=dwords, doff=doff,
                   dlen=dlen, flen=flen, fpat=fpat, has_fuse=has_fuse,
                   new_patterns=new_patterns, tfn=tfn, tfb=tfb,
                   tier_fns=tier_fns, has_tier=has_tier,
                   f_parts=f_parts, g_lo=img.globals_lo,
                   g_hi=img.globals_hi, v128=v128, advance=advance)


def concat_images(tenants: Sequence[Tenant], cache=None
                  ) -> Tuple[DeviceImage, list]:
    """Concatenate tenant DeviceImages into one super-image.

    Returns (image, bases) where bases[i] = dict of per-tenant index-space
    offsets (pc/func/glob/type/brt/table/eseg/dseg) — the indirection
    table.  `cache` (an imagestore SegmentCache, or None) memoizes the
    rebased per-tenant segments: with a cache, appending one module to an
    N-module generation rebuilds exactly one segment; without one this is
    the same per-tenant loop as ever, one build_segment call each, so the
    cache-off path is bit-identical by construction."""
    off = dict(pc=0, func=0, glob=0, type=0, brt=0, table=0, v128=0,
               eseg=0, eflat=0, dseg=0, dbyte=0, tier_slot=0)
    merged_patterns: list = []
    segments: List[Segment] = []
    for t in tenants:
        # planning is deferred to first build — run each tenant's
        # translation pass now so the concatenated planes see it
        # (idempotent; knob off plans nothing)
        plan = getattr(t.engine, "_plan_fusion", None)
        if plan is not None:
            plan()
        plan_t = getattr(t.engine, "_plan_tierup", None)
        if plan_t is not None:
            plan_t()
        pat_state = tuple(merged_patterns)
        seg = cache.lookup(t.img, off, pat_state) if cache is not None \
            else None
        if seg is None:
            seg = build_segment(t, off, pat_state)
            if cache is not None:
                cache.store(t.img, off, pat_state, seg)
        segments.append(seg)
        merged_patterns.extend(seg.new_patterns)
        for k, v in seg.advance.items():
            off[k] += v
    bases = [seg.base for seg in segments]
    any_fuse = any(seg.has_fuse for seg in segments)
    any_tier = any(seg.has_tier for seg in segments)
    # promotion descriptors are copied out of the (possibly cached,
    # cross-generation) segments so no two images ever share dicts
    merged_tier_fns = [dict(p, blocks=[dict(bk) for bk in p["blocks"]])
                       for seg in segments for p in seg.tier_fns]

    image = DeviceImage(
        cls=np.concatenate([s.planes["cls"] for s in segments]),
        sub=np.concatenate([s.planes["sub"] for s in segments]),
        a=np.concatenate([s.planes["a"] for s in segments]),
        b=np.concatenate([s.planes["b"] for s in segments]),
        c=np.concatenate([s.planes["c"] for s in segments]),
        imm_lo=np.concatenate([s.planes["imm_lo"] for s in segments]),
        imm_hi=np.concatenate([s.planes["imm_hi"] for s in segments]),
        op_id=np.concatenate([s.planes["op_id"] for s in segments]),
        br_table=np.concatenate([s.brt for s in segments], axis=0),
        f_entry=np.concatenate([s.f_parts["f_entry"] for s in segments]),
        f_nparams=np.concatenate([s.f_parts["f_nparams"]
                                  for s in segments]),
        f_nlocals=np.concatenate([s.f_parts["f_nlocals"]
                                  for s in segments]),
        f_nresults=np.concatenate([s.f_parts["f_nresults"]
                                   for s in segments]),
        f_frame_top=np.concatenate([s.f_parts["f_frame_top"]
                                    for s in segments]),
        f_type=np.concatenate([s.f_parts["f_type"] for s in segments]),
        table0=np.concatenate([s.tbl for s in segments]),
        globals_lo=np.concatenate([s.g_lo for s in segments]),
        globals_hi=np.concatenate([s.g_hi for s in segments]),
        mem_init=np.zeros(1, np.int32),       # per-lane init in the engine
        # watermark sizing reads mem_pages_init; cover every tenant's
        # initial pages (per-lane counts come from initial_state)
        mem_pages_init=max((t.img.mem_pages_init for t in tenants
                            if t.img.has_memory), default=0),
        mem_pages_max=max((t.img.mem_pages_max for t in tenants
                           if t.img.has_memory), default=0),
        has_memory=any(t.img.has_memory for t in tenants),
        max_local_zeros=max(t.img.max_local_zeros for t in tenants),
        code_len=off["pc"],
        v128=np.concatenate([s.v128 for s in segments], axis=0),
        has_simd=any(t.img.has_simd for t in tenants),
        elem_flat=np.concatenate([s.ef for s in segments]),
        elem_off=np.concatenate([s.eoff for s in segments]),
        elem_len=np.concatenate([s.elen for s in segments]),
        data_words=np.concatenate([s.dwords for s in segments]),
        data_off=np.concatenate([s.doff for s in segments]),
        data_len=np.concatenate([s.dlen for s in segments]),
        table_cap=off["table"],
        has_table_mut=any(getattr(t.img, "has_table_mut", False)
                          for t in tenants),
        has_table_grow=any(getattr(t.img, "has_table_grow", False)
                           for t in tenants),
        fuse_len=(np.concatenate([s.flen for s in segments])
                  if any_fuse else None),
        fuse_pat=(np.concatenate([s.fpat for s in segments])
                  if any_fuse else None),
        fuse_patterns=tuple(merged_patterns[:_CONCAT_MAX_PATTERNS])
        if any_fuse else None,
        fusion_report={
            "enabled": any_fuse,
            "patterns": min(len(merged_patterns), _CONCAT_MAX_PATTERNS),
            # recomputed from the MERGED planes (a run whose pattern
            # fell beyond the merged cap reverted to per-op cells and
            # must not be counted)
            "fused_runs": int(sum((s.flen >= 2).sum() for s in segments)),
            "fused_cells": int(sum(s.flen.sum() for s in segments)),
            "candidates": [], "runs": [],
        },
    )
    # whole-function promotion planes ride as plain attributes, like
    # plan_tierup binds them (batch/tierup.py); the report doubles as
    # the planned-sentinel so the merged engine's _plan_tierup never
    # re-plans (the concat image has no ModuleAnalysis to plan from)
    image.tier_fn = (np.concatenate([s.tfn for s in segments])
                     if any_tier else None)
    image.tier_fuel_bound = (np.concatenate([s.tfb for s in segments])
                             if any_tier else None)
    image.tier_fns = tuple(merged_tier_fns)
    image.tierup_report = {
        "enabled": any_tier,
        "promoted": [{k: p[k] for k in ("slot", "idx", "name",
                                        "entry_pc", "cost_bound",
                                        "fuel_bound", "device_loops")}
                     for p in merged_tier_fns],
        "candidates": [],
    }
    return image, bases


class MultiTenantBatchEngine(BatchEngine):
    """SIMT batch over the concatenation of several tenants' modules.

    Built from per-module BatchEngines (so each tenant's image reflects
    its own instance snapshot); lanes are assigned contiguously per
    tenant in order."""

    def __init__(self, tenants: Sequence[Tenant], conf=None, mesh=None):
        from wasmedge_tpu.common.configure import Configure

        if not tenants:
            raise ValueError("no tenants")
        self.tenants = list(tenants)
        # lane-sharded mesh execution (parallel/shard_drive.py): the
        # concatenated image replicates, lane planes shard — the same
        # single-program chunk the single-module engine jits
        self.mesh = mesh
        self.conf = conf or Configure()
        self.cfg = self.conf.batch
        self.lanes = sum(t.lanes for t in self.tenants)
        self.inst = self.tenants[0].inst  # nresults fallback; see run()
        self.img, self.bases = concat_images(
            self.tenants, cache=getattr(self, "_segment_cache", None))
        self._func_owner = []
        for ti, t in enumerate(self.tenants):
            self._func_owner.extend([ti] * len(t.img.f_entry))
        # concatenated images carry no t0kind plane: every tenant's
        # hostcalls stay on the per-tenant outcall channel (tier 1),
        # which is what keeps per-tenant WASI environs authoritative
        from wasmedge_tpu.batch.engine import new_hostcall_stats

        self._t0kinds = None
        self.hostcall_stats = new_hostcall_stats()
        from wasmedge_tpu.obs.recorder import recorder_of

        self.obs = recorder_of(self.conf)
        self._step = None
        self._run_chunk = None

    # hostcall serve resolves concatenated func index -> tenant-local one
    def resolve_func(self, k: int):
        ti = self._func_owner[k]
        return self.tenants[ti].inst.funcs[k - self.bases[ti]["func"]]

    def initial_state(self, func_idx=None, args_lanes=None) -> BatchState:
        import jax.numpy as jnp

        cfg = self.cfg
        L = self.lanes
        img = self.img
        D = cfg.value_stack_depth
        CD = cfg.call_stack_depth
        stack_lo = np.zeros((D, L), np.int32)
        stack_hi = np.zeros((D, L), np.int32)
        pc = np.zeros(L, np.int32)
        sp = np.zeros(L, np.int32)
        opbase = np.zeros(L, np.int32)
        pages = np.zeros(L, np.int32)
        mem_words = max(img.mem_pages_max * _PAGE_WORDS, 1)
        mem = np.zeros((mem_words, L), np.int32)
        from wasmedge_tpu.common.types import ValType

        lane0 = 0
        self._tenant_slices = []
        self._tenant_funcidx = []
        for ti, t in enumerate(self.tenants):
            sl = slice(lane0, lane0 + t.lanes)
            self._tenant_slices.append(sl)
            ex = t.inst.exports.get(t.func_name)
            if ex is None or ex[0] != 0:
                raise KeyError(f"tenant {ti}: no export {t.func_name}")
            ft = t.inst.funcs[ex[1]].functype
            if ValType.V128 in tuple(ft.params) + tuple(ft.results):
                raise ValueError(
                    f"tenant {ti}: batch entry functions cannot take or "
                    f"return v128 (lane args are 64-bit cells)")
            fidx = ex[1] + self.bases[ti]["func"]
            self._tenant_funcidx.append(fidx)
            meta = t.inst.lowered.funcs[ex[1]]
            pc[sl] = int(self.img.f_entry[fidx])
            sp[sl] = meta.nlocals
            opbase[sl] = meta.nlocals
            for i, arg in enumerate(t.args_lanes):
                arr = np.asarray(arg, np.int64)
                if arr.ndim == 0:
                    arr = np.full(t.lanes, arr, np.int64)
                stack_lo[i, sl] = (arr & 0xFFFFFFFF).astype(
                    np.uint32).view(np.int32)
                stack_hi[i, sl] = ((arr >> 32) & 0xFFFFFFFF).astype(
                    np.uint32).view(np.int32)
            if t.img.has_memory:
                pages[sl] = t.img.mem_pages_init
                n = min(t.img.mem_init.shape[0], mem_words)
                mem[:n, sl] = t.img.mem_init[:n, None]
            lane0 += t.lanes
        g_lo = np.repeat(img.globals_lo[:, None], L, axis=1)
        g_hi = np.repeat(img.globals_hi[:, None], L, axis=1)
        fuel0 = cfg.fuel_per_launch if cfg.fuel_per_launch is not None else 0
        return BatchState(
            pc=jnp.asarray(pc), sp=jnp.asarray(sp),
            fp=jnp.zeros(L, jnp.int32), opbase=jnp.asarray(opbase),
            call_depth=jnp.zeros(L, jnp.int32),
            trap=jnp.zeros(L, jnp.int32), retired=jnp.zeros(L, jnp.int32),
            fuel=jnp.full(L, fuel0, jnp.int32),
            mem_pages=jnp.asarray(pages),
            stack_lo=jnp.asarray(stack_lo), stack_hi=jnp.asarray(stack_hi),
            fr_ret_pc=jnp.zeros((CD, L), jnp.int32),
            fr_fp=jnp.zeros((CD, L), jnp.int32),
            fr_opbase=jnp.zeros((CD, L), jnp.int32),
            glob_lo=jnp.asarray(g_lo), glob_hi=jnp.asarray(g_hi),
            mem=jnp.asarray(mem),
            stack_e2=jnp.zeros((D, L), jnp.int32) if img.has_simd else None,
            stack_e3=jnp.zeros((D, L), jnp.int32) if img.has_simd else None,
            **self._r05_planes(),
        )

    def _r05_planes(self, tsize: Optional[np.ndarray] = None,
                    patches: Optional[dict] = None) -> dict:
        """Concatenated-image variant of engine.r05_state_planes: the
        tab plane holds every tenant's slot; `tsize` is the per-lane
        table-size vector — None derives the fixed-cohort default
        (each tenant's slice sees its own table size); the serving
        engine passes a lane-uniform vector instead.  `patches` is the
        snapshot-overlay row-range writes ({"tab"/"edrop"/"ddrop":
        (row0, column)}) applied lane-uniformly before upload; None
        (every non-snapshot caller) leaves the planes untouched."""
        import jax.numpy as jnp

        img = self.img
        L = self.lanes
        out = {}
        if getattr(img, "has_table_mut", False):
            T = max(int(img.table_cap or img.table0.shape[0]), 1)
            tb = np.zeros((T, L), np.int32)
            n0 = min(img.table0.shape[0], T)
            tb[:n0] = img.table0[:n0, None]
            if patches and "tab" in patches:
                row0, col = patches["tab"]
                n = min(col.shape[0], T - row0)
                if n > 0:
                    tb[row0:row0 + n] = col[:n, None]
            if tsize is None:
                tsize = np.zeros(L, np.int32)
                for ti, t in enumerate(self.tenants):
                    tsize[self._tenant_slices[ti]] = t.img.table_size_init
            out["tab"] = jnp.asarray(tb)
            out["tsize"] = jnp.asarray(np.asarray(tsize, np.int32))
        if bool(np.isin(img.cls, (CLS_TABLE_INIT, CLS_ELEM_DROP)).any()):
            ed = np.zeros((img.elem_len.shape[0], L), np.int32)
            if patches and "edrop" in patches:
                row0, col = patches["edrop"]
                n = min(col.shape[0], ed.shape[0] - row0)
                if n > 0:
                    ed[row0:row0 + n] = col[:n, None]
            out["edrop"] = jnp.asarray(ed)
        if bool(np.isin(img.cls, (CLS_MEMINIT, CLS_DATA_DROP)).any()):
            dd = np.zeros((img.data_len.shape[0], L), np.int32)
            if patches and "ddrop" in patches:
                row0, col = patches["ddrop"]
                n = min(col.shape[0], dd.shape[0] - row0)
                if n > 0:
                    dd[row0:row0 + n] = col[:n, None]
            out["ddrop"] = jnp.asarray(dd)
        return out

    def _try_schedulers(self, max_steps):
        """Per-tenant Pallas engines driven by interleaved block
        schedulers.  Tenants are share-nothing, so each gets its OWN
        kernel geometry (a memory-heavy tenant no longer drags
        memory-free tenants' lane blocks down to its VMEM footprint) and
        its own entry grouping.  Launches are asynchronous: while one
        tenant's host side processes results, the others' kernels run —
        the (module, PC)-bucket scheduling SURVEY §7 step 8 prescribes.
        Returns {tenant_index: BlockScheduler} for the eligible tenants,
        or None when the Pallas path is off or no tenant is eligible:
        the whole batch then runs on this SIMT engine."""
        from wasmedge_tpu.batch.pallas_engine import (
            PallasUniformEngine, pallas_enabled)
        from wasmedge_tpu.batch.scheduler import BlockScheduler

        if not pallas_enabled(self.cfg):
            return None
        scheds = {}
        for ti, t in enumerate(self.tenants):
            if t.engine.conf is self.conf:
                # reuse the tenant's existing BatchEngine (its image is
                # already built and normalized) as the SIMT side
                eng = PallasUniformEngine(
                    t.inst, simt=t.engine,
                    interpret=self.cfg.interpret or None)
            else:
                # mismatched confs: THIS engine's knobs must govern the
                # run (fuel, steps_per_launch, memory ceilings), so build
                # a fresh SIMT side under self.conf
                eng = PallasUniformEngine(
                    t.inst, store=t.engine.store, conf=self.conf,
                    lanes=t.lanes, interpret=self.cfg.interpret or None)
            if not eng.eligible:
                continue
            scheds[ti] = BlockScheduler(eng, t.func_name,
                                        list(t.args_lanes), max_steps)
        return scheds or None

    def run_tenants(self, max_steps: int = 10_000_000) -> List[BatchResult]:
        """Run the whole mixed batch; returns one BatchResult per tenant."""
        scheds = self._try_schedulers(max_steps)
        if scheds is not None:
            self.used_pallas = True
            active = dict(scheds)
            while active:
                for s in active.values():
                    s.launch()
                done = [ti for ti, s in active.items() if not s.process()]
                for ti in done:
                    del active[ti]
            for s in scheds.values():
                s._run_simt_residue()
            out = []
            for ti, t in enumerate(self.tenants):
                if ti in scheds:
                    out.append(scheds[ti].result())
                else:
                    # ineligible tenant: its own SIMT engine, alone
                    res = t.engine.run(t.func_name, list(t.args_lanes),
                                       max_steps)
                    out.append(res)
            return out
        from wasmedge_tpu.batch.compact import arm

        arm(self)   # fresh per-run lane-compaction mapping (off = None)
        self.used_pallas = False
        state, total = self.run_from_state(self.initial_state(), 0,
                                           max_steps)
        return self.results_from_state(state, total)

    def results_from_state(self, state: BatchState, total: int
                           ) -> List[BatchResult]:
        """Harvest one BatchResult per tenant from a final SIMT state —
        shared by run_tenants and the supervised entry
        (batch/supervisor.py drives run_from_state slices itself for
        checkpoint cadence, then harvests here)."""
        stack_lo = np.asarray(state.stack_lo)
        stack_hi = np.asarray(state.stack_hi)
        # lane compaction permutes across tenant slice boundaries: the
        # src mapping restores original (per-tenant-contiguous) order
        from wasmedge_tpu.batch.compact import restore_mirrors

        stack_lo, stack_hi, trap, retired = restore_mirrors(
            getattr(self, "compactor", None), stack_lo, stack_hi,
            np.asarray(state.trap), np.asarray(state.retired))
        out = []
        for ti, t in enumerate(self.tenants):
            sl = self._tenant_slices[ti]
            ex = t.inst.exports[t.func_name]
            nres = int(t.inst.lowered.funcs[ex[1]].nresults)
            results = []
            for r in range(nres):
                lo = stack_lo[r, sl].view(np.uint32).astype(np.uint64)
                hi = stack_hi[r, sl].view(np.uint32).astype(np.uint64)
                results.append((lo | (hi << np.uint64(32))).view(np.int64))
            out.append(BatchResult(results=results, trap=trap[sl],
                                   retired=retired[sl], steps=total))
        return out


class MultiModuleBatchEngine(MultiTenantBatchEngine):
    """Serving-oriented concatenation: many modules, ANY lane, ANY entry.

    `MultiTenantBatchEngine` packs a fixed cohort — each tenant owns a
    contiguous lane slice initialized once at its own entry.  The
    serving gateway needs the transpose: one long-lived lane pool where
    a freed lane can be re-initialized onto ANY registered module's
    exported function (the LaneRecycler `initial_state` template seam).
    This engine keeps the pure-concatenation image (every module's
    index spaces rebased into one super-image, so per-module execution
    is bit-identical to a solo run) but makes `initial_state` lane-
    UNIFORM per engine-global function index: entry pc/locals from the
    owning module, that module's memory/table snapshot in every lane,
    the full concatenated global plane (a fresh request resets its
    lane's whole global column to init — fresh-instance semantics).

    Entry names are qualified `module:func` (`export_func_idx`); an
    unqualified name falls back to the first registered module, so a
    one-module engine behaves like a plain BatchEngine under the
    serving layer.  Hostcalls stay on the per-module tier-1 channel
    (concatenated images carry no t0kind plane), which is what keeps
    per-module WASI environs authoritative.

    `modules` is an ordered [(name, inst, store)]; `lanes` is the
    serving pool width (unrelated to any per-module cohort).
    `engines` optionally supplies the per-module BatchEngines (one per
    entry of `modules`, order-matched) so repeated generation builds
    reuse the already-built-and-normalized DeviceImages instead of
    re-lowering every registered module on each swap (the gateway's
    registry caches one engine per module at registration time)."""

    def __init__(self, modules: Sequence[Tuple[str, object, object]],
                 conf=None, lanes: Optional[int] = None, engines=None,
                 mesh=None, segment_cache=None, init_overlays=None,
                 snapshot_counts=None):
        if not modules:
            raise ValueError("no modules")
        names = [name for name, _, _ in modules]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate module names in {names}")
        tenants = []
        for k, (name, inst, store) in enumerate(modules):
            # per-module BatchEngine: builds + normalizes the module's
            # own DeviceImage (raises ValueError when not batchable);
            # lanes=1 — only the image is used, never its state
            eng = engines[k] if engines is not None \
                else BatchEngine(inst, store=store, conf=conf, lanes=1)
            tenants.append(Tenant(engine=eng, func_name="",
                                  args_lanes=[], lanes=0))
        # segment memoization must be visible to the base __init__'s
        # concat_images call; overlays only matter to initial_state
        self._segment_cache = segment_cache
        super().__init__(tenants, conf=conf, mesh=mesh)
        self._init_overlays = dict(init_overlays) if init_overlays else {}
        self.snapshot_counts = (snapshot_counts
                                if snapshot_counts is not None else {})
        self.lanes = int(lanes) if lanes else self.cfg.lanes
        if mesh is not None:
            # even lane split across the mesh: round the serving pool
            # up — the extra lanes are plain capacity (idle lanes park
            # TRAP_DONE and cost only their plane storage)
            from wasmedge_tpu.parallel.shard_drive import padded_lanes

            self.lanes = padded_lanes(self.lanes, int(mesh.devices.size))
        self.module_names = list(names)
        self._mod_index = {name: ti for ti, name in enumerate(names)}

    # -- the export_func_idx / func_nresults seam (serve/recycle.py) ------
    def export_func_idx(self, func_name: str) -> int:
        from wasmedge_tpu.batch.engine import check_batch_entry

        mod, sep, fn = func_name.partition(":")
        if not sep:
            mod, fn = self.module_names[0], func_name
        ti = self._mod_index.get(mod)
        if ti is None:
            raise KeyError(f"no registered module {mod!r}")
        try:
            local = check_batch_entry(self.tenants[ti].inst, fn)
        except KeyError:
            raise KeyError(
                f"no exported function {fn!r} in module {mod!r}") \
                from None
        return local + self.bases[ti]["func"]

    def func_nresults(self, func_idx: int) -> int:
        return int(self.img.f_nresults[func_idx])

    def func_owner(self, func_idx: int) -> str:
        """Owning module name of an engine-global function index."""
        return self.module_names[self._func_owner[func_idx]]

    def note_snapshot_install(self, func_idx: int, n: int) -> None:
        """Recycler hook: count lanes admitted onto a snapshot overlay.

        serve/recycle.py calls this on every install; only entries whose
        owning module carries a pre-initialized overlay count (modules
        without one admit through plain template init)."""
        if not self._init_overlays:
            return
        if self.module_names[self._func_owner[func_idx]] \
                in self._init_overlays:
            self.snapshot_counts["installs"] = \
                self.snapshot_counts.get("installs", 0) + int(n)

    def exported_funcs(self, module: str) -> List[str]:
        return self.tenants[self._mod_index[module]].inst.func_names()

    # -- lane-uniform entry state (the recycler's template source) --------
    def initial_state(self, func_idx: int = 0, args_lanes=None
                      ) -> BatchState:
        import jax.numpy as jnp

        from wasmedge_tpu.batch.engine import pack_lane_args

        args_lanes = args_lanes or []
        cfg = self.cfg
        L = self.lanes
        img = self.img
        ti = self._func_owner[func_idx]
        t = self.tenants[ti]
        meta = t.inst.lowered.funcs[func_idx - self.bases[ti]["func"]]
        D = cfg.value_stack_depth
        CD = cfg.call_stack_depth
        stack_lo, stack_hi = pack_lane_args(args_lanes, L, D)
        # plane geometry is function-INDEPENDENT (the pool's lanes are
        # recycled across modules): memory sized to the concatenated
        # image's max, initialized with the owning module's snapshot
        mem_words = max(img.mem_pages_max * _PAGE_WORDS, 1)
        mem = np.zeros((mem_words, L), np.int32)
        pages = 0
        if t.img.has_memory:
            pages = t.img.mem_pages_init
            n = min(t.img.mem_init.shape[0], mem_words)
            mem[:n] = t.img.mem_init[:n, None]
        g_lo = np.repeat(img.globals_lo[:, None], L, axis=1)
        g_hi = np.repeat(img.globals_hi[:, None], L, axis=1)
        tsize_val = t.img.table_size_init
        patches = None
        ov = (self._init_overlays.get(self.module_names[ti])
              if getattr(self, "_init_overlays", None) else None)
        if ov is not None:
            # pre-initialized snapshot overlay (imagestore/snapshot.py):
            # the captured post-init columns replace the owning module's
            # template init in every lane — memory/pages from row 0 of
            # the shared per-lane planes, globals/table/drop flags into
            # the module's segment rows via the indirection bases
            om = ov.get("mem")
            if om is not None:
                n = min(om.shape[0], mem_words)
                mem[:n] = om[:n, None]
            if ov.get("mem_pages") is not None:
                pages = int(ov["mem_pages"])
            og = ov.get("glob_lo")
            if og is not None:
                gb = self.bases[ti]["glob"]
                g_lo[gb:gb + og.shape[0]] = og[:, None]
                oh = ov["glob_hi"]
                g_hi[gb:gb + oh.shape[0]] = oh[:, None]
            patches = {}
            ot = ov.get("tab")
            if ot is not None:
                # runtime table entries are funcidx+1 (0 = null); rebase
                # exactly the way concat rebases table0 snapshots
                col = np.asarray(ot, np.int32).copy()
                col[col != 0] += self.bases[ti]["func"]
                patches["tab"] = (self.bases[ti]["table"], col)
            if ov.get("tsize") is not None:
                tsize_val = int(ov["tsize"])
            if ov.get("edrop") is not None:
                patches["edrop"] = (self.bases[ti]["eseg"],
                                    np.asarray(ov["edrop"], np.int32))
            if ov.get("ddrop") is not None:
                patches["ddrop"] = (self.bases[ti]["dseg"],
                                    np.asarray(ov["ddrop"], np.int32))
        fuel0 = cfg.fuel_per_launch if cfg.fuel_per_launch is not None \
            else 0
        return BatchState(
            pc=jnp.full((L,), int(img.f_entry[func_idx]), jnp.int32),
            sp=jnp.full((L,), meta.nlocals, jnp.int32),
            fp=jnp.zeros(L, jnp.int32),
            opbase=jnp.full((L,), meta.nlocals, jnp.int32),
            call_depth=jnp.zeros(L, jnp.int32),
            trap=jnp.zeros(L, jnp.int32),
            retired=jnp.zeros(L, jnp.int32),
            fuel=jnp.full(L, fuel0, jnp.int32),
            mem_pages=jnp.full((L,), pages, jnp.int32),
            stack_lo=jnp.asarray(stack_lo),
            stack_hi=jnp.asarray(stack_hi),
            fr_ret_pc=jnp.zeros((CD, L), jnp.int32),
            fr_fp=jnp.zeros((CD, L), jnp.int32),
            fr_opbase=jnp.zeros((CD, L), jnp.int32),
            glob_lo=jnp.asarray(g_lo),
            glob_hi=jnp.asarray(g_hi),
            mem=jnp.asarray(mem),
            stack_e2=jnp.zeros((D, L), jnp.int32) if img.has_simd
            else None,
            stack_e3=jnp.zeros((D, L), jnp.int32) if img.has_simd
            else None,
            # lane-uniform tsize: every lane sees the owning module's
            # table size (the tab plane still holds every module's
            # slot — table ops address slots through the rebased
            # instruction words)
            **self._r05_planes(
                np.full(L, tsize_val, np.int32), patches=patches),
        )


def run_mixed(specs, conf=None, max_steps: int = 10_000_000):
    """Convenience: specs = [(inst, store, func_name, args_lanes, lanes)].

    Builds per-module BatchEngines, concatenates, runs, returns one
    BatchResult per tenant."""
    from wasmedge_tpu.common.configure import Configure

    conf = conf or Configure()
    tenants = []
    for inst, store, func_name, args_lanes, lanes in specs:
        eng = BatchEngine(inst, store=store, conf=conf, lanes=lanes)
        tenants.append(Tenant(engine=eng, func_name=func_name,
                              args_lanes=list(args_lanes), lanes=lanes))
    mt = MultiTenantBatchEngine(tenants, conf=conf)
    return mt.run_tenants(max_steps=max_steps)
