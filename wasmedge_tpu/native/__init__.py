"""Native scalar engine: ctypes bindings over the C++ dispatch loop.

This is the `EngineKind.NATIVE` implementation — a C++ interpreter over the
same lowered SoA image the Python oracle and the TPU engines execute
(engine.cpp here mirrors /root/reference/lib/executor/engine/
engine.cpp:68-1641 structurally).  It is the fast host-side engine behind
`--engine native`.

Build-on-demand: the shared library is compiled with g++ on first use and
cached by source hash under ~/.cache/wasmedge_tpu (no pip, no network).
The opcode-id header is generated from the Python opcode table so the two
sides cannot drift, and the supported-op set is parsed back out of
engine.cpp's `case` labels so eligibility is always exactly "what the C++
actually implements".

Eligibility (else the caller falls back to the Python engine — the same
graceful degradation the reference applies to mismatched AOT sections,
lib/loader/ast/module.cpp:279-326): single module, no imports/host
functions, no SIMD/table-mutation ops, at most one memory and one table
with locally-resolvable funcrefs.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import re
import subprocess
import sys
from typing import List, Optional

import numpy as np

from wasmedge_tpu.common.errors import ErrCode, TrapError
from wasmedge_tpu.common.opcodes import NAME_TO_ID, OPCODES
from wasmedge_tpu.validator.image import LOP_BR, LOP_BRNZ, LOP_BRZ

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "engine.cpp")

# ErrCode values the C++ side traps with (names must exist in ErrCode)
_ERR_EXPORTS = {
    "E_Terminated": ErrCode.Terminated,
    "E_Unreachable": ErrCode.Unreachable,
    "E_MemoryOOB": ErrCode.MemoryOutOfBounds,
    "E_DivideByZero": ErrCode.DivideByZero,
    "E_IntegerOverflow": ErrCode.IntegerOverflow,
    "E_InvalidConvToInt": ErrCode.InvalidConvToInt,
    "E_UndefinedElement": ErrCode.UndefinedElement,
    "E_UninitializedElement": ErrCode.UninitializedElement,
    "E_IndirectCallTypeMismatch": ErrCode.IndirectCallTypeMismatch,
    "E_CallStackExhausted": ErrCode.CallStackExhausted,
    "E_StackOverflow": ErrCode.StackOverflow,
    "E_ExecutionFailed": ErrCode.ExecutionFailed,
    "E_TableOOB": ErrCode.TableOutOfBounds,
}


def _sanitize(name: str) -> str:
    return name.replace(".", "_")


def _gen_header() -> str:
    lines = ["// generated from wasmedge_tpu/common/opcodes.py — do not edit"]
    for op_id, info in enumerate(OPCODES):
        lines.append(f"#define OP_{_sanitize(info.name)} {op_id}")
    lines.append(f"#define LOP_BR_ID {LOP_BR}")
    lines.append(f"#define LOP_BRZ_ID {LOP_BRZ}")
    lines.append(f"#define LOP_BRNZ_ID {LOP_BRNZ}")
    for cname, code in _ERR_EXPORTS.items():
        lines.append(f"#define {cname} {int(code)}")
    lines.append("")
    return "\n".join(lines)


_lib = None
_supported_ids: Optional[frozenset] = None


def _build_lib():
    """Compile (or reuse cached) shared library; returns ctypes CDLL."""
    global _lib
    if _lib is not None:
        return _lib
    src = open(_SRC).read()
    header = _gen_header()
    key = hashlib.sha256((src + header + "v1").encode()).hexdigest()[:16]
    cache = os.path.join(os.path.expanduser("~"), ".cache", "wasmedge_tpu")
    os.makedirs(cache, exist_ok=True)
    so_path = os.path.join(cache, f"we_native_{key}.so")
    if not os.path.exists(so_path):
        gen_dir = os.path.join(cache, f"gen_{key}")
        os.makedirs(gen_dir, exist_ok=True)
        with open(os.path.join(gen_dir, "gen_opcodes.h"), "w") as f:
            f.write(header)
        tmp = so_path + f".tmp{os.getpid()}"
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
               f"-I{gen_dir}", "-o", tmp, _SRC]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(f"native engine build failed:\n{e.stderr}")
        os.replace(tmp, so_path)
    lib = ctypes.CDLL(so_path)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.we_native_invoke.restype = ctypes.c_int32
    lib.we_native_invoke.argtypes = [
        i32p, i32p, i32p, i32p, i64p, ctypes.c_int32,   # code planes
        i32p,                                           # br_table
        i32p, i32p, i32p, i32p, i32p, i32p, ctypes.c_int32,  # func metas
        i32p,                                           # typeid_of_type
        i32p, i32p, ctypes.c_int32,                     # table/size/cap
        i32p, i32p, i32p, ctypes.c_int32, u8p,          # elem segs + drop
        u8p, i32p, i32p, ctypes.c_int32, u8p,           # data segs + drop
        u64p,                                           # globals
        u8p, ctypes.c_int32, ctypes.c_int32,            # mem, cur/max pages
        ctypes.c_int32, u64p, ctypes.c_int32, u64p,     # func, args, results
        ctypes.c_int32, ctypes.c_int64,                 # depth/stack limits
        i32p,                                           # stop flag
        i64p, i32p,                                     # retired, out_pages
    ]
    _lib = lib
    return lib


def supported_op_ids() -> frozenset:
    """Lowered-op ids the C++ engine implements, parsed from its source's
    `case` labels — eligibility can never drift from the implementation."""
    global _supported_ids
    if _supported_ids is not None:
        return _supported_ids
    src = open(_SRC).read()
    ids = set()
    name_by_macro = {f"OP_{_sanitize(info.name)}": NAME_TO_ID[info.name]
                     for info in OPCODES}
    name_by_macro["LOP_BR_ID"] = LOP_BR
    name_by_macro["LOP_BRZ_ID"] = LOP_BRZ
    name_by_macro["LOP_BRNZ_ID"] = LOP_BRNZ
    for m in re.finditer(r"case\s+(\w+)\s*:", src):
        macro = m.group(1)
        if macro in name_by_macro:
            ids.add(name_by_macro[macro])
    _supported_ids = frozenset(ids)
    return _supported_ids


class NativeModule:
    """Per-module prepared image + eligibility for the native engine."""

    def __init__(self, inst, store=None):
        self.inst = inst
        self.store = store  # funcref handle resolution + write-back
        self.reason: Optional[str] = None
        self._membuf = None  # cached memory transfer buffer
        self._prep(inst, store)

    def _prep(self, inst, store):
        image = inst.lowered
        mod = inst.ast
        if mod is not None and getattr(mod, "imports", None):
            if len(mod.imports.descs) > 0:
                self.reason = "module has imports"
                return
        for fn in image.funcs:
            if fn.is_import:
                self.reason = "imported/host function"
                return
        supported = supported_op_ids()
        for pc2 in range(image.code_len):
            if image.op[pc2] not in supported:
                from wasmedge_tpu.validator.image import lop_name
                self.reason = f"unsupported op {lop_name(image.op[pc2])}"
                return
        # branch/return keep counts are copied through a fixed kept[16]
        # buffer in the C++ loop; wider multi-value stays on Python
        for fn in image.funcs:
            if fn.nresults > 16:
                self.reason = "multi-value arity > 16"
                return
        for pc2 in range(image.code_len):
            if image.op[pc2] in (LOP_BR, LOP_BRNZ) and image.b[pc2] > 16:
                self.reason = "multi-value branch arity > 16"
                return
        arrays0 = image.arrays
        if arrays0["br_table"].size and (arrays0["br_table"][:, 1] > 16).any():
            self.reason = "multi-value branch arity > 16"
            return
        if len(inst.memories) > 1 or len(inst.tables) > 1:
            self.reason = "multiple memories/tables"
            return
        for g in inst.globals:
            if g.value < 0 or g.value >= (1 << 64):
                self.reason = "non-64-bit global"
                return

        arrays = image.arrays
        self.ops = np.ascontiguousarray(arrays["op"], np.int32)
        self.aa = np.ascontiguousarray(arrays["a"], np.int32)
        self.bb = np.ascontiguousarray(arrays["b"], np.int32)
        self.cc = np.ascontiguousarray(arrays["c"], np.int32)
        self.imm = np.ascontiguousarray(arrays["imm"], np.int64)
        self.brt = np.ascontiguousarray(arrays["br_table"].reshape(-1),
                                        np.int32)
        nf = len(image.funcs)
        self.f_entry = np.zeros(nf, np.int32)
        self.f_nparams = np.zeros(nf, np.int32)
        self.f_nlocals = np.zeros(nf, np.int32)
        self.f_nresults = np.zeros(nf, np.int32)
        self.f_ftop = np.zeros(nf, np.int32)
        self.f_typeid = np.zeros(nf, np.int32)
        type_ids = {}

        def dense(ti):
            key = (mod.types[ti].params, mod.types[ti].results) \
                if mod is not None else ti
            return type_ids.setdefault(key, len(type_ids))

        for i, fn in enumerate(image.funcs):
            self.f_entry[i] = fn.entry_pc
            self.f_nparams[i] = fn.nparams
            self.f_nlocals[i] = fn.nlocals
            self.f_nresults[i] = fn.nresults
            self.f_ftop[i] = fn.max_height
            self.f_typeid[i] = dense(fn.type_idx)
        ntypes = len(mod.types) if mod is not None else 0
        self.typeid_of_type = np.asarray(
            [dense(t) for t in range(ntypes)] or [0], np.int32)

        # table snapshot: funcidx+1, 0 = null (device-image convention)
        if inst.tables:
            func_index = {id(f): i for i, f in enumerate(inst.funcs)}
            refs = []
            for h in inst.tables[0].refs:
                if h == 0:
                    refs.append(0)
                    continue
                fi = store.deref_func(h) if store is not None else None
                idx = func_index.get(id(fi)) if fi is not None else None
                if idx is None:
                    self.reason = "table entry references non-local function"
                    return
                refs.append(idx + 1)
            self.table = np.asarray(refs or [0], np.int32)
        else:
            self.table = np.zeros(1, np.int32)

    @property
    def eligible(self) -> bool:
        return self.reason is None

    def _img_args(self, lib):
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)

        def p32(a):
            return a.ctypes.data_as(i32p)

        return (p32(self.ops), p32(self.aa), p32(self.bb), p32(self.cc),
                self.imm.ctypes.data_as(i64p), len(self.ops),
                p32(self.brt), p32(self.f_entry), p32(self.f_nparams),
                p32(self.f_nlocals), p32(self.f_nresults), p32(self.f_ftop),
                p32(self.f_typeid), len(self.f_entry),
                p32(self.typeid_of_type))

    def invoke(self, func_idx: int, raw_args: List[int],
               max_call_depth: int = 2048,
               stop_cell: Optional[np.ndarray] = None):
        """Run one invocation; mutates instance globals/memory in place.
        Returns (results, retired). Raises TrapError on traps."""
        lib = _build_lib()
        inst = self.inst
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)

        glob = np.asarray([g.value for g in inst.globals] or [0], np.uint64)
        if inst.memories:
            m = inst.memories[0]
            cur_pages = m.pages
            max_pages = m.page_limit if m.max is None \
                else min(m.max, m.page_limit)
            # Reuse one max-pages transfer buffer across invokes (np.zeros
            # maps lazily via calloc, so the declared max costs only the
            # pages actually touched).  m.data stays authoritative between
            # calls: copy in before, copy back after.
            buf = self._membuf
            if buf is None or buf.shape[0] != max_pages * 65536:
                buf = np.zeros(max_pages * 65536, np.uint8)
                self._membuf = buf
            # copy (not frombuffer view): a live view would pin the
            # bytearray and make the post-run resize raise BufferError
            n = len(m.data)
            buf[:n] = np.frombuffer(bytes(m.data), np.uint8)
            buf[n:cur_pages * 65536] = 0
        else:
            cur_pages = 0
            max_pages = 0
            buf = np.zeros(8, np.uint8)
        meta = inst.lowered.funcs[func_idx]
        args = np.asarray([a & ((1 << 64) - 1) for a in raw_args] or [0],
                          np.uint64)
        results = np.zeros(max(meta.nresults, 1), np.uint64)
        retired = np.zeros(1, np.int64)
        out_pages = np.zeros(1, np.int32)
        if stop_cell is None:
            stop_cell = np.zeros(1, np.int32)

        # Mutable table + segment state, rebuilt per invoke from the
        # instance (the scalar engine persists mutations across invokes;
        # so must this one) and written back after.  Capacity: declared
        # max when present, else a 64k-headroom growth window (growth
        # beyond it returns -1, which the spec allows at any size).
        u8p_ = u8p
        func_index = {id(f): i for i, f in enumerate(inst.funcs)}

        def to_handle_plane(refs):
            out = np.zeros(max(len(refs), 1), np.int32)
            for i, h in enumerate(refs):
                if h == 0:
                    continue
                fi = store.deref_func(h) if store is not None else None
                idx = func_index.get(id(fi)) if fi is not None else None
                if idx is None:
                    raise RuntimeError("non-local funcref in table/elem")
            # second pass fills (first pass validated)
            for i, h in enumerate(refs):
                if h:
                    out[i] = func_index[id(store.deref_func(h))] + 1
            return out

        store = self.store
        if inst.tables:
            t0 = inst.tables[0]
            tsize0 = t0.size
            tcap = t0.max if t0.max is not None else tsize0 + 65536
            tcap = max(tcap, tsize0)
            tbl = np.zeros(max(tcap, 1), np.int32)
            tbl[:tsize0] = to_handle_plane(t0.refs)[:tsize0] \
                if tsize0 else tbl[:0]
        else:
            tsize0, tcap = 0, 0
            tbl = np.zeros(1, np.int32)
        tsize_io = np.asarray([tsize0], np.int32)
        esegs = inst.elems
        eoff = np.zeros(max(len(esegs), 1), np.int32)
        elen = np.zeros(max(len(esegs), 1), np.int32)
        eflat_parts = []
        acc = 0
        for i, seg in enumerate(esegs):
            eoff[i] = acc
            elen[i] = len(seg.refs)
            eflat_parts.append(to_handle_plane(seg.refs)[:len(seg.refs)])
            acc += len(seg.refs)
        eflat = np.concatenate(eflat_parts) if acc else np.zeros(1, np.int32)
        edrop = np.zeros(max(len(esegs), 1), np.uint8)
        for i, seg in enumerate(esegs):
            if not seg.refs:
                edrop[i] = 1  # dropped (or empty) segment: length 0
        dsegs = inst.datas
        doff = np.zeros(max(len(dsegs), 1), np.int32)
        dlen = np.zeros(max(len(dsegs), 1), np.int32)
        dacc = bytearray()
        for i, seg in enumerate(dsegs):
            doff[i] = len(dacc)
            dlen[i] = len(seg.data)
            dacc.extend(seg.data)
        dflat = np.frombuffer(bytes(dacc) or b"\0", np.uint8).copy()
        ddrop = np.zeros(max(len(dsegs), 1), np.uint8)

        rc = lib.we_native_invoke(
            *self._img_args(lib),
            tbl.ctypes.data_as(i32p), tsize_io.ctypes.data_as(i32p),
            int(tcap),
            eflat.ctypes.data_as(i32p), eoff.ctypes.data_as(i32p),
            elen.ctypes.data_as(i32p), len(esegs),
            edrop.ctypes.data_as(u8p_),
            dflat.ctypes.data_as(u8p_), doff.ctypes.data_as(i32p),
            dlen.ctypes.data_as(i32p), len(dsegs),
            ddrop.ctypes.data_as(u8p_),
            glob.ctypes.data_as(u64p),
            buf.ctypes.data_as(u8p), cur_pages, max_pages,
            func_idx, args.ctypes.data_as(u64p), len(raw_args),
            results.ctypes.data_as(u64p),
            max_call_depth, 1 << 20,
            stop_cell.ctypes.data_as(i32p),
            retired.ctypes.data_as(i64p),
            out_pages.ctypes.data_as(i32p))

        # write state back (even on trap: partial effects are observable,
        # matching the Python engine which mutates in place)
        for i, g in enumerate(inst.globals):
            g.value = int(glob[i])
        if inst.memories:
            m = inst.memories[0]
            nbytes = int(out_pages[0]) * 65536
            m.data[:] = buf[:nbytes].tobytes()
        if inst.tables:
            t0 = inst.tables[0]
            ns = int(tsize_io[0])
            new_refs = []
            for i in range(ns):
                h = int(tbl[i])
                new_refs.append(
                    0 if h == 0 else
                    (store.intern_ref(inst.funcs[h - 1])
                     if store is not None else h))
            t0.refs = new_refs
        for i, seg in enumerate(esegs):
            if edrop[i] and seg.refs:
                seg.clear()
        for i, seg in enumerate(dsegs):
            if ddrop[i] and seg.data:
                seg.clear()
        if rc != 0:
            raise TrapError(ErrCode(rc))
        return [int(results[i]) for i in range(meta.nresults)], int(retired[0])


def module_for(inst, store=None) -> NativeModule:
    return NativeModule(inst, store)

