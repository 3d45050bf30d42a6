"""Embedding-API suite: unit coverage + the spec corpus through the VM
family — the reference's APIUnitTest + APIVMCoreTest pattern
(test/api/APIUnitTest.cpp, APIVMCoreTest.cpp:1-244)."""

import glob
import os

import numpy as np
import pytest

from wasmedge_tpu import capi as C
from wasmedge_tpu.models import build_fib
from wasmedge_tpu.spec import SpecTest
from wasmedge_tpu.common.errors import ErrCode, TrapError
from wasmedge_tpu.utils.builder import ModuleBuilder

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# value / result / configure units
# ---------------------------------------------------------------------------

def test_value_roundtrips():
    assert C.we_ValueGetI32(C.we_ValueGenI32(-5)) == -5
    assert C.we_ValueGetI32(C.we_ValueGenI32(0x7FFFFFFF)) == 0x7FFFFFFF
    assert C.we_ValueGetI64(C.we_ValueGenI64(-(2**63))) == -(2**63)
    assert C.we_ValueGetF32(C.we_ValueGenF32(1.5)) == 1.5
    assert C.we_ValueGetF64(C.we_ValueGenF64(-2.25)) == -2.25
    v = C.we_ValueGenF32(float("nan"))
    assert C.we_ValueGetF32(v) != C.we_ValueGetF32(v)  # NaN


def test_wasi_host_registration_via_capi():
    conf = C.we_ConfigureCreate()
    C.we_ConfigureAddHostRegistration(conf, "wasi")
    vm = C.we_VMCreate(conf)
    assert vm.vm.wasi_module is not None
    b = ModuleBuilder()
    b.import_func("wasi_snapshot_preview1", "args_sizes_get",
                  ["i32", "i32"], ["i32"])
    b.add_memory(1, 1)
    b.add_function([], ["i32"], [], [
        ("i32.const", 0), ("i32.const", 8), ("call", 0),
    ], export="f")
    res, out = C.we_VMRunWasmFromBuffer(vm, b.build(), "f")
    assert C.we_ResultOK(res)
    assert C.we_ValueGetI32(out[0]) == 0  # Errno.SUCCESS


def test_arity_mismatch_is_result():
    vm = C.we_VMCreate()
    res, out = C.we_VMRunWasmFromBuffer(vm, build_fib(), "fib", [])
    assert not C.we_ResultOK(res)
    assert C.we_ResultGetCode(res) == int(ErrCode.FuncSigMismatch)


def test_missing_file_is_result():
    vm = C.we_VMCreate()
    res, out = C.we_VMRunWasmFromFile(vm, "/nonexistent/x.wasm", "f")
    assert not C.we_ResultOK(res)
    assert C.we_ResultGetCode(res) == int(ErrCode.IllegalPath)


def test_configure_knobs():
    conf = C.we_ConfigureCreate()
    C.we_ConfigureAddProposal(conf, "tail-call")
    assert C.we_ConfigureHasProposal(conf, "tail-call")
    assert C.we_ConfigureHasProposal(conf, "simd")  # default-on
    C.we_ConfigureRemoveProposal(conf, "tail-call")
    assert not C.we_ConfigureHasProposal(conf, "tail-call")
    C.we_ConfigureAddHostRegistration(conf, "wasi")
    assert C.we_ConfigureHasHostRegistration(conf, "wasi")
    C.we_ConfigureSetMaxMemoryPage(conf, 16)
    assert C.we_ConfigureGetMaxMemoryPage(conf) == 16
    C.we_ConfigureSetEngine(conf, "native")
    assert C.we_ConfigureGetEngine(conf) == "native"
    C.we_ConfigureStatisticsSetInstructionCounting(conf, True)
    assert C.we_ConfigureStatisticsIsInstructionCounting(conf)


# ---------------------------------------------------------------------------
# staged pipeline (APIStepsCoreTest model)
# ---------------------------------------------------------------------------

def test_staged_pipeline():
    conf = C.we_ConfigureCreate()
    loader = C.we_LoaderCreate(conf)
    res, mod = C.we_LoaderParseFromBuffer(loader, build_fib())
    assert C.we_ResultOK(res)
    assert C.we_ASTModuleListExports(mod) == [("fib", "func")]
    validator = C.we_ValidatorCreate(conf)
    assert C.we_ResultOK(C.we_ValidatorValidate(validator, mod))
    store = C.we_StoreCreate()
    ex = C.we_ExecutorCreate(conf)
    res, inst = C.we_ExecutorInstantiate(ex, store, mod)
    assert C.we_ResultOK(res)
    fi = C.we_ModuleInstanceFindFunction(inst, "fib")
    assert fi is not None
    res, out = C.we_ExecutorInvoke(ex, store, fi, [C.we_ValueGenI32(10)])
    assert C.we_ResultOK(res)
    assert C.we_ValueGetI32(out[0]) == 55


def test_malformed_module_result():
    loader = C.we_LoaderCreate()
    res, mod = C.we_LoaderParseFromBuffer(loader, b"\x00asm\x02\x00\x00\x00")
    assert not C.we_ResultOK(res)
    assert C.we_ResultGetCode(res) == int(ErrCode.MalformedVersion)
    assert mod is None


# ---------------------------------------------------------------------------
# VM family
# ---------------------------------------------------------------------------

def test_vm_run_wasm():
    vm = C.we_VMCreate()
    res, out = C.we_VMRunWasmFromBuffer(vm, build_fib(), "fib",
                                        [C.we_ValueGenI32(12)])
    assert C.we_ResultOK(res)
    assert C.we_ValueGetI32(out[0]) == 144
    funcs = C.we_VMGetFunctionList(vm)
    assert funcs[0][0] == "fib"
    ft = C.we_VMGetFunctionType(vm, "fib")
    assert len(ft.params) == 1 and len(ft.results) == 1


def test_vm_trap_result():
    b = ModuleBuilder()
    b.add_function([], [], [], [("unreachable",)], export="boom")
    vm = C.we_VMCreate()
    res, out = C.we_VMRunWasmFromBuffer(vm, b.build(), "boom")
    assert not C.we_ResultOK(res)
    assert C.we_ResultGetCode(res) == int(ErrCode.Unreachable)


def test_vm_register_and_imports():
    lib = ModuleBuilder()
    lib.add_function(["i32"], ["i32"], [],
                     [("local.get", 0), ("i32.const", 2), "i32.mul"],
                     export="double")
    vm = C.we_VMCreate()
    assert C.we_ResultOK(
        C.we_VMRegisterModuleFromBuffer(vm, "lib", lib.build()))
    res, out = C.we_VMExecuteRegistered(vm, "lib", "double",
                                        [C.we_ValueGenI32(21)])
    assert C.we_ResultOK(res)
    assert C.we_ValueGetI32(out[0]) == 42

    # host import object + wasm importing it
    imp = C.we_ImportObjectCreate("env")
    seen = []
    C.we_ImportObjectAddFunction(imp, "note", ["i32"], ["i32"],
                                 lambda mem, x: (seen.append(x), x + 1)[1])
    assert C.we_ResultOK(C.we_VMRegisterModuleFromImport(vm, imp))
    user = ModuleBuilder()
    user.import_func("env", "note", ["i32"], ["i32"])
    user.add_function(["i32"], ["i32"], [],
                      [("local.get", 0), ("call", 0)], export="f")
    res, out = C.we_VMRunWasmFromBuffer(vm, user.build(), "f",
                                        [C.we_ValueGenI32(7)])
    assert C.we_ResultOK(res)
    assert C.we_ValueGetI32(out[0]) == 8
    assert seen == [7]


def test_vm_async_execute_and_cancel():
    b = ModuleBuilder()
    b.add_function([], [], [], [("loop",), ("br", 0), ("end",)],
                   export="spin")
    vm = C.we_VMCreate()
    assert C.we_ResultOK(C.we_VMLoadWasmFromBuffer(vm, b.build()))
    assert C.we_ResultOK(C.we_VMValidate(vm))
    assert C.we_ResultOK(C.we_VMInstantiate(vm))
    h = C.we_VMAsyncExecute(vm, "spin")
    assert not C.we_AsyncWaitFor(h, 100)
    C.we_AsyncCancel(h)
    res, _ = C.we_AsyncGet(h)
    assert C.we_ResultGetCode(res) == int(ErrCode.Terminated)


def test_vm_async_f64_roundtrip():
    """Raw float cells must survive the async (typed) path unchanged."""
    b = ModuleBuilder()
    b.add_function(["f64"], ["f64"], [],
                   [("local.get", 0)], export="id")
    vm = C.we_VMCreate()
    assert C.we_ResultOK(C.we_VMLoadWasmFromBuffer(vm, b.build()))
    assert C.we_ResultOK(C.we_VMValidate(vm))
    assert C.we_ResultOK(C.we_VMInstantiate(vm))
    h = C.we_VMAsyncExecute(vm, "id", [C.we_ValueGenF64(1.5)])
    res, out = C.we_AsyncGet(h)
    assert C.we_ResultOK(res)
    assert C.we_ValueGetF64(out[0]) == 1.5


def test_vm_statistics():
    conf = C.we_ConfigureCreate()
    C.we_ConfigureStatisticsSetInstructionCounting(conf, True)
    vm = C.we_VMCreate(conf)
    res, out = C.we_VMRunWasmFromBuffer(vm, build_fib(), "fib",
                                        [C.we_ValueGenI32(10)])
    assert C.we_ResultOK(res)
    stat = C.we_VMGetStatisticsContext(vm)
    assert C.we_StatisticsGetInstrCount(stat) > 100


def test_memory_and_global_accessors():
    b = ModuleBuilder()
    b.add_memory(1, 2, export="mem")
    b.add_global("i64", True, [("i64.const", -7)], export="g")
    b.add_function([], [], [], [], export="noop")
    vm = C.we_VMCreate()
    res, _ = C.we_VMRunWasmFromBuffer(vm, b.build(), "noop")
    assert C.we_ResultOK(res)
    inst = vm.vm.active_module
    mem = C.we_ModuleInstanceFindMemory(inst, "mem")
    assert C.we_MemoryInstanceGetPageSize(mem) == 1
    assert C.we_ResultOK(C.we_MemoryInstanceSetData(mem, 8, b"\xAA\xBB"))
    res, data = C.we_MemoryInstanceGetData(mem, 8, 2)
    assert data == b"\xAA\xBB"
    assert C.we_ResultOK(C.we_MemoryInstanceGrowPage(mem, 1))
    assert C.we_MemoryInstanceGetPageSize(mem) == 2
    assert not C.we_ResultOK(C.we_MemoryInstanceGrowPage(mem, 10))
    g = C.we_ModuleInstanceFindGlobal(inst, "g")
    gv = C.we_GlobalInstanceGetValue(g)
    assert gv.type == "i64"
    assert C.we_ValueGetI64(gv) == -7


def test_vm_batch_extension():
    vm = C.we_VMCreate()
    assert C.we_ResultOK(C.we_VMLoadWasmFromBuffer(vm, build_fib()))
    assert C.we_ResultOK(C.we_VMValidate(vm))
    assert C.we_ResultOK(C.we_VMInstantiate(vm))
    res, batch = C.we_VMBatchExecute(
        vm, "fib", [np.full(8, 10, np.int64)], lanes=8)
    assert C.we_ResultOK(res)
    assert (batch.results[0] == 55).all()


def test_vm_batch_weighted_cost_table_gas():
    """A non-uniform cost table set through the C API drives the batch
    engine's fuel: the weighted kill fires where flat per-instruction
    counting would not (reference: CostTab-weighted gas,
    include/common/statistics.h:85-98)."""
    from wasmedge_tpu.common.errors import ErrCode
    from wasmedge_tpu.common.opcodes import NAME_TO_ID
    from wasmedge_tpu.common.statistics import _NUM_COST_SLOTS

    def make_vm(limit, table=None):
        conf = C.we_ConfigureCreate()
        C.we_ConfigureStatisticsSetCostMeasuring(conf, True)
        vm = C.we_VMCreate(conf)
        stat = C.we_VMGetStatisticsContext(vm)
        C.we_StatisticsSetCostLimit(stat, limit)
        if table is not None:
            C.we_StatisticsSetCostTable(stat, table)
        assert C.we_ResultOK(C.we_VMLoadWasmFromBuffer(vm, build_fib()))
        assert C.we_ResultOK(C.we_VMValidate(vm))
        assert C.we_ResultOK(C.we_VMInstantiate(vm))
        return vm

    # fib(15) retires ~10k instructions / ~1.2k i32.add ops.  A flat
    # budget of 100k completes easily...
    vm = make_vm(100_000)
    res, ok = C.we_VMBatchExecute(vm, "fib", [np.full(4, 15, np.int64)],
                                  lanes=4)
    assert C.we_ResultOK(res) and (ok.trap == -1).all()
    # ...but the same budget with i32.add weighted 1000x must kill every
    # lane with the gas trap: ~1.2k adds * 1000 >> 100k
    table = [1] * _NUM_COST_SLOTS
    table[int(NAME_TO_ID["i32.add"])] = 1000
    vm = make_vm(100_000, table)
    res, killed = C.we_VMBatchExecute(vm, "fib",
                                      [np.full(4, 15, np.int64)], lanes=4)
    assert C.we_ResultOK(res)
    assert (killed.trap == int(ErrCode.CostLimitExceeded)).all()
    # a uniform-weight run under the same table geometry still completes
    vm = make_vm(100_000, [1] * _NUM_COST_SLOTS)
    res, ok2 = C.we_VMBatchExecute(vm, "fib", [np.full(4, 15, np.int64)],
                                   lanes=4)
    assert C.we_ResultOK(res) and (ok2.trap == -1).all()


# ---------------------------------------------------------------------------
# the spec corpus through the capi VM family (APIVMCoreTest model)
# ---------------------------------------------------------------------------

def _capi_spec_callbacks(conf=None):
    vm = C.we_VMCreate(conf)
    bytes_of = {}  # handle -> module bytes (register replays them)

    def on_module(name, data):
        if name:
            res = C.we_VMRegisterModuleFromBuffer(vm, name.lstrip("$"), data)
            _raise(res)
            h = ("named", name.lstrip("$"))
            bytes_of[h] = data
            return h
        res = C.we_VMLoadWasmFromBuffer(vm, data)
        _raise(res)
        _raise(C.we_VMValidate(vm))
        _raise(C.we_VMInstantiate(vm))
        h = ("active", None)
        bytes_of[h] = data
        return h

    def _raise(res):
        if not C.we_ResultOK(res):
            code = ErrCode(C.we_ResultGetCode(res))
            from wasmedge_tpu.common.errors import (
                LoadError, ValidationError)
            msg = C.we_ResultGetMessage(res)
            if int(code) < 0x40:
                raise LoadError(code, msg)
            if int(code) < 0x80:
                raise ValidationError(code, msg)
            raise TrapError(code, msg)

    def on_invoke(handle, field, raw_args):
        kind, name = handle
        params = [C.we_Value("raw", a) for a in raw_args]
        if kind == "named":
            res, out = C.we_VMExecuteRegistered(vm, name, field, params)
        else:
            res, out = C.we_VMExecute(vm, field, params)
        _raise(res)
        return [v.raw for v in out]

    def on_register(handle, as_name):
        # replay the module bytes under the new namespace (the C API has
        # no alias-an-instance entry; state-aliasing register chains are
        # covered by the scalar harness)
        data = bytes_of.get(handle)
        if data is None:
            raise TrapError(ErrCode.FuncNotFound,
                            "register of unknown module")
        _raise(C.we_VMRegisterModuleFromBuffer(vm, as_name, data))

    return SpecTest(on_module, on_invoke, on_register)


def test_spec_corpus_through_capi():
    corpus = sorted(glob.glob(os.path.join(HERE, "spec", "*.wast")))
    assert corpus
    from wasmedge_tpu.spec import _conf_for_file

    total_passed = 0
    for path in corpus:
        # per-file proposal gating, as run_corpus does (tail_call.wast
        # needs the TailCall proposal enabled)
        st = _capi_spec_callbacks(_conf_for_file(path))
        with open(path) as f:
            rep = st.run_script(f.read(), os.path.basename(path))
        detail = "\n".join(str(x) for x in rep.failures[:10])
        assert rep.failed == 0, f"{path}: {rep.failed} failed\n{detail}"
        total_passed += rep.passed
    assert total_passed > 9900


# ---------------------------------------------------------------------------
# round-3 families: types, instance creation, ImportObjectAdd*, Compiler
# ---------------------------------------------------------------------------

def test_function_type_contexts():
    ft = C.we_FunctionTypeCreate(["i32", "i64"], ["f64"])
    assert C.we_FunctionTypeGetParametersLength(ft) == 2
    assert C.we_FunctionTypeGetParameters(ft) == ["i32", "i64"]
    assert C.we_FunctionTypeGetReturnsLength(ft) == 1
    assert C.we_FunctionTypeGetReturns(ft) == ["f64"]
    C.we_FunctionTypeDelete(ft)


def test_table_memory_global_types_and_instances():
    tt = C.we_TableTypeCreate("funcref", 4, 8)
    assert C.we_TableTypeGetRefType(tt) == "funcref"
    assert C.we_TableTypeGetLimit(tt) == (4, 8)
    tab = C.we_TableInstanceCreate(tt)
    assert C.we_TableInstanceGetSize(tab) == 4
    res = C.we_TableInstanceSetData(tab, 2, 7)
    assert C.we_ResultOK(res)
    res, ref = C.we_TableInstanceGetData(tab, 2)
    assert C.we_ResultOK(res) and ref == 7
    res, _ = C.we_TableInstanceGetData(tab, 99)
    assert not C.we_ResultOK(res)
    assert C.we_ResultOK(C.we_TableInstanceGrow(tab, 2))
    assert C.we_TableInstanceGetSize(tab) == 6

    mt = C.we_MemoryTypeCreate(1, 2)
    assert C.we_MemoryTypeGetLimit(mt) == (1, 2)
    mem = C.we_MemoryInstanceCreate(mt)
    assert C.we_MemoryInstanceGetPageSize(mem) == 1

    gt = C.we_GlobalTypeCreate("i64", True)
    assert C.we_GlobalTypeGetValType(gt) == "i64"
    assert C.we_GlobalTypeGetMutability(gt)
    g = C.we_GlobalInstanceCreate(gt, C.we_Value("i64", -5))
    assert C.we_GlobalInstanceGetGlobalType(g).mutable


def test_import_object_add_table_memory_global():
    """A module importing a host table/memory/global through the
    ImportObjectAdd* family (reference: ImportObjectAddTable etc.)."""
    imp = C.we_ImportObjectCreate("env")
    tab = C.we_TableInstanceCreate(C.we_TableTypeCreate("funcref", 2, 2))
    mem = C.we_MemoryInstanceCreate(C.we_MemoryTypeCreate(1, 1))
    glob = C.we_GlobalInstanceCreate(C.we_GlobalTypeCreate("i32", False),
                                     C.we_Value("i32", 41))
    C.we_ImportObjectAddTable(imp, "t", tab)
    C.we_ImportObjectAddMemory(imp, "m", mem)
    C.we_ImportObjectAddGlobal(imp, "g", glob)

    b = ModuleBuilder()
    b.import_table("env", "t", "funcref", 2, 2)
    b.import_memory("env", "m", 1, 1)
    b.import_global("env", "g", "i32", False)
    b.add_function([], ["i32"], [], [
        ("i32.const", 64), ("i32.const", 7), ("i32.store", 2, 0),
        ("i32.const", 64), ("i32.load", 2, 0),
        ("global.get", 0), "i32.add",
    ], export="f")
    vm = C.we_VMCreate()
    assert C.we_ResultOK(C.we_VMRegisterModuleFromImport(vm, imp))
    res, out = C.we_VMRunWasmFromBuffer(vm, b.build(), "f", [])
    assert C.we_ResultOK(res), res
    assert C.we_ValueGetI32(out[0]) == 48
    # the host memory instance saw the guest's store
    assert mem.load(64, 4, False) == 7


def test_compiler_family(tmp_path):
    from wasmedge_tpu.models import build_fib

    src = tmp_path / "fib.wasm"
    out = tmp_path / "fib.twasm"
    src.write_bytes(build_fib())
    comp = C.we_CompilerCreate()
    res = C.we_CompilerCompile(comp, str(src), str(out))
    assert C.we_ResultOK(res)
    data = out.read_bytes()
    assert b"tpu.aot" in data
    # buffer variant round-trips and still runs through the VM
    res, buf = C.we_CompilerCompileFromBuffer(comp, build_fib())
    assert C.we_ResultOK(res)
    vm = C.we_VMCreate()
    res, outv = C.we_VMRunWasmFromBuffer(vm, bytes(buf), "fib",
                                         [C.we_Value("i32", 12)])
    assert C.we_ResultOK(res)
    assert C.we_ValueGetI32(outv[0]) == 144
    C.we_CompilerDelete(comp)


def test_version_and_listings():
    assert C.we_VersionGet().startswith("0.9.1")
    assert C.we_VersionGetMajor() == 0
    assert C.we_VersionGetMinor() == 9
    b = ModuleBuilder()
    b.add_memory(1, 1, export="m")
    b.add_global("i32", False, [("i32.const", 3)], export="g")
    b.add_function([], ["i32"], [], [("i32.const", 1)], export="f")
    vm = C.we_VMCreate()
    assert C.we_ResultOK(C.we_VMLoadWasmFromBuffer(vm, b.build()))
    assert C.we_ResultOK(C.we_VMValidate(vm))
    assert C.we_ResultOK(C.we_VMInstantiate(vm))
    inst = C.we_VMGetActiveModule(vm)
    assert C.we_ModuleInstanceListFunctionLength(inst) == 1
    assert C.we_ModuleInstanceListMemory(inst) == ["m"]
    assert C.we_ModuleInstanceListGlobal(inst) == ["g"]


# ---------------------------------------------------------------------------
# round-4 parity families: String, ref Values, Compiler knobs,
# Import/Export type contexts, Store find/list remainder, standalone
# host FunctionInstance, memory pointers, VM ASTModule/async-run forms
# (reference: wasmedge.h families; parity table in CAPI_PARITY.md)
# ---------------------------------------------------------------------------

def _fib_mod():
    conf = C.we_ConfigureCreate()
    loader = C.we_LoaderCreate(conf)
    res, mod = C.we_LoaderParseFromBuffer(loader, build_fib())
    assert C.we_ResultOK(res)
    return conf, mod


def test_string_family():
    s = C.we_StringCreateByCString("hello")
    assert C.we_StringIsEqual(s, C.we_StringWrap("hello"))
    assert not C.we_StringIsEqual(s, C.we_StringCreateByCString("world"))
    b = C.we_StringCreateByBuffer(b"hello world", 5)
    assert C.we_StringIsEqual(s, b)
    assert C.we_StringCopy(3, s) == "hel"
    C.we_StringDelete(s)


def test_result_constants():
    assert C.we_ResultOK(C.we_Result_Success)
    assert not C.we_ResultOK(C.we_Result_Terminate)
    assert not C.we_ResultOK(C.we_Result_Fail)
    assert C.we_ResultGetCode(C.we_Result_Terminate) == int(
        ErrCode.Terminated)


def test_ref_values():
    st = C.we_StoreCreate()
    null = C.we_ValueGenNullRef("funcref")
    assert C.we_ValueIsNullRef(null)
    fr = C.we_ValueGenFuncRef(7)
    assert not C.we_ValueIsNullRef(fr)
    assert C.we_ValueGetFuncRef(fr) == 7
    obj = {"k": 1}
    er = C.we_ValueGenExternRef(st, obj)
    assert C.we_ValueGetExternRef(st, er) is obj
    v = C.we_ValueGenV128((1 << 100) | 5)
    assert C.we_ValueGetV128(v) == (1 << 100) | 5


def test_compiler_configure_knobs():
    conf = C.we_ConfigureCreate()
    assert C.we_ConfigureCompilerGetOptimizationLevel(conf) == "O3"
    C.we_ConfigureCompilerSetOptimizationLevel(conf, "Os")
    assert C.we_ConfigureCompilerGetOptimizationLevel(conf) == "Os"
    C.we_ConfigureCompilerSetOutputFormat(conf, "Native")
    assert C.we_ConfigureCompilerGetOutputFormat(conf) == "Native"
    for setter, getter in (
            (C.we_ConfigureCompilerSetDumpIR,
             C.we_ConfigureCompilerIsDumpIR),
            (C.we_ConfigureCompilerSetGenericBinary,
             C.we_ConfigureCompilerIsGenericBinary),
            (C.we_ConfigureCompilerSetInterruptible,
             C.we_ConfigureCompilerIsInterruptible)):
        assert getter(conf) is False
        setter(conf, True)
        assert getter(conf) is True


def test_import_export_type_contexts():
    b = ModuleBuilder()
    b.import_func("env", "h", ["i32"], ["i32"])
    b.add_memory(1, 4)
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("call", 0),
    ], export="go")
    conf = C.we_ConfigureCreate()
    loader = C.we_LoaderCreate(conf)
    _res, mod = C.we_LoaderParseFromBuffer(loader, b.build())
    assert C.we_ASTModuleListImportsLength(mod) == 1
    its = C.we_ASTModuleListImportTypes(mod)
    it = its[0]
    assert C.we_ImportTypeGetModuleName(it) == "env"
    assert C.we_ImportTypeGetExternalName(it) == "h"
    assert C.we_ImportTypeGetExternalType(it) == "func"
    ft = C.we_ImportTypeGetFunctionType(it)
    assert len(ft.params) == 1 and len(ft.results) == 1
    assert C.we_ImportTypeGetTableType(it) is None
    assert C.we_ASTModuleListExportsLength(mod) >= 1
    ets = C.we_ASTModuleListExportTypes(mod)
    go = [e for e in ets if C.we_ExportTypeGetExternalName(e) == "go"][0]
    assert C.we_ExportTypeGetExternalType(go) == "func"
    ft2 = C.we_ExportTypeGetFunctionType(go)
    assert len(ft2.params) == 1
    # tuple-compat iteration (pre-round-4 shape)
    m, n, k = it
    assert (m, n, k) == ("env", "h", "func")
    C.we_ASTModuleDelete(mod)


def test_limit_is_equal():
    from wasmedge_tpu.loader.ast import Limit

    assert C.we_LimitIsEqual(Limit(1, 4), Limit(1, 4))
    assert not C.we_LimitIsEqual(Limit(1, 4), Limit(1, 5))
    assert not C.we_LimitIsEqual(Limit(1, None), Limit(1, 4))


def test_store_find_and_list_families():
    b = ModuleBuilder()
    b.add_memory(1, 2, export="mem")
    b.add_global("i32", True, [("i32.const", 7)], export="g")
    b.add_function([], ["i32"], [], [("i32.const", 3)], export="f")
    data = b.build()
    conf = C.we_ConfigureCreate()
    vm = C.we_VMCreate(conf)
    assert C.we_ResultOK(C.we_VMRegisterModuleFromBuffer(vm, "m", data))
    res, _ = C.we_VMRunWasmFromBuffer(vm, data, "f")
    assert C.we_ResultOK(res)
    store = C.we_VMGetStoreContext(vm)
    assert C.we_StoreGetActiveModule(store) is not None
    assert C.we_StoreFindFunction(store, "f") is not None
    assert C.we_StoreFindMemory(store, "mem") is not None
    assert C.we_StoreFindGlobal(store, "g") is not None
    assert C.we_StoreFindTable(store, "nope") is None
    assert C.we_StoreListFunction(store) == ["f"]
    assert C.we_StoreListFunctionLength(store) == 1
    assert C.we_StoreListMemory(store) == ["mem"]
    assert C.we_StoreListMemoryLength(store) == 1
    assert C.we_StoreListGlobal(store) == ["g"]
    assert C.we_StoreListGlobalLength(store) == 1
    assert C.we_StoreListTable(store) == []
    assert C.we_StoreListTableLength(store) == 0
    # registered variants
    assert C.we_StoreFindMemoryRegistered(store, "m", "mem") is not None
    assert C.we_StoreFindGlobalRegistered(store, "m", "g") is not None
    assert C.we_StoreFindTableRegistered(store, "m", "nope") is None
    assert C.we_StoreListFunctionRegistered(store, "m") == ["f"]
    assert C.we_StoreListFunctionRegisteredLength(store, "m") == 1
    assert C.we_StoreListMemoryRegistered(store, "m") == ["mem"]
    assert C.we_StoreListMemoryRegisteredLength(store, "m") == 1
    assert C.we_StoreListGlobalRegisteredLength(store, "m") == 1
    assert C.we_StoreListTableRegisteredLength(store, "m") == 0


def test_function_instance_create_and_executor_invoke_registered():
    ft = C.we_FunctionTypeCreate(["i32", "i32"], ["i32"])
    seen = []

    def host(data, mem, vals):
        seen.append(data)
        a = C.we_ValueGetI32(vals[0])
        bb = C.we_ValueGetI32(vals[1])
        return C.we_Result_Success, [C.we_ValueGenI32(a * bb)]

    fi = C.we_FunctionInstanceCreate(ft, host, data="tok")
    imp = C.we_ImportObjectCreate("env")
    imp.add_func("mul", fi)
    b = ModuleBuilder()
    b.import_func("env", "mul", ["i32", "i32"], ["i32"])
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.const", 6), ("call", 0),
    ], export="six_times")
    conf = C.we_ConfigureCreate()
    vm = C.we_VMCreate(conf)
    assert C.we_ResultOK(C.we_VMRegisterModuleFromImport(vm, imp))
    res, out = C.we_VMRunWasmFromBuffer(
        vm, b.build(), "six_times", [C.we_ValueGenI32(7)])
    assert C.we_ResultOK(res)
    assert C.we_ValueGetI32(out[0]) == 42
    assert seen == ["tok"]
    # ExecutorInvokeRegistered against the named host module
    ex = C.we_ExecutorCreate(conf)
    store = C.we_VMGetStoreContext(vm)
    res, out = C.we_ExecutorInvokeRegistered(
        ex, store, "env", "mul",
        [C.we_ValueGenI32(3), C.we_ValueGenI32(5)])
    assert C.we_ResultOK(res)
    assert C.we_ValueGetI32(out[0]) == 15


def test_function_instance_create_binding():
    ft = C.we_FunctionTypeCreate(["i32"], ["i32"])

    def wrap(binding, data, mem, vals):
        assert binding == "BIND" and data == "DATA"
        return C.we_Result_Success, [
            C.we_ValueGenI32(C.we_ValueGetI32(vals[0]) + 1)]

    fi = C.we_FunctionInstanceCreateBinding(ft, wrap, binding="BIND",
                                            data="DATA")
    imp = C.we_ImportObjectCreate("env")
    assert C.we_ImportObjectGetModuleName(imp) == "env"
    imp.add_func("inc", fi)
    b = ModuleBuilder()
    b.import_func("env", "inc", ["i32"], ["i32"])
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("call", 0)], export="f")
    vm = C.we_VMCreate(C.we_ConfigureCreate())
    C.we_VMRegisterModuleFromImport(vm, imp)
    res, out = C.we_VMRunWasmFromBuffer(vm, b.build(), "f",
                                        [C.we_ValueGenI32(41)])
    assert C.we_ResultOK(res) and C.we_ValueGetI32(out[0]) == 42


def test_memory_pointers():
    b = ModuleBuilder()
    b.add_memory(1, 2, export="mem")
    b.add_function([], ["i32"], [], [
        ("i32.const", 16), ("i32.load", 2, 0)], export="peek")
    vm = C.we_VMCreate(C.we_ConfigureCreate())
    res, _ = C.we_VMRunWasmFromBuffer(vm, b.build(), "peek")
    assert C.we_ResultOK(res)
    mem = C.we_StoreFindMemory(C.we_VMGetStoreContext(vm), "mem")
    view = C.we_MemoryInstanceGetPointer(mem, 16, 4)
    view[:4] = (1234567).to_bytes(4, "little")
    res, out = C.we_VMExecute(vm, "peek")
    assert C.we_ValueGetI32(out[0]) == 1234567
    const = C.we_MemoryInstanceGetPointerConst(mem, 16, 4)
    assert const == (1234567).to_bytes(4, "little")
    with pytest.raises(TrapError):
        C.we_MemoryInstanceGetPointer(mem, 65536 - 2, 4)


def test_vm_astmodule_and_file_forms(tmp_path):
    conf, mod = _fib_mod()
    vm = C.we_VMCreate(conf)
    res, out = C.we_VMRunWasmFromASTModule(vm, mod, "fib",
                                           [C.we_ValueGenI32(10)])
    assert C.we_ResultOK(res) and C.we_ValueGetI32(out[0]) == 55
    # load-from-AST staged form
    vm2 = C.we_VMCreate(C.we_ConfigureCreate())
    assert C.we_ResultOK(C.we_VMLoadWasmFromASTModule(vm2, mod))
    assert C.we_ResultOK(C.we_VMValidate(vm2))
    assert C.we_ResultOK(C.we_VMInstantiate(vm2))
    res, out = C.we_VMExecute(vm2, "fib", [C.we_ValueGenI32(9)])
    assert C.we_ValueGetI32(out[0]) == 34
    # register-from-AST / from-file
    vm3 = C.we_VMCreate(C.we_ConfigureCreate())
    assert C.we_ResultOK(C.we_VMRegisterModuleFromASTModule(vm3, "m", mod))
    assert C.we_VMGetFunctionTypeRegistered(vm3, "m", "fib") is not None
    assert C.we_VMGetFunctionTypeRegistered(vm3, "m", "nope") is None
    p = tmp_path / "fib.wasm"
    p.write_bytes(build_fib())
    vm4 = C.we_VMCreate(C.we_ConfigureCreate())
    assert C.we_ResultOK(C.we_VMRegisterModuleFromFile(vm4, "f", str(p)))
    res, out = C.we_VMExecuteRegistered(vm4, "f", "fib",
                                        [C.we_ValueGenI32(8)])
    assert C.we_ResultOK(res) and C.we_ValueGetI32(out[0]) == 21


def test_vm_async_run_family(tmp_path):
    conf, mod = _fib_mod()
    vm = C.we_VMCreate(conf)
    h = C.we_VMAsyncRunWasmFromBuffer(vm, build_fib(), "fib",
                                      [C.we_ValueGenI32(10)])
    C.we_AsyncWait(h)
    assert C.we_AsyncGetReturnsLength(h) == 1
    res, out = C.we_AsyncGet(h)
    assert C.we_ResultOK(res) and C.we_ValueGetI32(out[0]) == 55
    C.we_AsyncDelete(h)
    h = C.we_VMAsyncRunWasmFromASTModule(vm, mod, "fib",
                                         [C.we_ValueGenI32(9)])
    res, out = C.we_AsyncGet(h)
    assert C.we_ValueGetI32(out[0]) == 34
    p = tmp_path / "fib.wasm"
    p.write_bytes(build_fib())
    h = C.we_VMAsyncRunWasmFromFile(vm, str(p), "fib",
                                    [C.we_ValueGenI32(8)])
    res, out = C.we_AsyncGet(h)
    assert C.we_ValueGetI32(out[0]) == 21
    # registered async
    vm2 = C.we_VMCreate(C.we_ConfigureCreate())
    C.we_VMRegisterModuleFromBuffer(vm2, "m", build_fib())
    h = C.we_VMAsyncExecuteRegistered(vm2, "m", "fib",
                                      [C.we_ValueGenI32(7)])
    res, out = C.we_AsyncGet(h)
    assert C.we_ResultOK(res) and C.we_ValueGetI32(out[0]) == 13


def test_vm_get_import_module_context():
    conf = C.we_ConfigureCreate()
    C.we_ConfigureAddHostRegistration(conf, "wasi")
    vm = C.we_VMCreate(conf)
    assert C.we_VMGetImportModuleContext(vm, "wasi") is not None
    assert C.we_VMGetImportModuleContext(vm, "wasmedge_process") is None
    C.we_LoaderDelete(None)
    C.we_ValidatorDelete(None)
    C.we_ExecutorDelete(None)
    C.we_ImportObjectDelete(None)
    C.we_FunctionInstanceDelete(None)


def test_capi_parity_table_complete():
    """Every reference export has a we_* counterpart.  The export names
    come from the reference's header where a machine has it, and
    otherwise from column 1 of the committed CAPI_PARITY.md, which was
    generated from that header."""
    import os
    import re

    header = "/root/reference/include/api/wasmedge/wasmedge.h"
    if os.path.exists(header):
        names = re.findall(r"WasmEdge_[A-Za-z0-9_]+(?= *\()",
                           open(header).read())
    else:
        table = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "CAPI_PARITY.md")
        names = re.findall(r"^\| (WasmEdge_[A-Za-z0-9_]+) \|",
                           open(table).read(), re.M)
        assert len(names) == 236
    ref = set("we_" + m[len("WasmEdge_"):] for m in names)
    ref = {r for r in ref if not r.endswith("_t")}
    have = set(dir(C))
    missing = sorted(r for r in ref if r not in have)
    assert not missing, missing
