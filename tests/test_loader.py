"""Loader unit tests — byte-level decode with handcrafted binaries, the
reference's test/loader pattern (sectionTest.cpp, filemgrTest.cpp,
instructionTest.cpp)."""

import pytest

from wasmedge_tpu.common.errors import ErrCode, LoadError
from wasmedge_tpu.common.opcodes import Op, name_of
from wasmedge_tpu.common.types import ValType
from wasmedge_tpu.loader import Loader
from wasmedge_tpu.loader.filemgr import FileMgr
from wasmedge_tpu.utils.builder import ModuleBuilder, uleb, sleb


class TestFileMgr:
    def test_uleb_basic(self):
        assert FileMgr(b"\x00").read_u32() == 0
        assert FileMgr(b"\x7f").read_u32() == 127
        assert FileMgr(b"\x80\x01").read_u32() == 128
        assert FileMgr(b"\xff\xff\xff\xff\x0f").read_u32() == 0xFFFFFFFF

    def test_uleb_too_long(self):
        with pytest.raises(LoadError) as e:
            FileMgr(b"\xff\xff\xff\xff\xff\x0f").read_u32()
        assert e.value.code == ErrCode.IntegerTooLong

    def test_uleb_unused_bits(self):
        # 5th byte may only contribute 4 bits for u32
        with pytest.raises(LoadError) as e:
            FileMgr(b"\xff\xff\xff\xff\x1f").read_u32()
        assert e.value.code == ErrCode.IntegerTooLarge

    def test_sleb_basic(self):
        assert FileMgr(b"\x00").read_s32() == 0
        assert FileMgr(b"\x7f").read_s32() == -1
        assert FileMgr(b"\x40").read_s32() == -64
        assert FileMgr(b"\xc0\x00").read_s32() == 64
        assert FileMgr(sleb(-(2**31))).read_s32() == -(2**31)
        assert FileMgr(sleb(2**31 - 1)).read_s32() == 2**31 - 1

    def test_sleb_sign_bits(self):
        # -2^31 encoded, then corrupt final byte sign-extension
        with pytest.raises(LoadError):
            FileMgr(b"\xff\xff\xff\xff\x4f").read_s32()

    def test_sleb64_roundtrip(self):
        for v in (0, 1, -1, 2**62, -(2**63), 2**63 - 1, 123456789012345):
            assert FileMgr(sleb(v)).read_s64() == v

    def test_truncated(self):
        with pytest.raises(LoadError) as e:
            FileMgr(b"\x80").read_u32()
        assert e.value.code == ErrCode.UnexpectedEnd

    def test_name_utf8(self):
        fm = FileMgr(uleb(2) + b"\xc3\xa9")
        assert fm.read_name() == "é"
        with pytest.raises(LoadError) as e:
            FileMgr(uleb(1) + b"\xff").read_name()
        assert e.value.code == ErrCode.MalformedUTF8


class TestHeaders:
    def test_bad_magic(self):
        with pytest.raises(LoadError) as e:
            Loader().parse_module(b"\x00msa\x01\x00\x00\x00")
        assert e.value.code == ErrCode.MalformedMagic

    def test_bad_version(self):
        with pytest.raises(LoadError) as e:
            Loader().parse_module(b"\x00asm\x02\x00\x00\x00")
        assert e.value.code == ErrCode.MalformedVersion

    def test_empty_module(self):
        mod = Loader().parse_module(b"\x00asm\x01\x00\x00\x00")
        assert mod.types == [] and mod.functions == []

    def test_section_out_of_order(self):
        # function section (3) before type section (1)
        raw = b"\x00asm\x01\x00\x00\x00" + b"\x03\x02\x01\x00" + b"\x01\x04\x01\x60\x00\x00"
        with pytest.raises(LoadError) as e:
            Loader().parse_module(raw)
        assert e.value.code == ErrCode.JunkSection

    def test_section_size_mismatch(self):
        # type section claims 5 bytes but content is 4
        raw = b"\x00asm\x01\x00\x00\x00" + b"\x01\x05\x01\x60\x00\x00"
        with pytest.raises(LoadError):
            Loader().parse_module(raw)

    def test_func_code_mismatch(self):
        b = ModuleBuilder()
        b.add_function([], [], [], [])
        raw = bytearray(b.build())
        # strip the code section (last section) entirely
        # find code section: id 10
        i = 8
        while i < len(raw):
            sid = raw[i]
            size = raw[i + 1]
            if sid == 10:
                del raw[i:]
                break
            i += 2 + size
        with pytest.raises(LoadError) as e:
            Loader().parse_module(bytes(raw))
        assert e.value.code == ErrCode.IncompatibleFuncCode


class TestSections:
    def test_type_section(self):
        b = ModuleBuilder()
        b.add_type(["i32", "i64"], ["f32"])
        mod = Loader().parse_module(b.build())
        assert mod.types[0].params == (ValType.I32, ValType.I64)
        assert mod.types[0].results == (ValType.F32,)

    def test_import_section(self):
        b = ModuleBuilder()
        b.import_func("env", "f", ["i32"], [])
        b.import_memory("env", "m", 1, 4)
        b.import_global("env", "g", "i64", mutable=True)
        b.import_table("env", "t", "funcref", 2, 10)
        mod = Loader().parse_module(b.build())
        assert len(mod.imports) == 4
        assert mod.imports[0].kind == 0
        assert mod.imports[1].memory_type.limit.max == 4
        assert mod.imports[2].global_type.mutable
        assert mod.imports[3].table_type.limit.min == 2

    def test_memory_global_export_start(self):
        b = ModuleBuilder()
        b.add_memory(2, 8, export="mem")
        b.add_global("i32", True, [("i32.const", 41)], export="g")
        f = b.add_function([], [], [], [])
        b.set_start(f)
        mod = Loader().parse_module(b.build())
        assert mod.memories[0].limit.min == 2
        assert mod.globals[0].type.mutable
        assert mod.start == f
        assert {e.name for e in mod.exports} == {"mem", "g"}

    def test_elem_and_data(self):
        b = ModuleBuilder()
        b.add_table("funcref", 4)
        f = b.add_function([], [], [], [])
        b.add_active_elem(0, [("i32.const", 1)], [f])
        b.add_memory(1)
        b.add_active_data(0, [("i32.const", 0)], b"hello")
        b.data_count = 1
        mod = Loader().parse_module(b.build())
        assert mod.elements[0].mode == 0
        assert len(mod.elements[0].init_exprs) == 1
        assert mod.datas[0].data == b"hello"

    def test_custom_section_anywhere(self):
        raw = b"\x00asm\x01\x00\x00\x00" + b"\x00\x05\x04name" + b"\x01\x04\x01\x60\x00\x00"
        mod = Loader().parse_module(raw)
        assert mod.customs[0].name == "name"


class TestInstructions:
    def test_jump_precompute(self):
        b = ModuleBuilder()
        b.add_function([], [], [], [
            ("block", None), ("block", None), ("br", 1), "end", "end",
        ])
        mod = Loader().parse_module(b.build())
        body = mod.codes[0].body
        names = [name_of(i.op) for i in body]
        assert names == ["block", "block", "br", "end", "end", "end"]
        assert body[0].jump_end == 4
        assert body[1].jump_end == 2

    def test_if_else_jumps(self):
        b = ModuleBuilder()
        b.add_function(["i32"], ["i32"], [], [
            ("local.get", 0), ("if", "i32"), ("i32.const", 1),
            "else", ("i32.const", 2), "end",
        ])
        mod = Loader().parse_module(b.build())
        body = mod.codes[0].body
        if_i = 1
        assert name_of(body[if_i].op) == "if"
        assert body[if_i].jump_else == 2
        assert body[if_i].jump_end == 4

    def test_illegal_opcode(self):
        # handcrafted: one void function whose body is [0x27 (illegal), end]
        raw = (b"\x00asm\x01\x00\x00\x00"
               b"\x01\x04\x01\x60\x00\x00"
               b"\x03\x02\x01\x00"
               b"\x0a\x05\x01\x03\x00\x27\x0b")
        with pytest.raises(LoadError) as e:
            Loader().parse_module(raw)
        assert e.value.code == ErrCode.IllegalOpCode

    def test_proposal_gating(self):
        from wasmedge_tpu.common.configure import Configure, Proposal
        b = ModuleBuilder()
        b.add_function(["i32"], ["i32"], [], [("local.get", 0), "i32.extend8_s"])
        conf = Configure()
        conf.remove_proposal(Proposal.SignExtensionOperators)
        with pytest.raises(LoadError) as e:
            Loader(conf).parse_module(b.build())
        assert e.value.code == ErrCode.IllegalOpCode
        # default conf allows it
        Loader().parse_module(b.build())

    def test_br_table_decode(self):
        b = ModuleBuilder()
        b.add_function(["i32"], [], [], [
            ("block", None), ("block", None),
            ("local.get", 0), ("br_table", [0, 1], 1),
            "end", "end",
        ])
        mod = Loader().parse_module(b.build())
        bt = [i for i in mod.codes[0].body if name_of(i.op) == "br_table"][0]
        assert bt.targets == [0, 1] and bt.target_idx == 1

    def test_const_immediates(self):
        b = ModuleBuilder()
        b.add_function([], ["f64"], [], [("f64.const", 3.14159)])
        mod = Loader().parse_module(b.build())
        import struct
        bits = mod.codes[0].body[0].imm
        assert struct.unpack("<d", struct.pack("<Q", bits))[0] == pytest.approx(3.14159)


def test_aot_fused_planes_roundtrip():
    """tpu.aot artifacts carry the Pallas fused encoding; it must
    round-trip bit-exactly, verify by regeneration, and a tampered
    section must be refused (verify_fused False)."""
    import numpy as np

    from wasmedge_tpu.aot import (
        compile_module, deserialize_image, extract_precompiled,
        fused_planes_for, verify_fused)
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.models import build_fib
    from wasmedge_tpu.validator import Validator

    conf = Configure()
    twasm = compile_module(build_fib(), conf)
    mod = Loader(conf).parse_module(twasm)
    payload = extract_precompiled(
        mod.source_bytes, [(c.name, c.data, c.start) for c in mod.customs])
    assert payload is not None
    img = deserialize_image(payload)
    assert getattr(img, "fused", None) is not None
    src = Validator(conf).validate(Loader(conf).parse_module(build_fib()))
    regen = fused_planes_for(src.lowered, src)
    for k in regen:
        assert np.array_equal(img.fused[k], regen[k]), k
    assert verify_fused(img, mod)
    # tamper: redirect a fused branch -> must be refused
    img.fused["a"] = img.fused["a"].copy()
    img.fused["a"][0] ^= 1
    assert not verify_fused(img, mod)


def test_aot_fused_planes_consumed_by_engine():
    """Loading a tpu.aot artifact end-to-end: the Pallas engine must see
    the fused section and verify it against regeneration — including for
    call_indirect modules, whose table window size comes from the
    DECLARED table (no table mutation in the batch subset)."""
    import numpy as np

    from wasmedge_tpu.aot import compile_module
    from wasmedge_tpu.batch.pallas_engine import PallasUniformEngine
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.utils.builder import ModuleBuilder
    from wasmedge_tpu.validator import Validator

    b = ModuleBuilder()
    f_dbl = b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.const", 2), "i32.mul"])
    b.add_table("funcref", 3)
    b.add_active_elem(0, [("i32.const", 1)], [f_dbl])
    ti = b.add_type(["i32"], ["i32"])
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.const", 1), ("call_indirect", ti, 0),
    ], export="f")
    conf = Configure()
    conf.batch.steps_per_launch = 10_000
    twasm = compile_module(b.build(), conf)
    mod = Validator(conf).validate(Loader(conf).parse_module(twasm))
    assert getattr(mod.lowered, "fused", None) is not None
    store = StoreManager()
    inst = Executor(conf).instantiate(store, mod)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=8,
                              interpret=True)
    res = eng.run("f", [np.arange(8, dtype=np.int64)], max_steps=10_000)
    assert (res.trap == -1).all()
    assert (np.asarray(res.results[0]) == np.arange(8) * 2).all()
    assert eng.aot_fused_verified is True


def test_aot_stale_fused_plane_is_regenerated_not_trusted():
    """An artifact written before superblocks (PR 29) carries the hid
    plane of the plain block fuser.  g1 and g2 start with the same
    plain block (`local.get; brz; const; br`), so that fuser gave both
    heads shape id 0; their superblocks differ (the `br` runs on into
    different code), so the ids are 0 and 2 now and every later id
    shifts.  The stale plane must fail verification and never run: the
    engine regenerates."""
    import numpy as np

    from wasmedge_tpu.aot import (
        compile_module, deserialize_image, extract_precompiled,
        verify_fused)
    from wasmedge_tpu.batch.pallas_engine import (
        H_BLOCK_BASE, PallasUniformEngine)
    from wasmedge_tpu.common.configure import Configure
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.runtime.store import StoreManager
    from wasmedge_tpu.utils.builder import ModuleBuilder
    from wasmedge_tpu.validator import Validator

    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("if", "i32"), ("i32.const", 1), "else",
        ("i32.const", 2), "end"], export="g1")
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("if", "i32"), ("i32.const", 1), "else",
        ("local.get", 0), "end", ("i32.const", 5), "i32.add"],
        export="g2")
    B = H_BLOCK_BASE
    # what fuse_blocks wrote at commit e867578 (PR 28) for this module
    parent_hid = np.asarray([B + 0, 10, 1, 9, B + 1, 13,
                             B + 0, 10, 1, 9, 2, B + 2, 24, 13], np.int32)
    conf = Configure()
    conf.batch.steps_per_launch = 10_000
    twasm = compile_module(b.build(), conf)
    mod = Loader(conf).parse_module(twasm)
    payload = extract_precompiled(
        mod.source_bytes, [(c.name, c.data, c.start) for c in mod.customs])
    img = deserialize_image(payload)
    assert verify_fused(img, mod)
    fresh = img.fused["hid"].copy()
    assert fresh.tolist() == [B + 0, 10, 1, 9, B + 1, 13,
                              B + 2, 10, 1, 9, 2, B + 3, 24, 13]
    img.fused["hid"] = parent_hid
    assert not verify_fused(img, mod)

    vmod = Validator(conf).validate(Loader(conf).parse_module(twasm))
    vmod.lowered.fused["hid"] = parent_hid
    store = StoreManager()
    inst = Executor(conf).instantiate(store, vmod)
    eng = PallasUniformEngine(inst, store=store, conf=conf, lanes=8,
                              interpret=True)
    xs = np.asarray([0, 1, 0, 0, 0, 0, 0, 0], np.int64)
    res = eng.run("g2", [xs * 0], max_steps=10_000)
    assert (res.trap == -1).all()
    assert (np.asarray(res.results[0]) == 5).all()
    assert eng.aot_fused_verified is False
    inner = next(iter(eng.simt._sched_cache.values()))
    assert inner.aot_fused_verified is False
    assert np.array_equal(inner._np_fused["hid"], fresh)
    res = eng.run("g2", [xs + 1], max_steps=10_000)
    assert np.asarray(res.results[0]).tolist() == [6] * 8
