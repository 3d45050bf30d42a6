"""Static bytecode analyzer (wasmedge_tpu/analysis/, marker `analysis`).

Pins the r12 acceptance contract:

  - CFG construction over the lowered image: leaders from branch/
    brtable/call targets, edges including the full brtable entry table,
    loop/back-edge marking
  - SOUNDNESS: a bounded function's static cost bound dominates the
    engine's measured retired-instruction count; loops, recursion, and
    dynamic calls verdict "unbounded" instead of guessing
  - superinstruction n-gram census emitted as block metadata
  - hostcall inventory split tier-0-serviceable vs drain-required with
    the image build's exact fd-safety gates
  - static memory/stack footprint bounds
  - the report schema stays machine-readable (validate_report)
  - batchability() rejection taxonomy pinned reason-by-reason
  - LoweredModule.disasm round-trips every opcode in the lop_name table
  - gateway admission: policy-enabled POST /v1/modules rejects with the
    structured StaticPolicyViolation taxonomy; flag mode warns; the
    registry probe cache spares a rejected-then-retried module the
    second lowering
  - tools/lint_jit_purity.py runs clean over the jitted chunk bodies

Speed discipline: tier-1 fast — one tiny BatchEngine compile for the
soundness pin, gateway tests never invoke (registration builds engines
but first-launch jit never runs).
"""

import json
import tempfile
from http.client import HTTPConnection

import numpy as np
import pytest

from wasmedge_tpu.analysis import (
    AnalysisPolicy,
    AnalysisRejection,
    ModuleAnalysis,
    analyze_validated,
    build_func_cfg,
    validate_report,
)
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import ErrCode, rejection_info
from wasmedge_tpu.common.opcodes import NAME_TO_ID
from wasmedge_tpu.models import (
    build_counted_loop,
    build_fib,
    build_loop_sum,
)
from wasmedge_tpu.utils.builder import ModuleBuilder
from wasmedge_tpu.validator.image import (
    LOP_BR,
    NUM_LOPS,
    FuncMeta,
    LoweredModule,
    lop_name,
)

pytestmark = pytest.mark.analysis


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def load(data: bytes, conf=None):
    from wasmedge_tpu.loader import Loader
    from wasmedge_tpu.validator import Validator

    conf = conf or Configure()
    return Validator(conf).validate(Loader(conf).parse_module(data))


def analyzed(data: bytes, conf=None):
    mod = load(data, conf)
    return mod, analyze_validated(mod)


def instantiate(data: bytes, conf):
    from wasmedge_tpu.executor import Executor
    from wasmedge_tpu.runtime.store import StoreManager

    mod = load(data, conf)
    store = StoreManager()
    inst = Executor(conf).instantiate(store, mod)
    return inst, store


def tiny_conf():
    conf = Configure()
    conf.batch.steps_per_launch = 64
    conf.batch.value_stack_depth = 32
    conf.batch.call_stack_depth = 8
    return conf


def build_bounded() -> bytes:
    """if/else + a straight-line callee: finite, exactly boundable."""
    b = ModuleBuilder()
    leaf = b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.const", 3), "i32.mul"])
    b.add_function(["i32"], ["i32"], [], [
        ("local.get", 0), ("i32.const", 2), "i32.lt_s",
        ("if", "i32"),
        ("local.get", 0), ("call", leaf),
        "else",
        ("local.get", 0), ("i32.const", 5), "i32.add", ("call", leaf),
        "end",
    ], export="f")
    return b.build()


def build_unbounded() -> bytes:
    b = ModuleBuilder()
    b.add_function(["i32"], ["i32"], [], [
        ("block", None), ("loop", None), ("br", 0), "end", "end",
        ("local.get", 0)], export="spin")
    return b.build()


def synth_func(ops, nresults=1, nparams=1) -> LoweredModule:
    """A hand-built LoweredModule with one defined function — the unit
    vehicle for pinning batchability()/analyzer behavior per opcode
    without fighting the wasm validator."""
    lm = LoweredModule()
    for op, a, b_, c, imm in ops:
        lm.emit(op, a, b_, c, imm)
    lm.funcs.append(FuncMeta(
        type_idx=0, nparams=nparams, nresults=nresults,
        nlocals=nparams, entry_pc=0, end_pc=lm.code_len - 1,
        max_height=4))
    return lm


# ---------------------------------------------------------------------------
# CFG
# ---------------------------------------------------------------------------

class TestCFG:
    def test_straightline_single_block(self):
        _, a = analyzed(build_bounded())
        leaf = a.funcs[0]
        assert len(leaf.cfg.blocks) == 1
        blk = leaf.cfg.blocks[0]
        assert blk.succ == () and blk.kind == "return"
        assert not leaf.has_loop and leaf.bounded

    def test_if_else_edges_and_max_arm_cost(self):
        _, a = analyzed(build_bounded())
        f = a.func_by_idx(a.exports["f"])
        brz = f.cfg.blocks[0]
        assert brz.kind == "brz"
        # conditional: branch target + fallthrough, in that order
        assert len(brz.succ) == 2 and brz.succ[1] == brz.end + 1
        # bound takes the MAX arm (else arm is longer) + callee cost
        assert f.cost_bound == 13

    def test_loop_back_edge_detected(self):
        _, a = analyzed(build_loop_sum())
        f = a.funcs[0]
        assert f.has_loop and not f.recursive
        assert not f.bounded and f.cost_bound is None
        heads = [b for b in f.cfg.blocks if b.is_loop_head]
        assert heads, "loop head not marked"
        assert any(b.in_loop for b in f.cfg.blocks)
        # the back edge points AT a loop head
        starts = {b.start for b in heads}
        assert any(set(b.succ) & starts for b in f.cfg.blocks
                   if b.in_loop)

    def test_brtable_entry_table_edges(self):
        b = ModuleBuilder()
        b.add_function(["i32"], ["i32"], [], [
            ("block", None), ("block", None), ("block", None),
            ("local.get", 0),
            ("br_table", [0, 1], 2),
            "end", ("i32.const", 10), ("return",),
            "end", ("i32.const", 20), ("return",),
            "end", ("i32.const", 30),
        ], export="sel")
        mod, a = analyzed(b.build())
        f = a.funcs[0]
        tbl = [blk for blk in f.cfg.blocks if blk.kind == "br_table"]
        assert len(tbl) == 1
        # 2 targets + default, all distinct arms
        assert tbl[0].brtable_entries == 3
        assert len(tbl[0].succ) == 3
        cfg = build_func_cfg(mod.lowered, 0)
        starts = {blk.start for blk in cfg.blocks}
        assert set(tbl[0].succ) <= starts
        # data-dependent multiway = the dominant divergence driver
        assert f.divergence >= 3

    def test_recursion_unbounded(self):
        _, a = analyzed(build_fib())
        f = a.funcs[0]
        assert f.recursive and not f.has_loop
        assert f.cost_bound is None and f.value_stack_bound is None \
            and f.call_depth_bound is None
        assert not a.bounded


# ---------------------------------------------------------------------------
# cost soundness vs the real engine
# ---------------------------------------------------------------------------

class TestSoundness:
    def test_cost_bound_dominates_retired(self):
        from wasmedge_tpu.batch.engine import BatchEngine

        conf = tiny_conf()
        data = build_bounded()
        _, a = analyzed(data, conf)
        inst, store = instantiate(data, conf)
        eng = BatchEngine(inst, store=store, conf=conf, lanes=4)
        res = eng.run("f", [np.array([0, 1, 5, 9], np.int64)],
                      max_steps=10_000)
        assert res.completed.all()
        assert a.cost_bound is not None
        assert a.cost_bound >= int(res.retired.max())
        # the bound is TIGHT on this fixture (longest path is taken by
        # lanes >= 2): an overcounting regression shows up here
        assert a.cost_bound == int(res.retired.max())

    def test_device_image_carries_analysis(self):
        from wasmedge_tpu.batch.engine import BatchEngine

        conf = tiny_conf()
        inst, store = instantiate(build_fib(), conf)
        eng = BatchEngine(inst, store=store, conf=conf, lanes=2)
        a = eng.img.analysis
        assert isinstance(a, ModuleAnalysis)
        assert not a.bounded and a.funcs[0].recursive

    def test_stack_and_depth_bounds(self):
        _, a = analyzed(build_bounded())
        f = a.func_by_idx(a.exports["f"])
        leaf = a.funcs[0]
        # leaf frame: 1 local + max_height; caller adds its own frame
        assert leaf.call_depth_bound == 1 and f.call_depth_bound == 2
        assert leaf.value_stack_bound is not None
        assert f.value_stack_bound > leaf.value_stack_bound


# ---------------------------------------------------------------------------
# superinstruction census
# ---------------------------------------------------------------------------

class TestNgrams:
    def test_census_ranks_repeated_sequence(self):
        b = ModuleBuilder()
        body = []
        for _ in range(6):
            body += [("local.get", 0), ("i32.const", 7), "i32.xor",
                     ("local.set", 0)]
        body += [("local.get", 0)]
        b.add_function(["i32"], ["i32"], [], body, export="f")
        _, a = analyzed(b.build())
        assert a.superinstructions, "census empty"
        top = a.superinstructions[0]
        # the 4-gram body of the repeated unit wins on saved dispatches
        assert top["ops"] == ["local.get", "i32.const", "i32.xor",
                              "local.set"]
        assert top["count"] == 6 and top["n"] == 4
        assert top["saved_dispatches"] == 18
        f = a.funcs[0]
        # emitted as block metadata: the hosting block lists the winner
        assert any(0 in ng for ng in f.block_ngrams)

    def test_loop_occurrences_outweigh_straightline(self):
        # the same 2-gram once in a loop vs 3x straight-line: loop wins
        b = ModuleBuilder()
        b.add_function(["i32"], ["i32"], ["i32"], [
            # straight-line: 3 x (i32.const, i32.add)
            ("local.get", 0),
            ("i32.const", 1), "i32.add",
            ("i32.const", 2), "i32.add",
            ("i32.const", 3), "i32.add",
            ("local.set", 1),
            # loop: 1 x (i32.const, i32.sub) per iteration
            ("block", None), ("loop", None),
            ("local.get", 1), "i32.eqz", ("br_if", 1),
            ("local.get", 1), ("i32.const", 1), "i32.sub",
            ("local.set", 1),
            ("br", 0), "end", "end",
            ("local.get", 1),
        ], export="f")
        _, a = analyzed(b.build())
        by_ops = {tuple(c["ops"]): c for c in a.superinstructions}
        in_loop = by_ops[("i32.const", "i32.sub")]
        straight = by_ops[("i32.const", "i32.add")]
        assert in_loop["count"] == 1 and straight["count"] == 3
        assert in_loop["weight"] > straight["weight"]

    def test_ngrams_never_span_control(self):
        _, a = analyzed(build_fib())
        for c in a.superinstructions:
            for name in c["ops"]:
                assert name not in ("call", "return", "lop.br",
                                    "lop.brz", "lop.brnz", "br_table")


# ---------------------------------------------------------------------------
# hostcall inventory
# ---------------------------------------------------------------------------

class TestHostcalls:
    def test_echo_fd_write_is_tier0(self):
        from wasmedge_tpu.models import build_echo

        _, a = analyzed(build_echo())
        assert a.tier0_sites == 2 and a.drain_sites == 0
        sites = [s for f in a.funcs for s in f.hostcall_sites]
        assert all(s.kind == "fd_write" and s.tier0 for s in sites)
        assert all(s.import_name == "wasi_snapshot_preview1.fd_write"
                   for s in sites)

    def test_fd_unsafe_import_degrades_fd_write(self):
        # an fd_-family sibling import makes fd_write drain-required
        # (the kernel's "fd 1/2 is a plain sink" assumption is stale),
        # exactly like build_device_image's t0_fdwrite_safe gate
        b = ModuleBuilder()
        fdw = b.import_func("wasi_snapshot_preview1", "fd_write",
                            ["i32", "i32", "i32", "i32"], ["i32"])
        fdc = b.import_func("wasi_snapshot_preview1", "fd_close",
                            ["i32"], ["i32"])
        b.add_memory(1, 1)
        b.add_function(["i32"], ["i32"], [], [
            ("local.get", 0), ("call", fdc), "drop",
            ("i32.const", 1), ("i32.const", 64), ("i32.const", 1),
            ("i32.const", 32), ("call", fdw),
        ], export="f")
        mod, a = analyzed(b.build())
        by_kind = {s.kind: s for f in a.funcs
                   for s in f.hostcall_sites}
        assert not by_kind["fd_write"].tier0
        assert not by_kind["fd_close"].tier0
        assert a.drain_sites == 2 and a.tier0_sites == 0
        # and the image build agrees with the analyzer's gate
        from wasmedge_tpu.batch.image import build_device_image

        img = build_device_image(mod.lowered, mod=mod)
        assert not img.t0_fdwrite_safe

    def test_zero_min_memory_still_counts_as_memory(self):
        # (memory 0) with min=0 and no max is still a memory: tier-0
        # classification must match the image build's has_memory gate,
        # not infer memory-lessness from pages_init == 0
        b = ModuleBuilder()
        clk = b.import_func("wasi_snapshot_preview1", "clock_time_get",
                            ["i32", "i64", "i32"], ["i32"])
        b.add_memory(0)
        b.add_function([], ["i32"], [], [
            ("i32.const", 0), ("i64.const", 0), ("i32.const", 8),
            ("call", clk)], export="f")
        _, a = analyzed(b.build())
        sites = [s for f in a.funcs for s in f.hostcall_sites]
        assert len(sites) == 1 and sites[0].tier0

    def test_clock_without_memory_not_tier0(self):
        b = ModuleBuilder()
        clk = b.import_func("wasi_snapshot_preview1", "clock_time_get",
                            ["i32", "i64", "i32"], ["i32"])
        b.add_function([], ["i32"], [], [
            ("i32.const", 0), ("i64.const", 0), ("i32.const", 8),
            ("call", clk)], export="f")
        _, a = analyzed(b.build())
        sites = [s for f in a.funcs for s in f.hostcall_sites]
        assert len(sites) == 1 and not sites[0].tier0


# ---------------------------------------------------------------------------
# footprint bounds
# ---------------------------------------------------------------------------

class TestFootprint:
    def test_pages_bound_no_grow_is_initial(self):
        from wasmedge_tpu.models import build_echo

        _, a = analyzed(build_echo())
        assert a.mem_grow_sites == 0 and a.mem_pages_bound == 1

    def test_grow_with_declared_max(self):
        b = ModuleBuilder()
        b.add_memory(1, 4)
        b.add_function(["i32"], ["i32"], [], [
            ("local.get", 0), ("memory.grow", 0)], export="grow")
        _, a = analyzed(b.build())
        assert a.mem_grow_sites == 1
        assert a.mem_pages_init == 1 and a.mem_pages_bound == 4

    def test_grow_without_max_unbounded(self):
        b = ModuleBuilder()
        b.add_memory(1)
        b.add_function(["i32"], ["i32"], [], [
            ("local.get", 0), ("memory.grow", 0)], export="grow")
        _, a = analyzed(b.build())
        assert a.mem_pages_bound is None


# ---------------------------------------------------------------------------
# report schema
# ---------------------------------------------------------------------------

class TestReportSchema:
    @pytest.mark.parametrize("builder", [build_fib, build_loop_sum,
                                         build_bounded,
                                         build_unbounded])
    def test_fixture_reports_validate(self, builder):
        _, a = analyzed(builder())
        assert validate_report(a.to_dict()) == []

    def test_schema_catches_drift(self):
        _, a = analyzed(build_bounded())
        doc = a.to_dict()
        doc["summary"]["bounded"] = False  # disagrees with cost_bound
        assert validate_report(doc)
        doc2 = a.to_dict()
        del doc2["funcs"][0]["blocks"][0]["cost"]
        assert validate_report(doc2)
        doc3 = a.to_dict()
        doc3["funcs"][1]["blocks"][0]["succ"] = [999999]
        assert validate_report(doc3)
        assert validate_report({"schema": "nope"})

    def test_analyze_cli_end_to_end(self, tmp_path):
        from wasmedge_tpu import cli

        p = tmp_path / "fib.wasm"
        p.write_bytes(build_fib())
        out_path = tmp_path / "report.json"
        rc = cli.main(["analyze", str(p), "--disasm", "--out",
                       str(out_path)])
        assert rc == 0
        doc = json.loads(out_path.read_text())
        assert validate_report(doc) == []
        assert "lop.brz" in doc["disasm"]
        assert doc["exports"] == {"fib": 0}

    def test_annotated_disasm_marks_blocks(self):
        mod, a = analyzed(build_loop_sum())
        text = a.annotated_disasm(mod.lowered)
        assert ";; func 0" in text and "loop-head" in text
        assert "cost unbounded" in text


# ---------------------------------------------------------------------------
# disasm round-trip (satellite: every lowered opcode prints a name)
# ---------------------------------------------------------------------------

class TestDisasm:
    def test_every_opcode_roundtrips_through_disasm(self):
        for op in range(NUM_LOPS):
            name = lop_name(op)
            assert name and not name.isdigit(), f"opcode {op} unnamed"
            lm = LoweredModule()
            lm.emit(op)
            line = lm.disasm(0, 1)
            assert name in line, \
                f"opcode {op} ({name}) prints as raw int: {line!r}"

    def test_out_of_range_opcode_is_loud(self):
        lm = LoweredModule()
        lm.emit(NUM_LOPS + 7)
        with pytest.raises(ValueError, match="outside the lowered ISA"):
            lm.disasm(0, 1)
        # a NEGATIVE id used to index the opcode table from the end and
        # print a plausible but wrong name — now loud, never aliased
        with pytest.raises(ValueError, match="outside the lowered ISA"):
            lop_name(-5)


# ---------------------------------------------------------------------------
# batchability rejection taxonomy (satellite: one test per reason)
# ---------------------------------------------------------------------------

class TestBatchability:
    def test_happy_path(self):
        from wasmedge_tpu.batch.image import batchability

        mod = load(build_fib())
        assert batchability(mod.lowered) is None

    def test_unservable_import(self):
        from wasmedge_tpu.batch.image import batchability

        b = ModuleBuilder()
        b.import_func("env", "mystery", ["i32"], ["i32"])
        b.add_function(["i32"], ["i32"], [],
                       [("local.get", 0), ("call", 0)], export="f")
        mod = load(b.build())
        reason = batchability(mod.lowered, host_imports=None)
        assert reason == "unservable imported function env.mystery"
        # ... and servable when the host backs it
        assert batchability(mod.lowered, host_imports={0}) is None

    def test_multi_memory(self):
        from wasmedge_tpu.batch.engine import BatchEngine
        from wasmedge_tpu.batch.image import batchability
        from wasmedge_tpu.common.configure import Proposal

        b = ModuleBuilder()
        b.add_memory(1, 1)
        b.add_memory(1, 1)
        b.add_function(["i32"], ["i32"], [], [("local.get", 0)],
                       export="f")
        conf = Configure()
        conf.add_proposal(Proposal.MultiMemories)
        inst, store = instantiate(b.build(), conf)
        assert len(inst.memories) == 2
        assert batchability(inst.lowered, n_memories=2) \
            == "multiple memories"
        with pytest.raises(ValueError, match="multiple memories"):
            BatchEngine(inst, store=store, conf=conf, lanes=1)

    def test_multi_value_results(self):
        from wasmedge_tpu.batch.image import batchability

        lm = synth_func([(NAME_TO_ID["local.get"], 0, 0, 0, 0),
                         (NAME_TO_ID["local.get"], 0, 0, 0, 0),
                         (NAME_TO_ID["return"], 0, 2, 0, 0)],
                        nresults=2)
        assert batchability(lm) == "multi-value results"

    def test_multi_value_branch_arity(self):
        from wasmedge_tpu.batch.image import batchability

        lm = synth_func([(LOP_BR, 1, 2, 0, 0),
                         (NAME_TO_ID["return"], 0, 1, 0, 0)])
        assert batchability(lm) == "multi-value branch arity"

    def test_unsupported_op(self):
        from wasmedge_tpu.batch.image import batchability

        lm = synth_func([(NAME_TO_ID["v128.load8x8_s"], 0, 0, 0, 0),
                         (NAME_TO_ID["return"], 0, 1, 0, 0)])
        assert batchability(lm) == "unsupported op v128.load8x8_s"

    def test_table_not_zero(self):
        from wasmedge_tpu.batch.image import batchability

        lm = synth_func([(NAME_TO_ID["table.get"], 1, 0, 0, 0),
                         (NAME_TO_ID["return"], 0, 1, 0, 0)])
        assert batchability(lm) == "table.get on table != 0"

    def test_v128_entry_signature(self):
        from wasmedge_tpu.batch.engine import check_batch_entry

        b = ModuleBuilder()
        b.add_function(["v128"], ["i32"], [], [
            ("local.get", 0), "i8x16.all_true"], export="f")
        inst, _ = instantiate(b.build(), Configure())
        with pytest.raises(ValueError, match="v128"):
            check_batch_entry(inst, "f")


# ---------------------------------------------------------------------------
# admission policy
# ---------------------------------------------------------------------------

class TestPolicy:
    def test_evaluate_limits(self):
        _, unb = analyzed(build_unbounded())
        _, bnd = analyzed(build_bounded())
        pol = AnalysisPolicy(max_static_cost=1000)
        assert [v["limit"] for v in pol.evaluate(unb)] \
            == ["max_static_cost"]
        assert pol.evaluate(bnd) == []
        assert AnalysisPolicy(max_static_cost=5).evaluate(bnd)
        assert AnalysisPolicy(require_bounded=True).evaluate(unb)
        assert AnalysisPolicy(max_call_depth=1).evaluate(bnd)
        assert AnalysisPolicy(max_call_depth=2).evaluate(bnd) == []
        # missing analysis never passes an enforcing policy
        assert AnalysisPolicy(require_bounded=True).evaluate(None)
        assert AnalysisPolicy().evaluate(None) == []

    def test_memory_and_hostcall_limits(self):
        from wasmedge_tpu.models import build_echo

        _, echo = analyzed(build_echo())
        assert AnalysisPolicy(max_memory_pages=1).evaluate(echo) == []
        assert AnalysisPolicy(max_memory_pages=0).evaluate(echo)
        # echo's fd_write is tier-0-serviceable: tier0-only admits it
        assert AnalysisPolicy(
            tier0_only_hostcalls=True).evaluate(echo) == []

    def test_rejection_info_carries_violations(self):
        exc = AnalysisRejection("m", [{"limit": "max_static_cost",
                                       "allowed": 5,
                                       "actual": "unbounded",
                                       "message": "x"}])
        info = rejection_info(exc)
        assert info["code"] == int(ErrCode.StaticPolicyViolation)
        assert info["name"] == "StaticPolicyViolation"
        assert not info["retryable"]
        assert info["violations"][0]["limit"] == "max_static_cost"

    def test_from_dict_rejects_unknown_keys(self):
        with pytest.raises(ValueError, match="unknown analysis"):
            AnalysisPolicy.from_dict({"max_cost": 5})

    def test_lint_jit_purity_clean(self):
        import os

        from tools.lint_jit_purity import run_lint

        root = os.path.join(os.path.dirname(__file__), "..")
        assert run_lint(root) == []


# ---------------------------------------------------------------------------
# gateway admission over real sockets
# ---------------------------------------------------------------------------

def rpc(gw, method, path, body=None, headers=None, timeout=120.0):
    c = HTTPConnection(gw.host, gw.port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if isinstance(body, dict) \
            else body
        c.request(method, path, body=data, headers=headers or {})
        r = c.getresponse()
        raw = r.read()
    finally:
        c.close()
    try:
        doc = json.loads(raw)
    except Exception:
        doc = raw.decode(errors="replace")
    return r.status, doc


@pytest.fixture(scope="module")
def _compile_cache():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    d = tempfile.mkdtemp(prefix="analysis-jit-cache-")
    jax.config.update("jax_compilation_cache_dir", d)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


class TestGatewayAdmission:
    @pytest.fixture()
    def gw(self, _compile_cache):
        from wasmedge_tpu.gateway import (
            Gateway, GatewayService, GatewayTenants)

        conf = Configure()
        conf.batch.steps_per_launch = 128
        tenants = GatewayTenants.from_dict({
            "analysis": {"max_static_cost": 1_000_000},
            "tenants": {
                "strict": {},
                "audit": {"analysis": {"require_bounded": True,
                                       "enforce": False}},
                "free": {"analysis": {"enforce": True}},
            },
        })
        svc = GatewayService(conf=conf, lanes=2, tenants=tenants)
        gw = Gateway(svc, port=0).start()
        yield gw
        gw.shutdown(drain=True, timeout_s=60.0)

    def test_policy_rejects_unbounded_on_the_wire(self, gw):
        st, doc = rpc(gw, "POST", "/v1/modules?name=spin&tenant=strict",
                      body=build_unbounded(),
                      headers={"Content-Type": "application/wasm"})
        assert st == 400
        err = doc["err"]
        assert err["name"] == "StaticPolicyViolation"
        assert err["code"] == int(ErrCode.StaticPolicyViolation)
        assert err["retryable"] is False
        assert err["violations"][0]["limit"] == "max_static_cost"
        assert err["violations"][0]["actual"] == "unbounded"
        # nothing registered, no generation swapped
        assert gw.service.registry.names == []
        st, doc = rpc(gw, "GET", "/v1/status")
        assert doc["generation"] == 0
        assert doc["gateway"]["policy_rejected"] == 1
        assert doc["analysis"]["policy_rejected"] == 1

    @pytest.mark.parametrize("guest,cost_bound,trip_bounded_loops", [
        (build_bounded, 13, 0),
        # "unbounded" before the abstract interpreter, which the strict
        # tenant's max_static_cost rejects: admitted on its trip bound
        (build_counted_loop, 770, 1),
    ])
    def test_bounded_admits_with_summary(self, gw, guest, cost_bound,
                                         trip_bounded_loops):
        st, doc = rpc(gw, "POST", "/v1/modules?name=ok&tenant=strict",
                      body=guest(),
                      headers={"Content-Type": "application/wasm"})
        assert st == 201 and doc["ok"]
        assert doc["analysis"]["bounded"] is True
        assert doc["analysis"]["cost_bound"] == cost_bound
        assert doc["analysis"].get("trip_bounded_loops", 0) == \
            trip_bounded_loops
        assert "analysis_warnings" not in doc

    def test_flag_mode_registers_with_warnings(self, gw):
        st, doc = rpc(gw, "POST", "/v1/modules?name=spin&tenant=audit",
                      body=build_unbounded(),
                      headers={"Content-Type": "application/wasm"})
        assert st == 201 and doc["ok"]
        assert doc["analysis"]["bounded"] is False
        warns = doc["analysis_warnings"]
        assert warns[0]["limit"] == "require_bounded"
        assert "spin" in gw.service.registry.names

    def test_boot_registration_skips_default_policy(self, gw):
        # operator-supplied boot modules (tenant=None: CLI --module,
        # VM.gateway()) are trusted — a strict file-level default for
        # HTTP registrants must not abort gateway startup on them
        info = gw.service.register_module(
            "bootspin", wasm_bytes=build_unbounded(), source="boot")
        assert info["analysis"]["bounded"] is False
        assert "analysis_warnings" not in info
        assert "bootspin" in gw.service.registry.names

    def test_tenant_policy_overrides_default(self, gw):
        # "free" carries its OWN empty enforcing policy: no limits set,
        # so the unbounded module admits — per-tenant wins over default
        st, doc = rpc(gw, "POST", "/v1/modules?name=spin2&tenant=free",
                      body=build_unbounded(),
                      headers={"Content-Type": "application/wasm"})
        assert st == 201 and doc["ok"]

    def test_probe_cache_spares_second_lowering(self, gw):
        svc = gw.service
        data = build_unbounded()
        base = svc.registry.lowered_count
        st, _ = rpc(gw, "POST", "/v1/modules?name=a&tenant=strict",
                    body=data,
                    headers={"Content-Type": "application/wasm"})
        assert st == 400
        assert svc.registry.lowered_count == base + 1
        # rejected-then-fixed: same bytes under a permissive tenant
        # adopt the stashed probe engine — no second lowering
        st, doc = rpc(gw, "POST", "/v1/modules?name=b&tenant=free",
                      body=data,
                      headers={"Content-Type": "application/wasm"})
        assert st == 201 and doc["module"] == "b"
        assert svc.registry.lowered_count == base + 1
        # adoption retargets the guest-visible argv[0]: a cache hit is
        # not observably different from a fresh registration
        assert svc.registry.get("b").wasi.env.args[0] == "b"

    def test_metrics_export_analysis_counters(self, gw):
        from wasmedge_tpu.obs.metrics import parse_prometheus

        rpc(gw, "POST", "/v1/modules?name=spin&tenant=strict",
            body=build_unbounded(),
            headers={"Content-Type": "application/wasm"})
        rpc(gw, "POST", "/v1/modules?name=ok&tenant=strict",
            body=build_bounded(),
            headers={"Content-Type": "application/wasm"})
        st, text = rpc(gw, "GET", "/metrics")
        assert st == 200
        parsed = parse_prometheus(text)
        assert parsed[("wasmedge_analysis_policy_rejections_total",
                       frozenset())] == 1.0
        assert parsed[("wasmedge_analysis_modules_total",
                       frozenset({("verdict", "bounded")}))] == 1.0
        assert parsed[("wasmedge_analysis_modules_total",
                       frozenset({("verdict", "unbounded")}))] == 1.0
