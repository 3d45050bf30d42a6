"""Abstract-interpretation value-range analysis (analysis/absint.py).

Pins the r19 tentpole's contracts:

  - counted loops flip the seed's blanket "unbounded" verdict to a
    finite SOUND cost bound, EXACT on the canonical latch-tested
    fixture (cost_bound == the engine's measured retired max);
  - the CFG edge cases the interpreter leans on: br_table entry tables
    as loop back-edges, nested-loop widening termination, and a
    self-recursive function staying honestly "unbounded";
  - memory-effect facts: licensed (proven in-bounds + aligned) sites
    vs refused misaligned / OOB-adjacent ones, and the proven
    page-touch bound with its hv budget seeding;
  - the report schema: absint keys validate, PRE-absint reports still
    validate (back-compat), and the reconciliation rules fire.

Fast by construction (pure-python analysis, tiny engine rigs): tier-1.
"""

import numpy as np
import pytest

from wasmedge_tpu.analysis import analyze_validated, validate_report
from wasmedge_tpu.analysis.policy import AnalysisPolicy
from wasmedge_tpu.batch.engine import BatchEngine
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.models import (
    build_counted_loop,
    build_fib,
    build_loop_sum,
    build_memfuse_workload,
)
from wasmedge_tpu.utils.builder import ModuleBuilder
from tests.helpers import instantiate, load_validate

pytestmark = pytest.mark.analysis


def analyzed(data: bytes):
    mod = load_validate(data)
    return mod, analyze_validated(mod)


def engine_of(data: bytes, lanes=4, **batch):
    conf = Configure()
    conf.batch.steps_per_launch = batch.pop("steps_per_launch", 256)
    conf.batch.value_stack_depth = 64
    conf.batch.call_stack_depth = 16
    for k, v in batch.items():
        setattr(conf.batch, k, v)
    ex, store, inst = instantiate(data, conf)
    return BatchEngine(inst, store=store, conf=conf, lanes=lanes)


class TestTripBounds:
    def test_counted_loop_exact_bound(self):
        """The admission-precision flagship: verdict unbounded ->
        finite, and EXACT on the canonical fixture."""
        n = 64
        _, a = analyzed(build_counted_loop(n))
        f = a.funcs[0]
        assert f.has_loop and a.bounded
        assert f.loops == [{"head": 0, "trip_bound": n}]
        eng = engine_of(build_counted_loop(n))
        res = eng.run("count", [np.zeros(4, np.int64)],
                      max_steps=50_000)
        assert res.completed.all()
        assert a.cost_bound == int(res.retired.max())  # exact, pinned

    def test_head_tested_loop_sound_bound(self):
        """Exit-at-head / unconditional-back-edge shape (the
        build_loop_sum lowering) with a CONSTANT limit: sound finite
        bound >= measured (the +1-head-execution slack is allowed,
        undercounting is not)."""
        n = 37
        b = ModuleBuilder()
        b.add_function(["i32"], ["i32"], ["i32", "i32"], [
            ("block", None), ("loop", None),
            ("local.get", 1), ("i32.const", n), "i32.ge_u",
            ("br_if", 1),
            ("local.get", 2), ("local.get", 1), "i32.add",
            ("local.set", 2),
            ("local.get", 1), ("i32.const", 1), "i32.add",
            ("local.set", 1),
            ("br", 0),
            "end", "end",
            ("local.get", 2)], export="f")
        data = b.build()
        _, a = analyzed(data)
        assert a.bounded and a.cost_bound is not None
        res = engine_of(data).run("f", [np.zeros(4, np.int64)],
                                  max_steps=50_000)
        assert res.completed.all()
        assert a.cost_bound >= int(res.retired.max())
        assert int(np.asarray(res.results[0])[0]) == n * (n - 1) // 2

    def test_countdown_ne_zero_shape(self):
        """Decrement-to-zero with a raw brnz value test (the tee/br_if
        idiom) is a counted loop too."""
        n = 9
        b = ModuleBuilder()
        b.add_function([], ["i32"], ["i32", "i32"], [
            ("i32.const", n), ("local.set", 0),
            ("block", None), ("loop", None),
            ("local.get", 1), ("i32.const", 3), "i32.add",
            ("local.set", 1),
            ("local.get", 0), ("i32.const", 1), "i32.sub",
            ("local.tee", 0), ("br_if", 0),
            "end", "end",
            ("local.get", 1)], export="f")
        data = b.build()
        _, a = analyzed(data)
        assert a.bounded
        res = engine_of(data).run("f", [], max_steps=50_000)
        assert res.completed.all()
        assert (np.asarray(res.results[0]) == 3 * n).all()
        assert a.cost_bound >= int(res.retired.max())

    def test_param_limited_loop_stays_unbounded(self):
        """No static limit -> the seed's honest verdict survives."""
        _, a = analyzed(build_loop_sum())
        assert not a.bounded
        assert a.funcs[0].loops[0]["trip_bound"] is None

    def test_nested_counted_loops_bound_and_terminate(self):
        """Nested widening terminates and the loop-nest composition
        multiplies trips (outer x inner), staying sound."""
        outer, inner = 7, 11
        b = ModuleBuilder()
        b.add_function([], ["i32"], ["i32", "i32", "i32"], [
            ("block", None), ("loop", None),            # outer: j
            ("i32.const", 0), ("local.set", 1),
            ("block", None), ("loop", None),            # inner: i
            ("local.get", 2), ("i32.const", 1), "i32.add",
            ("local.set", 2),
            ("local.get", 1), ("i32.const", 1), "i32.add",
            ("local.set", 1),
            ("local.get", 1), ("i32.const", inner), "i32.lt_u",
            ("br_if", 0),
            "end", "end",
            ("local.get", 0), ("i32.const", 1), "i32.add",
            ("local.set", 0),
            ("local.get", 0), ("i32.const", outer), "i32.lt_u",
            ("br_if", 0),
            "end", "end",
            ("local.get", 2)], export="f")
        data = b.build()
        _, a = analyzed(data)
        f = a.funcs[0]
        assert a.bounded and a.cost_bound is not None
        trips = sorted(l["trip_bound"] for l in f.loops)
        assert trips == [outer, inner]
        res = engine_of(data).run("f", [], max_steps=100_000)
        assert res.completed.all()
        assert (np.asarray(res.results[0]) == outer * inner).all()
        assert a.cost_bound >= int(res.retired.max())

    def test_brtable_back_edge_stays_honest(self):
        """A loop whose back edge rides a br_table entry table: the
        interpreter must terminate and keep the honest unbounded
        verdict (no conditional-compare trip pattern exists)."""
        b = ModuleBuilder()
        b.add_function(["i32"], ["i32"], ["i32"], [
            ("block", None), ("loop", None),
            ("local.get", 1), ("i32.const", 1), "i32.add",
            ("local.set", 1),
            ("local.get", 1), ("i32.const", 3), "i32.rem_u",
            ("br_table", [0, 0], 1),     # both entries: back edges
            "end", "end",
            ("local.get", 1)], export="f")
        mod, a = analyzed(b.build())
        f = a.funcs[0]
        assert f.has_loop
        assert not a.bounded
        assert all(l["trip_bound"] is None for l in f.loops)
        # the brtable rows really are the CFG back edges
        heads = [blk for blk in f.cfg.blocks if blk.is_loop_head]
        assert heads and any(
            heads[0].start in blk.succ for blk in f.cfg.blocks
            if blk.kind == "br_table")

    def test_self_recursion_stays_unbounded(self):
        _, a = analyzed(build_fib())
        assert not a.bounded
        assert a.funcs[0].recursive
        # absint must not fabricate loop facts for recursion
        assert a.summary()["trip_bounded_loops"] == 0


class TestMemoryFacts:
    def test_licensed_sites_proven(self):
        _, a = analyzed(build_memfuse_workload(256, passes=2))
        facts = a.funcs[0].mem_facts
        scalar = [m for m in facts if m["kind"] in ("load", "store")]
        assert len(scalar) == 2 and all(m["licensed"] for m in scalar)
        for m in scalar:
            assert m["lo"] == 0 and m["hi"] == 255 * 4
            assert m["align"] >= 4 and m["in_bounds"] and m["aligned"]
        assert a.licensed_pcs == frozenset(m["pc"] for m in scalar)
        assert a.mem_pages_touch_bound == 1

    def test_misaligned_refused(self):
        _, a = analyzed(build_memfuse_workload(64, byte_offset=2))
        scalar = [m for m in a.funcs[0].mem_facts
                  if m["kind"] in ("load", "store")]
        assert scalar and all(not m["licensed"] for m in scalar)
        assert all(m["in_bounds"] and not m["aligned"] for m in scalar)
        assert a.licensed_sites == 0 and a.unlicensed_sites == 2

    def test_oob_adjacent_refused(self):
        # 16385 words * 4 bytes overruns the single 64 KiB page
        _, a = analyzed(build_memfuse_workload(16385))
        scalar = [m for m in a.funcs[0].mem_facts
                  if m["kind"] in ("load", "store")]
        assert scalar and all(not m["in_bounds"] for m in scalar)
        assert a.licensed_sites == 0
        assert a.mem_pages_touch_bound == 2  # finite, just over a page

    def test_refinement_severed_by_clobbering_write(self):
        """A comparison computed on a local's ENTRY value must not
        refine the interval of its POST-clobber value: compute
        `i <u 10` first, then i := param (opaque) + 1, branch on the
        stale comparison — the load at i*4 is genuinely unbounded and
        must NOT be licensed (the one shape that would break the
        fused path's bit-identity by skipping a real trap)."""
        b = ModuleBuilder()
        b.add_memory(1, 1)
        # locals: 0=param, 1=i
        b.add_function(["i32"], ["i32"], ["i32"], [
            ("local.get", 1), ("i32.const", 10), "i32.lt_u",  # entry i
            ("local.get", 0), ("local.set", 1),               # clobber
            ("local.get", 1), ("i32.const", 1), "i32.add",
            ("local.set", 1),
            ("if", "i32"),                                    # stale cmp
            ("local.get", 1), ("i32.const", 4), "i32.mul",
            ("i32.load", 2, 0),
            "else",
            ("i32.const", 0),
            "end",
        ], export="f")
        _, a = analyzed(b.build())
        loads = [m for m in a.funcs[0].mem_facts if m["kind"] == "load"]
        assert loads and not loads[0]["licensed"]
        assert not loads[0]["in_bounds"]
        assert a.licensed_sites == 0

    def test_hostcalls_void_touch_bound(self):
        from wasmedge_tpu.models import build_echo

        _, a = analyzed(build_echo())
        assert a.tier0_sites + a.drain_sites > 0
        assert a.mem_pages_touch_bound is None

    def test_hv_budget_seeds_from_touch_bound(self):
        """A module declaring more pages than it can touch is charged
        the PROVEN touch, not the declaration."""
        from wasmedge_tpu.hv.policy import (
            _geometry_lane_bytes, effective_lane_bytes)

        b = ModuleBuilder()
        b.add_memory(4, 4)          # 4 pages declared + resident
        b.add_function(["i32"], ["i32"], ["i32", "i32"], [
            ("block", None), ("loop", None),
            ("local.get", 1), ("i32.const", 4), "i32.mul",
            ("local.get", 1), ("i32.store", 2, 0),
            ("local.get", 1), ("i32.const", 1), "i32.add",
            ("local.set", 1),
            ("local.get", 1), ("i32.const", 16), "i32.lt_u",
            ("br_if", 0),
            "end", "end",
            ("local.get", 2)], export="f")
        eng = engine_of(b.build(), memory_pages_per_lane=4)
        a = eng.img.analysis
        assert a.mem_pages_touch_bound == 1
        assert a.mem_pages_bound == 4
        eff = effective_lane_bytes(eng)
        geo = _geometry_lane_bytes(eng)
        assert eff <= geo - 3 * 65536  # 3 untouched pages reclaimed

    def test_policy_max_pages_touched(self):
        proven, _ = AnalysisPolicy(max_memory_pages_touched=1), None
        _, a_ok = analyzed(build_memfuse_workload(64))
        assert proven.evaluate(a_ok) == []
        from wasmedge_tpu.models import build_memory_workload

        _, a_bad = analyzed(build_memory_workload())  # param-driven
        v = proven.evaluate(a_bad)
        assert v and v[0]["limit"] == "max_memory_pages_touched"
        assert v[0]["actual"] == "unbounded"


class TestReportSchema:
    def _doc(self, data=None):
        mod, a = analyzed(data or build_memfuse_workload(64))
        return a.to_dict()

    def test_absint_report_validates(self):
        assert validate_report(self._doc()) == []

    def test_pre_absint_report_back_compat(self):
        """A report WITHOUT the r19 keys (what older artifacts and
        peers emit) must still validate."""
        doc = self._doc()
        doc["summary"].pop("mem_pages_touch_bound")
        doc["summary"].pop("licensed_mem_sites")
        doc["summary"].pop("unlicensed_mem_sites")
        doc["summary"].pop("trip_bounded_loops")
        doc["memory"].pop("pages_touch_bound")
        for f in doc["funcs"]:
            f.pop("loops")
            f.pop("mem_facts")
        assert validate_report(doc) == []

    def test_bounded_with_unbounded_loop_flagged(self):
        doc = self._doc()
        fn = next(f for f in doc["funcs"] if f["has_loop"])
        fn["loops"][0]["trip_bound"] = None
        assert any("unbounded loop" in p for p in validate_report(doc))

    def test_license_without_proof_flagged(self):
        doc = self._doc()
        fn = doc["funcs"][0]
        fact = next(m for m in fn["mem_facts"]
                    if m["kind"] in ("load", "store"))
        fact["aligned"] = False
        assert any("licensed without" in p for p in validate_report(doc))

    def test_mem_run_license_reconciliation(self):
        """licensed runs must be a superset of realized runs: an
        unlicensed load/store inside a fused mem run is flagged."""
        from wasmedge_tpu.batch.fuse import plan_fusion
        from wasmedge_tpu.batch.image import build_device_image

        conf = Configure()
        mod = load_validate(build_memfuse_workload(64), conf)
        a = analyze_validated(mod)
        img = build_device_image(mod.lowered, mod=mod)
        doc = a.to_dict()
        doc["fusion"] = plan_fusion(img, conf.batch, analysis=a)
        assert doc["fusion"]["memory"]["mem_runs"] > 0
        assert validate_report(doc) == []
        # forge: revoke one license the planner consumed
        head, n, _ = doc["fusion"]["mem_runs"][0]
        for f in doc["funcs"]:
            for m in f["mem_facts"]:
                if head <= m["pc"] < head + n:
                    m["licensed"] = False
                    m["aligned"] = False
        assert any("unlicensed load/store" in p
                   for p in validate_report(doc))
        # count drift in the memory section is flagged too
        doc2 = self._doc()
        doc2["fusion"] = plan_fusion(
            build_device_image(load_validate(
                build_memfuse_workload(64)).lowered),
            conf.batch, analysis=analyze_validated(
                load_validate(build_memfuse_workload(64))))
        doc2["fusion"]["memory"]["mem_runs"] += 1
        assert any("disagrees with the realized run list" in p
                   for p in validate_report(doc2))

    def test_cli_disasm_annotates_trips_and_mem(self, tmp_path):
        import json

        from wasmedge_tpu.cli import analyze_command

        wasm = tmp_path / "m.wasm"
        wasm.write_bytes(build_memfuse_workload(64))
        out = tmp_path / "report.json"
        rc = analyze_command([str(wasm), "--disasm", "--out",
                              str(out)])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert validate_report(doc) == []
        dis = doc["disasm"]
        assert "trip<=64" in dis
        assert "licensed" in dis and "mem@" in dis
        assert "memfused=" in dis
        # and the unbounded marking still renders for honest loops
        wasm2 = tmp_path / "u.wasm"
        wasm2.write_bytes(build_loop_sum())
        out2 = tmp_path / "u.json"
        assert analyze_command([str(wasm2), "--disasm", "--out",
                                str(out2)]) == 0
        assert "trip=unbounded" in json.loads(out2.read_text())["disasm"]
