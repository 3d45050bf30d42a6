"""SIMT-tier superinstruction fusion (batch/fuse.py) — ISSUE 13.

Pins the translation pass (analyzer candidates -> fused dispatch cells
in the device image) and its hard guarantees:

  - fusion on/off bit-identical to each other AND to the gas-metered
    scalar engine (results, traps, retired counts);
  - a lane whose pc sits mid-run executes the original per-op stream
    (residue handoff / resume-from-state), bit-exactly;
  - gas exhaustion lands at the correct op with per-op attribution even
    when the budget runs out mid-superinstruction (flat AND weighted);
  - opcode histogram == retired under fusion (per-constituent op_id);
  - the degradation ladder gains a rung: a fused-step fault demotes to
    the unfused SIMT build (checkpoints transfer) before scalar;
  - planning is block-local (never spans leaders/branches/terminators),
    non-overlapping, and reported planned-vs-realized per candidate.

Fast by construction (tiny lane counts, short chunks): tier-1.
"""

import io
import json
import os

import numpy as np
import pytest

from wasmedge_tpu.batch.engine import BatchEngine
from wasmedge_tpu.batch.fuse import (
    cell_eligible,
    fusion_active,
    plan_fusion,
)
from wasmedge_tpu.batch.image import TRAP_DONE, build_device_image
from wasmedge_tpu.common.configure import Configure
from wasmedge_tpu.common.errors import ErrCode
from wasmedge_tpu.models import build_fib, build_loop_sum
from tests.helpers import instantiate, load_validate

pytestmark = pytest.mark.fuse

LANES = 16


def fib_ref(n):
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def make_conf(fuse=True, **batch):
    conf = Configure()
    conf.batch.fuse_superinstructions = fuse
    conf.batch.steps_per_launch = 200
    for k, v in batch.items():
        setattr(conf.batch, k, v)
    return conf


def make_engine(data, conf, lanes=LANES, mesh=None):
    ex, store, inst = instantiate(data, conf)
    return BatchEngine(inst, store=store, conf=conf, lanes=lanes,
                       mesh=mesh)


def div_args(lanes=LANES, lo=4, hi=12):
    return [(lo + np.arange(lanes) % (hi - lo + 1)).astype(np.int64)]


def assert_results_identical(a, b):
    for ra, rb in zip(a.results, b.results):
        assert (np.asarray(ra) == np.asarray(rb)).all()
    assert (np.asarray(a.trap) == np.asarray(b.trap)).all()
    assert (np.asarray(a.retired) == np.asarray(b.retired)).all()


# ---------------------------------------------------------------------------
# translation pass: planning invariants
# ---------------------------------------------------------------------------
class TestPlanning:
    def test_fib_realizes_runs_within_blocks(self):
        conf = Configure()
        mod = load_validate(build_fib(), conf)
        img = build_device_image(mod.lowered, mod=mod)
        report = plan_fusion(img, conf.batch)
        assert report["enabled"] and report["fused_runs"] > 0
        assert report["patterns"] >= 1
        flen = np.asarray(img.fuse_len)
        fpat = np.asarray(img.fuse_pat)
        analysis = img.analysis
        # block spans: [start, end] per basic block, terminator excluded
        # for non-fallthrough blocks (the planner's own rule, re-derived
        # here from the r12 CFG so a planner regression can't self-pin)
        spans = []
        for f in analysis.funcs:
            for b in f.cfg.blocks:
                end = b.end if b.kind == "fallthrough" else b.end - 1
                spans.append((b.start, end))
        covered = np.zeros(flen.shape[0], bool)
        for head, n, k in report["runs"]:
            assert n >= 2
            assert flen[head] == n and fpat[head] == k
            assert 0 <= k < len(img.fuse_patterns)
            assert len(img.fuse_patterns[k]) == n
            # strictly inside ONE block (never spans a leader/terminator)
            assert any(s <= head and head + n - 1 <= e for s, e in spans)
            # no overlap between runs
            assert not covered[head:head + n].any()
            covered[head:head + n] = True
            # every constituent cell is an eligible pure stack/ALU op
            for j in range(n):
                assert cell_eligible(int(img.cls[head + j]),
                                     int(img.sub[head + j]))
        # non-head cells carry no fuse metadata
        heads = {r[0] for r in report["runs"]}
        for p in np.nonzero(flen)[0]:
            assert int(p) in heads
        # report arithmetic: realized counts reconcile
        assert report["fused_cells"] == int(flen.sum())
        assert report["fused_runs"] == sum(
            c["realized_runs"] for c in report["candidates"])
        for c in report["candidates"]:
            assert c["realized_runs"] <= c["planned"]

    def test_knob_off_plans_nothing(self):
        conf = make_conf(fuse=False)
        eng = make_engine(build_fib(), conf, lanes=4)
        eng.run("fib", [np.full(4, 5, np.int64)], max_steps=10_000)
        assert eng.img.fuse_len is None
        assert getattr(eng.img, "fusion_report", None) is None
        assert not fusion_active(eng.img, conf.batch)

    def test_knob_on_engine_plans_at_build(self):
        conf = make_conf()
        eng = make_engine(build_fib(), conf, lanes=4)
        # planning is deferred: a merely-constructed engine must not
        # have paid the analyzer (r12 lazy-analysis guarantee)
        assert getattr(eng.img, "fusion_report", None) is None
        eng.run("fib", [np.full(4, 5, np.int64)], max_steps=10_000)
        assert eng.img.fusion_report["fused_runs"] > 0
        assert fusion_active(eng.img, conf.batch)

    def test_top_k_zero_plans_nothing(self):
        conf = make_conf(fuse_top_k=0)
        eng = make_engine(build_fib(), conf, lanes=4)
        eng.run("fib", [np.full(4, 5, np.int64)], max_steps=10_000)
        assert eng.img.fuse_len is None
        assert not fusion_active(eng.img, conf.batch)


# ---------------------------------------------------------------------------
# bit-exactness: fused vs unfused SIMT vs gas-metered scalar
# ---------------------------------------------------------------------------
class TestBitExact:
    def test_fused_matches_unfused_and_scalar(self):
        from wasmedge_tpu.batch.supervisor import scalar_rerun

        args = div_args()
        res = {}
        for fuse in (True, False):
            conf = make_conf(fuse=fuse)
            eng = make_engine(build_fib(), conf)
            res[fuse] = eng.run("fib", args, max_steps=200_000)
            if fuse:
                assert fusion_active(eng.img, conf.batch)
        assert res[True].completed.all()
        assert_results_identical(res[True], res[False])
        # fewer dispatches is the whole point
        assert res[True].steps < res[False].steps
        expected = [fib_ref(int(n)) for n in args[0]]
        assert (res[True].results[0] == expected).all()
        # gas-metered scalar engine parity (the ladder's bottom rung)
        from wasmedge_tpu.batch.engine import check_batch_entry

        conf = make_conf()
        ex, store, inst = instantiate(build_fib(), conf)
        cells, trap, recs = scalar_rerun(
            inst, conf, "fib", check_batch_entry(inst, "fib"),
            args, np.arange(LANES), max_steps=200_000)
        assert not recs
        assert (trap == TRAP_DONE).all()
        assert (cells[0].view(np.int64) == res[True].results[0]).all()

    def test_echo_stdout_identical_fused_and_unfused(self, tmp_path):
        """The hostcall path: fd_write's byte stream, results, traps and
        retired counts are the same with fusion on and off."""
        from tests.test_serve import _echo_engine

        runs = {}
        for fuse in (True, False):
            path = tmp_path / f"out-{fuse}"
            eng, sink = _echo_engine(
                make_conf(fuse=fuse, steps_per_launch=100), LANES, path)
            res = eng.run("echo", [np.full(LANES, 2, np.int64)],
                          max_steps=1_000_000)
            os.close(sink)
            runs[fuse] = (res, path.read_bytes())
        (on, out_on), (off, out_off) = runs[True], runs[False]
        assert on.completed.all() and off.completed.all()
        assert_results_identical(on, off)
        assert out_on == out_off
        assert len(out_on) == LANES * 2 * 2 * len(b"hello wasi echo\n")

    def test_mid_run_resume_executes_per_op(self):
        """A state whose pcs sit MID-superinstruction (exported at an
        arbitrary step boundary of the unfused build) resumes on the
        fused build bit-exactly: mid-run lanes step per-op to the next
        head, head lanes take the fused dispatch."""
        args = div_args()
        conf_f = make_conf(steps_per_launch=1)
        fused = make_engine(build_fib(), conf_f)
        fused._plan_fusion()  # planning is deferred to first build
        flen = np.asarray(fused.img.fuse_len)
        midrun = np.zeros(flen.shape[0] + 1, bool)
        for h in np.nonzero(flen >= 2)[0]:
            midrun[h + 1:h + flen[h]] = True

        conf_u = make_conf(fuse=False, steps_per_launch=1)
        unfused = make_engine(build_fib(), conf_u)
        fi = unfused.export_func_idx("fib")
        state = unfused.initial_state(fi, args)
        total = 0
        hit = False
        for _ in range(200):
            state, total = unfused.run_from_state(state, total, total + 1)
            pcs = np.asarray(state.pc)[np.asarray(state.trap) == 0]
            if midrun[np.clip(pcs, 0, flen.shape[0] - 1)].any():
                hit = True
                break
        assert hit, "never reached a mid-superinstruction pc"
        # resume the SAME state on BOTH builds (host snapshot: the chunk
        # loop donates its input buffers); finish bit-identically
        import jax.numpy as jnp

        def replica():
            return state._replace(**{
                n: jnp.asarray(np.asarray(getattr(state, n)).copy())
                for n in state._fields
                if getattr(state, n) is not None})

        sf, tf = fused.run_from_state(replica(), total, 200_000)
        su, tu = unfused.run_from_state(replica(), total, 200_000)
        assert tf < tu  # the fused continuation used fewer dispatches
        for plane in ("pc", "sp", "retired", "trap", "stack_lo",
                      "stack_hi", "glob_lo", "glob_hi", "mem"):
            assert (np.asarray(getattr(sf, plane))
                    == np.asarray(getattr(su, plane))).all(), plane
        assert (np.asarray(sf.trap) == TRAP_DONE).all()

    def test_divergent_uniform_handoff(self):
        """The uniform engine's divergence handoff lands mid-stream on
        the fused SIMT build (the residue seam named by the ISSUE)."""
        from wasmedge_tpu.batch.uniform import UniformBatchEngine

        args = div_args()
        out = {}
        for fuse in (True, False):
            conf = make_conf(fuse=fuse)
            ex, store, inst = instantiate(build_fib(), conf)
            eng = UniformBatchEngine(inst, store=store, conf=conf,
                                     lanes=LANES)
            out[fuse] = eng.run("fib", args, max_steps=200_000)
        assert out[True].completed.all()
        assert_results_identical(out[True], out[False])
        expected = [fib_ref(int(n)) for n in args[0]]
        assert (out[True].results[0] == expected).all()


# ---------------------------------------------------------------------------
# gas: exhaustion mid-superinstruction lands at the correct op
# ---------------------------------------------------------------------------
class TestGas:
    def _exhaust(self, conf_extra):
        """Run fused and unfused builds from the same initial state with
        a per-lane fuel ramp wide enough that exhaustion sweeps across
        every stream position — including positions strictly inside a
        fused run.  Returns (fused_state, unfused_state, fused_img)."""
        import jax.numpy as jnp

        args = [np.full(LANES, 10, np.int64)]
        states = {}
        img_f = None
        for fuse in (True, False):
            conf = make_conf(fuse=fuse, fuel_per_launch=1_000_000,
                             **conf_extra)
            eng = make_engine(build_fib(), conf)
            if fuse:
                img_f = eng.img
            fi = eng.export_func_idx("fib")
            st = eng.initial_state(fi, args)
            fuel = 20 + 3 * np.arange(LANES, dtype=np.int32)
            st = st._replace(fuel=jnp.asarray(fuel))
            states[fuse] = eng.run_from_state(st, 0, 200_000)[0]
        return states[True], states[False], img_f

    def _pin(self, sf, su, img):
        for plane in ("pc", "sp", "fp", "retired", "trap", "fuel"):
            a = np.asarray(getattr(sf, plane))
            b = np.asarray(getattr(su, plane))
            assert (a == b).all(), f"{plane} diverged under gas"
        trap = np.asarray(sf.trap)
        assert (trap == int(ErrCode.CostLimitExceeded)).any()
        # at least one exhaustion pc sits strictly INSIDE a fused run
        flen = np.asarray(img.fuse_len)
        midrun = np.zeros(flen.shape[0], bool)
        for h in np.nonzero(flen >= 2)[0]:
            midrun[h + 1:h + flen[h]] = True
        pcs = np.asarray(sf.pc)[trap == int(ErrCode.CostLimitExceeded)]
        assert midrun[np.clip(pcs, 0, flen.shape[0] - 1)].any(), \
            "fuel ramp never exhausted mid-superinstruction"

    def test_flat_gas_mid_run(self):
        self._pin(*self._exhaust({}))

    def test_weighted_gas_mid_run(self):
        from wasmedge_tpu.common.statistics import _NUM_COST_SLOTS

        table = tuple(1 + (i % 3) for i in range(_NUM_COST_SLOTS))
        self._pin(*self._exhaust({"cost_table": table}))


# ---------------------------------------------------------------------------
# obs: histogram == retired per constituent op; fused/unfused split
# ---------------------------------------------------------------------------
class TestObs:
    def _obs_run(self, fuse):
        conf = make_conf(fuse=fuse)
        conf.obs.enabled = True
        conf.obs.opcode_histogram = True
        eng = make_engine(build_fib(), conf)
        res = eng.run("fib", div_args(), max_steps=200_000)
        return eng, res

    def test_histogram_equals_retired_under_fusion(self):
        engs, ress = {}, {}
        for fuse in (True, False):
            engs[fuse], ress[fuse] = self._obs_run(fuse)
        assert_results_identical(ress[True], ress[False])
        cf = engs[True].obs.opcode_counts
        cu = engs[False].obs.opcode_counts
        assert cf is not None and cu is not None
        # per-constituent attribution: the fused histogram is IDENTICAL
        # to the unfused one, and both equal total retired
        assert (cf == cu).all()
        assert cf.sum() == np.asarray(ress[True].retired).sum()

    def test_fused_counters_and_prometheus(self):
        from wasmedge_tpu.obs.metrics import (
            parse_prometheus, render_prometheus)

        eng, res = self._obs_run(True)
        fc = eng.obs.fused_counts
        retired = int(np.asarray(res.retired, np.int64).sum())
        assert fc["dispatches"] > 0
        assert fc["retired_fused"] >= 2 * fc["dispatches"]
        assert fc["retired_total"] == retired
        text = render_prometheus(recorder=eng.obs)
        fams = parse_prometheus(text)
        assert fams[("wasmedge_fused_dispatches_total",
                     frozenset())] == fc["dispatches"]
        rf = fams[("wasmedge_retired_by_path_total",
                   frozenset({("path", "fused")}))]
        ru = fams[("wasmedge_retired_by_path_total",
                   frozenset({("path", "unfused")}))]
        assert rf == fc["retired_fused"]
        assert rf + ru == retired

    def test_unfused_run_exports_no_fused_metrics(self):
        from wasmedge_tpu.obs.metrics import render_prometheus

        eng, _res = self._obs_run(False)
        assert eng.obs.fused_counts["dispatches"] == 0
        assert "wasmedge_fused_dispatches_total" not in \
            render_prometheus(recorder=eng.obs)


# ---------------------------------------------------------------------------
# mesh + multi-tenant: fused planes ride the shard drive and concat
# ---------------------------------------------------------------------------
class TestComposition:
    def test_shard_drive_fused_parity(self):
        from wasmedge_tpu.parallel.mesh import lane_mesh

        args = div_args(32, 4, 11)
        out = {}
        for fuse in (True, False):
            conf = make_conf(fuse=fuse)
            out[fuse] = make_engine(build_fib(), conf, lanes=32,
                                    mesh=lane_mesh(8)).run(
                "fib", args, max_steps=200_000)
        solo = make_engine(build_fib(), make_conf(), lanes=32).run(
            "fib", args, max_steps=200_000)
        assert out[True].completed.all()
        assert_results_identical(out[True], out[False])
        assert_results_identical(out[True], solo)

    def test_multitenant_concat_fused_parity(self):
        from wasmedge_tpu.batch.multitenant import (
            MultiTenantBatchEngine, Tenant)

        L = 8
        out = {}
        for fuse in (True, False):
            conf = make_conf(fuse=fuse)
            tenants = []
            for data, fn, args in (
                    (build_fib(), "fib", div_args(L, 4, 9)),
                    (build_loop_sum(), "loop_sum",
                     [np.full(L, 25, np.int64)])):
                ex, store, inst = instantiate(data, conf)
                tenants.append(Tenant(
                    engine=BatchEngine(inst, store=store, conf=conf,
                                       lanes=L),
                    func_name=fn, args_lanes=args, lanes=L))
            mt = MultiTenantBatchEngine(tenants, conf=conf)
            if fuse:
                img = mt.img
                assert img.fuse_len is not None
                assert img.fusion_report["fused_cells"] == \
                    int(np.asarray(img.fuse_len).sum())
                assert len(img.fuse_patterns) <= 16
            out[fuse] = mt.run_tenants(max_steps=200_000)
        for a, b in zip(out[True], out[False]):
            assert a.completed.all()
            assert_results_identical(a, b)


# ---------------------------------------------------------------------------
# ladder: fused-step fault demotes fused -> unfused SIMT -> scalar
# ---------------------------------------------------------------------------
@pytest.mark.faults
class TestLadder:
    def _sup(self, tmp_path, inj, sub, **sup):
        from wasmedge_tpu.batch.supervisor import BatchSupervisor

        conf = make_conf(steps_per_launch=100)
        conf.supervisor.backoff_base_s = 0.0
        conf.supervisor.checkpoint_every_steps = 200
        conf.supervisor.max_retries = 2
        for k, v in sup.items():
            setattr(conf.supervisor, k, v)
        return BatchSupervisor(make_engine(build_fib(), conf),
                               faults=inj,
                               checkpoint_dir=str(tmp_path / sub))

    def test_fused_fault_demotes_to_unfused_simt(self, tmp_path):
        from wasmedge_tpu.testing.faults import Fault, FaultInjector

        args = div_args()
        ref = self._sup(tmp_path, None, "ref").run(
            "fib", args, max_steps=200_000)
        # launches 2..4 fault: the fused rung has checkpointed by then,
        # exhausts its retries, and the unfused rung must ADOPT the
        # fused rung's checkpoint instead of replaying from scratch
        inj = FaultInjector([Fault(point="launch", at=2, times=3)])
        sup = self._sup(tmp_path, inj, "a")
        res = sup.run("fib", args, max_steps=200_000)
        assert inj.fired == 3
        assert res.completed.all()
        assert_results_identical(res, ref)
        classes = [f.fault_class for f in sup.failures]
        assert classes.count("launch") == 3
        assert "demote" in classes
        # the demoted engine really is the unfused build, resumed from
        # the fused rung's lineage — and its conf.batch agrees with its
        # cfg, so the obs plane allocator can never disagree with the
        # step builder about fusion_active
        assert sup.engine.cfg.fuse_superinstructions is False
        assert sup.engine.conf.batch.fuse_superinstructions is False
        assert sup._restored_from is not None

    def test_full_ladder_to_scalar(self, tmp_path):
        from wasmedge_tpu.testing.faults import Fault, FaultInjector

        args = div_args()
        inj = FaultInjector([Fault(point="launch", at=0, times=1000)])
        sup = self._sup(tmp_path, inj, "b")
        res = sup.run("fib", args, max_steps=200_000)
        assert res.completed.all()
        expected = [fib_ref(int(n)) for n in args[0]]
        assert (res.results[0] == expected).all()
        classes = [f.fault_class for f in sup.failures]
        # 3 launch faults on the fused rung + 3 on the unfused rung
        assert classes.count("launch") == 6
        assert classes.count("demote") == 2

    def test_demotion_does_not_leak_into_next_run(self, tmp_path):
        from wasmedge_tpu.testing.faults import Fault, FaultInjector

        args = div_args()
        inj = FaultInjector([Fault(point="launch", at=0, times=3)])
        sup = self._sup(tmp_path, inj, "d")
        res = sup.run("fib", args, max_steps=200_000)
        assert res.completed.all()
        assert sup.engine.cfg.fuse_superinstructions is False
        # a later run() on the same supervisor starts from the pristine
        # (fused) engine again — one demotion never de-fuses forever
        res2 = sup.run("fib", args, max_steps=200_000)
        assert res2.completed.all()
        assert sup.engine.cfg.fuse_superinstructions is True
        assert_results_identical(res, res2)

    def test_knob_off_ladder_has_no_unfused_rung(self, tmp_path):
        from wasmedge_tpu.batch.supervisor import BatchSupervisor
        from wasmedge_tpu.testing.faults import Fault, FaultInjector

        conf = make_conf(fuse=False, steps_per_launch=100)
        conf.supervisor.backoff_base_s = 0.0
        conf.supervisor.max_retries = 2
        inj = FaultInjector([Fault(point="launch", at=0, times=1000)])
        sup = BatchSupervisor(make_engine(build_fib(), conf),
                              faults=inj,
                              checkpoint_dir=str(tmp_path / "c"))
        res = sup.run("fib", div_args(), max_steps=200_000)
        assert res.completed.all()
        classes = [f.fault_class for f in sup.failures]
        assert classes.count("launch") == 3  # one SIMT rung only
        assert classes.count("demote") == 1


# ---------------------------------------------------------------------------
# report schema + analyze CLI
# ---------------------------------------------------------------------------
class TestReport:
    def _report(self):
        from wasmedge_tpu.analysis import analyze_validated, validate_report

        conf = Configure()
        mod = load_validate(build_fib(), conf)
        analysis = analyze_validated(mod)
        img = build_device_image(mod.lowered, mod=mod)
        doc = analysis.to_dict()
        doc["fusion"] = plan_fusion(img, conf.batch, analysis=analysis)
        return doc, validate_report

    def test_fusion_section_validates(self):
        doc, validate_report = self._report()
        assert validate_report(doc) == []
        assert doc["fusion"]["fused_runs"] > 0
        assert any(c["realized_runs"] for c in doc["fusion"]["candidates"])

    def test_fusion_section_bad_counts_flagged(self):
        doc, validate_report = self._report()
        doc["fusion"]["candidates"][0]["realized_runs"] = 10 ** 6
        problems = validate_report(doc)
        assert any("realized_runs > planned" in p for p in problems)
        assert any("disagrees" in p for p in problems)

    def test_cli_analyze_disasm_marks_fused_runs(self, tmp_path):
        from wasmedge_tpu.cli import analyze_command

        path = str(tmp_path / "fib.wasm")
        with open(path, "wb") as f:
            f.write(build_fib())
        out, err = io.StringIO(), io.StringIO()
        rc = analyze_command([path, "--disasm"], out=out, err=err)
        assert rc == 0, err.getvalue()
        doc = json.loads(out.getvalue())
        assert doc["fusion"]["fused_runs"] > 0
        assert "fused=" in doc["disasm"]
