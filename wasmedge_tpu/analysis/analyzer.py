"""ModuleAnalysis: static facts the runtime layers consume.

Per defined function, over the validated/lowered image (no execution):

  - basic-block CFG (analysis/cfg.py) with loop/back-edge marking
  - straight-line opcode n-gram census ranked as superinstruction
    candidates (block metadata for the ROADMAP #3 fusion tier)
  - a SOUND per-invocation cost upper bound: every retired instruction
    costs its cost-table weight (flat 1 by default, i.e. the bound is
    in retired-instruction units); loops, recursion, and dynamic calls
    (call_indirect — the table could route back) make the verdict
    "unbounded" (cost_bound None) rather than a guess
  - hostcall-site inventory split tier-0-serviceable (in-kernel WASI,
    batch/image.py T0_WASI_KINDS with the same fd-safety/memory gates)
    vs drain-required (device<->host round trip)
  - a divergence-risk score per block (branch fan-out, data-dependent
    brtables, dynamic calls, loop residency) for ROADMAP #5 scheduling
  - static memory/stack footprint bounds (declared pages + grow sites,
    value-stack and frame-depth bounds along the static call graph) for
    ROADMAP #4 resident-lane budgeting

Soundness contract (pinned by tests/test_analysis.py): for any
terminating run of an exported
function, cost_bound is None (unbounded verdict) or >= the engine's
retired-instruction count for that invocation.  Overcounting is fine;
undercounting is a bug.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from wasmedge_tpu.analysis.cfg import BasicBlock, FuncCFG, build_func_cfg, \
    longest_path_cost
from wasmedge_tpu.common.opcodes import NAME_TO_ID, Op
from wasmedge_tpu.validator.image import LoweredModule, lop_name

SCHEMA = "wasmedge-tpu/analysis/v1"

_OP_CALL = NAME_TO_ID["call"]
_OP_RETCALL = NAME_TO_ID["return_call"]
_OP_MEMGROW = NAME_TO_ID["memory.grow"]

# An imported function executes as a 2-instruction synthetic stub on
# the batch engines (HOSTCALL + RETURN, batch/image.py): bound its cost
# by the stub length.  The host-side service time is not instruction
# retirement and is budgeted elsewhere (drain histograms, obs/).
IMPORT_STUB_COST = 2

# n-gram window sizes for the superinstruction census, and how many
# ranked candidates the report keeps.
NGRAM_SIZES = (2, 3, 4)
MAX_CANDIDATES = 16
LOOP_WEIGHT = 8  # census weight of an occurrence inside a CFG cycle


@dataclasses.dataclass
class HostcallSite:
    pc: int
    func_idx: int                   # imported function called
    import_name: str                # "module.name"
    tier0: bool                     # serviceable in-kernel (tier 0)
    kind: str                       # WASI call name, or "" for non-WASI

    def asdict(self) -> dict:
        return {"pc": self.pc, "func": self.func_idx,
                "import": self.import_name, "tier0": self.tier0,
                "kind": self.kind}


@dataclasses.dataclass
class FuncAnalysis:
    idx: int
    name: str                       # export name when exported
    entry_pc: int
    end_pc: int
    cfg: FuncCFG
    block_costs: List[int]          # per-block cost EXCLUDING callees
    has_loop: bool = False
    recursive: bool = False
    dynamic_calls: bool = False
    cost_bound: Optional[int] = None
    value_stack_bound: Optional[int] = None
    call_depth_bound: Optional[int] = None
    divergence: int = 0             # max block divergence score
    block_divergence: List[int] = dataclasses.field(default_factory=list)
    block_ngrams: List[List[int]] = dataclasses.field(default_factory=list)
    hostcall_sites: List[HostcallSite] = dataclasses.field(
        default_factory=list)
    # absint (analysis/absint.py) products: one entry per CFG loop
    # ({"head": pc, "trip_bound": int|None}) and one per memory-access
    # site ({"pc", "kind", "nbytes", "lo", "hi", "align", "in_bounds",
    # "aligned", "licensed"})
    loops: List[dict] = dataclasses.field(default_factory=list)
    mem_facts: List[dict] = dataclasses.field(default_factory=list)

    @property
    def bounded(self) -> bool:
        return self.cost_bound is not None

    def asdict(self) -> dict:
        blocks = []
        for i, b in enumerate(self.cfg.blocks):
            blocks.append({
                "start": b.start, "end": b.end,
                "succ": list(b.succ), "kind": b.kind,
                "cost": self.block_costs[i],
                "in_loop": b.in_loop, "loop_head": b.is_loop_head,
                "brtable_entries": b.brtable_entries,
                "divergence": self.block_divergence[i],
                "ngrams": list(self.block_ngrams[i]),
            })
        return {
            "idx": self.idx, "name": self.name,
            "entry_pc": self.entry_pc, "end_pc": self.end_pc,
            "has_loop": self.has_loop, "recursive": self.recursive,
            "dynamic_calls": self.dynamic_calls,
            "bounded": self.bounded,
            "cost_bound": self.cost_bound,
            "value_stack_bound": self.value_stack_bound,
            "call_depth_bound": self.call_depth_bound,
            "divergence": self.divergence,
            "hostcall_sites": [s.asdict() for s in self.hostcall_sites],
            "loops": [dict(l) for l in self.loops],
            "mem_facts": [dict(m) for m in self.mem_facts],
            "blocks": blocks,
        }


@dataclasses.dataclass
class ModuleAnalysis:
    """The full static report; attached to DeviceImage at build time
    and serialized by the analyze CLI / gateway admission policy."""

    funcs: List[FuncAnalysis]
    imports: List[dict]             # imported funcs: name/tier0/kind
    superinstructions: List[dict]
    code_len: int = 0
    n_funcs: int = 0
    exports: Dict[str, int] = dataclasses.field(default_factory=dict)
    bounded: bool = False
    cost_bound: Optional[int] = None
    value_stack_bound: Optional[int] = None
    call_depth_bound: Optional[int] = None
    divergence: int = 0
    mem_pages_init: int = 0
    mem_pages_max: int = 0          # declared max; 0 = none declared
    mem_grow_sites: int = 0
    mem_pages_bound: Optional[int] = None
    tier0_sites: int = 0
    drain_sites: int = 0
    dynamic_call_sites: int = 0
    # absint aggregate: proven max page TOUCH (every access site's
    # effective-address range is finite and hostcalls cannot write
    # guest memory), vs the declared bound above; plus the licensed
    # (trap-free-provable) vs unproven scalar load/store site split —
    # batch/fuse.py consumes licensed_pcs as its fusion license
    mem_pages_touch_bound: Optional[int] = None
    licensed_sites: int = 0
    unlicensed_sites: int = 0
    licensed_pcs: frozenset = frozenset()

    def func_by_idx(self, idx: int) -> Optional[FuncAnalysis]:
        for f in self.funcs:
            if f.idx == idx:
                return f
        return None

    def summary(self) -> dict:
        """The compact view the gateway returns in registration bodies
        and the admission policy evaluates."""
        return {
            "bounded": self.bounded,
            "cost_bound": self.cost_bound,
            "value_stack_bound": self.value_stack_bound,
            "call_depth_bound": self.call_depth_bound,
            "divergence": self.divergence,
            "mem_pages_bound": self.mem_pages_bound,
            "mem_pages_touch_bound": self.mem_pages_touch_bound,
            "mem_grow_sites": self.mem_grow_sites,
            "tier0_hostcall_sites": self.tier0_sites,
            "drain_hostcall_sites": self.drain_sites,
            "dynamic_call_sites": self.dynamic_call_sites,
            "superinstruction_candidates": len(self.superinstructions),
            "licensed_mem_sites": self.licensed_sites,
            "unlicensed_mem_sites": self.unlicensed_sites,
            "trip_bounded_loops": sum(
                1 for f in self.funcs for l in f.loops
                if l.get("trip_bound") is not None),
        }

    def to_dict(self) -> dict:
        return {
            "schema": SCHEMA,
            "code_len": self.code_len,
            "n_funcs": self.n_funcs,
            "exports": dict(self.exports),
            "summary": self.summary(),
            "memory": {
                "pages_init": self.mem_pages_init,
                "pages_max_declared": self.mem_pages_max,
                "grow_sites": self.mem_grow_sites,
                "pages_bound": self.mem_pages_bound,
                "pages_touch_bound": self.mem_pages_touch_bound,
            },
            "hostcalls": {
                "imports": list(self.imports),
                "tier0_sites": self.tier0_sites,
                "drain_sites": self.drain_sites,
                "dynamic_call_sites": self.dynamic_call_sites,
            },
            "superinstructions": list(self.superinstructions),
            "funcs": [f.asdict() for f in self.funcs],
        }

    # -- annotated disassembly --------------------------------------------
    def annotated_disasm(self, image: LoweredModule,
                         fusion: Optional[dict] = None) -> str:
        """LoweredModule.disasm interleaved with block/analysis
        annotations — the human half of the analyze CLI's report.
        `fusion` (a batch/fuse.py plan_fusion report) annotates which
        candidate runs were REALIZED as fused dispatch cells:
        `fused=<head>+<len>` marks on the owning block lines
        (`memfused=` for the r19 licensed load/store runs).  Loop
        heads carry their absint trip verdict (`trip<=N` /
        `trip=unbounded`), memory-access sites their proven
        range/alignment class."""
        runs_by_pc = {}
        for r in (fusion or {}).get("runs", ()):
            runs_by_pc[int(r[0])] = (int(r[1]), int(r[2]))
        mem_runs_by_pc = {}
        for r in (fusion or {}).get("mem_runs", ()):
            mem_runs_by_pc[int(r[0])] = (int(r[1]), int(r[2]))
        out: List[str] = []
        for f in self.funcs:
            flags = []
            if f.recursive:
                flags.append("recursive")
            if f.has_loop:
                flags.append("loop")
            if f.dynamic_calls:
                flags.append("dynamic-calls")
            bound = "unbounded" if f.cost_bound is None \
                else f"<= {f.cost_bound}"
            out.append(f";; func {f.idx} {f.name!r} "
                       f"[{f.entry_pc}..{f.end_pc}] cost {bound}"
                       + (f" ({', '.join(flags)})" if flags else ""))
            trips_by_head = {l["head"]: l["trip_bound"] for l in f.loops}
            for i, b in enumerate(f.cfg.blocks):
                marks = []
                if b.is_loop_head:
                    marks.append("loop-head")
                    t = trips_by_head.get(b.start)
                    marks.append("trip=unbounded" if t is None
                                 else f"trip<={t}")
                if b.in_loop:
                    marks.append("in-loop")
                if self.block_ngram_names(f, i):
                    marks.append(
                        "ngrams=" + ",".join(
                            "|".join(ops)
                            for ops in self.block_ngram_names(f, i)))
                fused_here = [f"{pc}+{n}" for pc, (n, _k)
                              in sorted(runs_by_pc.items())
                              if b.start <= pc <= b.end]
                if fused_here:
                    marks.append("fused=" + ",".join(fused_here))
                memfused_here = [f"{pc}+{n}" for pc, (n, _k)
                                 in sorted(mem_runs_by_pc.items())
                                 if b.start <= pc <= b.end]
                if memfused_here:
                    marks.append("memfused=" + ",".join(memfused_here))
                out.append(f";;   block [{b.start}..{b.end}] "
                           f"kind={b.kind} cost={f.block_costs[i]} "
                           f"div={f.block_divergence[i]} "
                           f"succ={list(b.succ)}"
                           + ((" " + " ".join(marks)) if marks else ""))
                for m in f.mem_facts:
                    if not (b.start <= m["pc"] <= b.end) \
                            or m["kind"] == "bulk":
                        continue
                    rng = "[?]" if m["hi"] is None \
                        else f"[{m['lo']}..{m['hi']}]"
                    verdict = "licensed" if m["licensed"] else \
                        ("in-bounds" if m["in_bounds"] else "unproven")
                    out.append(f";;     mem@{m['pc']} {m['kind']}"
                               f"{m['nbytes']} {rng} "
                               f"align={m['align']} {verdict}")
                out.append(image.disasm(b.start, b.end + 1))
        return "\n".join(out)

    def block_ngram_names(self, f: FuncAnalysis, block_i: int) \
            -> List[Tuple[str, ...]]:
        out = []
        for ci in f.block_ngrams[block_i]:
            if 0 <= ci < len(self.superinstructions):
                out.append(tuple(self.superinstructions[ci]["ops"]))
        return out


# ---------------------------------------------------------------------------
# tier-0 classification (mirrors batch/image.py build_device_image)
# ---------------------------------------------------------------------------

def _classify_imports(image: LoweredModule, has_memory: bool) \
        -> Dict[int, Tuple[bool, str, str]]:
    """func_idx -> (tier0, wasi_kind, 'module.name') for imports.
    Delegates the gating rules to batch/image.classify_t0_imports +
    T0_NEEDS_MEMORY — the SAME source the image build and
    t0_effective_kinds consume, so admission verdicts cannot drift
    from what the engine services in-kernel."""
    from wasmedge_tpu.batch.image import (
        T0_FD_WRITE, T0_NEEDS_MEMORY, T0_NONE, _WASI_MODULE,
        classify_t0_imports)

    kinds, fdwrite_safe = classify_t0_imports(image.funcs)
    out = {}
    for idx, fn in enumerate(image.funcs):
        if not fn.is_import:
            continue
        qual = f"{fn.import_module}.{fn.import_name}"
        kind = fn.import_name if fn.import_module == _WASI_MODULE else ""
        t0n = kinds.get(idx, T0_NONE)
        t0 = t0n != T0_NONE
        if t0n in T0_NEEDS_MEMORY and not has_memory:
            t0 = False
        if t0n == T0_FD_WRITE and not fdwrite_safe:
            t0 = False
        out[idx] = (t0, kind, qual)
    return out


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------

def analyze_validated(mod, cost_table=None) -> "ModuleAnalysis":
    """Analyze a VALIDATED AST module (loader/ast.py Module carrying
    `mod.lowered`): the shared front door for the CLI, bench smoke,
    and tests — one place derives exports + declared-memory facts, so
    the surfaces cannot drift from each other (the image-build path in
    batch/image.py stays the only instance-level variant)."""
    exports = {e.name: e.index for e in mod.exports if e.kind == 0}
    mems = mod.all_memory_types()
    # non-escaping-global seeding for absint: only module-local const
    # inits are extractable without instantiation (imported globals
    # make every index unknowable pre-link -> None, which degrades the
    # global domain to TOP, never to a wrong constant)
    globals_init = None
    if not mod.imported_globals():
        globals_init = []
        for g in mod.globals:
            if len(g.init) == 1 and g.init[0].op in (
                    Op.i32_const, Op.i64_const, Op.f32_const,
                    Op.f64_const):
                globals_init.append(int(g.init[0].imm))
            else:
                globals_init.append(None)
    return analyze_module(
        mod.lowered, exports=exports,
        mem_pages_init=mems[0].limit.min if mems else 0,
        mem_pages_max=(mems[0].limit.max or 0) if mems else 0,
        has_memory=bool(mems), cost_table=cost_table,
        globals_init=globals_init)


def analyze_module(image: LoweredModule,
                   exports: Optional[Dict[str, int]] = None,
                   mem_pages_init: int = 0,
                   mem_pages_max: int = 0,
                   has_memory: Optional[bool] = None,
                   cost_table=None,
                   globals_init=None) -> ModuleAnalysis:
    """Analyze a validated lowered image.  `exports` maps export name
    -> function index (used for naming and the module-level aggregate);
    `cost_table` maps opcode id -> gas weight (flat 1 = bounds in
    retired-instruction units); `globals_init` optionally carries the
    module globals' initial values (absint constant-folds the ones no
    global.set site can reach)."""
    exports = exports or {}
    if has_memory is None:
        has_memory = mem_pages_init > 0 or mem_pages_max > 0
    export_of = {}
    for name, idx in exports.items():
        export_of.setdefault(idx, name)

    def w(op: int) -> int:
        if cost_table is None:
            return 1
        try:
            return int(cost_table[op])
        except (IndexError, KeyError):
            return 1

    imports_info = _classify_imports(image, has_memory)

    # -- per-function CFGs + static call graph ------------------------------
    defined = [i for i, fn in enumerate(image.funcs) if not fn.is_import]
    cfgs: Dict[int, FuncCFG] = {i: build_func_cfg(image, i)
                                for i in defined}
    callees: Dict[int, set] = {i: set() for i in defined}
    dynamic: Dict[int, bool] = {i: False for i in defined}
    for i in defined:
        for b in cfgs[i].blocks:
            callees[i].update(b.calls)
            dynamic[i] = dynamic[i] or b.dynamic_call

    # recursion: any call-graph cycle reachable through static edges
    recursive = _callgraph_cycles(defined, callees)

    # -- abstract interpretation (analysis/absint.py): loop trip
    # bounds, memory-effect facts, fusion licenses.  Total by
    # construction (a per-function failure degrades to no facts).
    from wasmedge_tpu.analysis.absint import (
        analyze_module_absint, loop_nest_cost)

    absints = analyze_module_absint(
        image, cfgs, mem_pages_init=mem_pages_init,
        mem_pages_max=mem_pages_max, has_memory=bool(has_memory),
        globals_init=globals_init)

    # -- bottom-up bounds over the call-graph condensation ------------------
    cost_bound: Dict[int, Optional[int]] = {}
    stack_bound: Dict[int, Optional[int]] = {}
    depth_bound: Dict[int, Optional[int]] = {}
    for idx, fn in enumerate(image.funcs):
        if fn.is_import:
            cost_bound[idx] = IMPORT_STUB_COST
            stack_bound[idx] = fn.nparams + max(fn.nresults, 1)
            depth_bound[idx] = 1

    order = _postorder(defined, callees)
    block_costs: Dict[int, List[int]] = {}
    for i in order:
        fn = image.funcs[i]
        cfg = cfgs[i]
        own_costs = []
        for b in cfg.blocks:
            own_costs.append(sum(w(image.op[pc]) for pc in b.pcs()))
        block_costs[i] = own_costs
        if recursive[i] or dynamic[i]:
            cost_bound[i] = None
            stack_bound[i] = None
            depth_bound[i] = None
            continue

        bi_of = {b.start: bi for bi, b in enumerate(cfg.blocks)}

        def bcost(b: BasicBlock, _costs=own_costs, _bi=bi_of):
            total = _costs[_bi[b.start]]
            for k in b.calls:
                sub = cost_bound.get(k)
                if sub is None:
                    return None
                total += sub
            return total

        if cfg.has_loop:
            # counted loops: the absint trip bounds compose through
            # the loop-nest walk (trip x per-iteration longest path,
            # recursively); any unbounded loop poisons to None — the
            # seed's honest verdict, now only for loops that ARE
            # statically unbounded
            trips = absints[i].trips if i in absints else {}
            cost_bound[i] = loop_nest_cost(cfg, bcost, trips) \
                if trips else None
        else:
            cost_bound[i] = longest_path_cost(cfg, bcost)
        frame = fn.nlocals + fn.max_height
        sb: Optional[int] = frame
        db: Optional[int] = 1
        for k in callees[i]:
            ks, kd = stack_bound.get(k), depth_bound.get(k)
            if ks is None or kd is None:
                sb = db = None
                break
            sb = max(sb, frame + ks)
            db = max(db, 1 + kd)
        stack_bound[i] = sb
        depth_bound[i] = db

    # -- n-gram census ------------------------------------------------------
    census: Dict[Tuple[str, ...], List[int]] = {}  # ops -> [count, weight]
    runs: Dict[int, List[List[str]]] = {}  # func -> per-block op names
    for i in defined:
        per_block = []
        for b in cfgs[i].blocks:
            # the straight-line run excludes the control terminator
            # (a fused superinstruction cannot span a dispatch exit)
            end = b.end if b.kind == "fallthrough" else b.end - 1
            names = [lop_name(image.op[pc])
                     for pc in range(b.start, end + 1)]
            per_block.append(names)
            wgt = LOOP_WEIGHT if b.in_loop else 1
            for n in NGRAM_SIZES:
                for off in range(len(names) - n + 1):
                    key = tuple(names[off:off + n])
                    ent = census.setdefault(key, [0, 0])
                    ent[0] += 1
                    ent[1] += wgt
        runs[i] = per_block
    ranked = sorted(census.items(),
                    key=lambda kv: (kv[1][1] * (len(kv[0]) - 1),
                                    kv[1][0], kv[0]),
                    reverse=True)
    # weight > 1 keeps single occurrences inside loops (they execute
    # per iteration — prime fusion targets) while dropping one-shot
    # straight-line sequences
    ranked = [(ops, cnt, wgt) for ops, (cnt, wgt) in ranked
              if wgt > 1][:MAX_CANDIDATES]
    superinstructions = [{
        "ops": list(ops), "n": len(ops), "count": cnt, "weight": wgt,
        "saved_dispatches": (len(ops) - 1) * cnt,
    } for ops, cnt, wgt in ranked]
    cand_idx = {tuple(c["ops"]): ci
                for ci, c in enumerate(superinstructions)}

    # -- assemble per-function reports --------------------------------------
    mem_grow_sites = sum(1 for pc in range(image.code_len)
                         if image.op[pc] == _OP_MEMGROW)
    funcs: List[FuncAnalysis] = []
    total_t0 = total_drain = total_dyn = 0
    for i in defined:
        fn = image.funcs[i]
        cfg = cfgs[i]
        div = []
        ngrams: List[List[int]] = []
        sites: List[HostcallSite] = []
        for bi, b in enumerate(cfg.blocks):
            fanout = max(len(b.succ) - 1, 0)
            score = fanout + b.brtable_entries \
                + (4 if b.dynamic_call else 0)
            if b.in_loop:
                score *= 2
            div.append(score)
            names = runs[i][bi]
            present = []
            for n in NGRAM_SIZES:
                for off in range(len(names) - n + 1):
                    ci = cand_idx.get(tuple(names[off:off + n]))
                    if ci is not None and ci not in present:
                        present.append(ci)
            ngrams.append(sorted(present))
            for pc in b.pcs():
                if image.op[pc] in (_OP_CALL, _OP_RETCALL):
                    k = image.a[pc]
                    info = imports_info.get(k)
                    if info is not None:
                        t0, kind, qual = info
                        sites.append(HostcallSite(
                            pc=pc, func_idx=k, import_name=qual,
                            tier0=t0, kind=kind))
            if b.dynamic_call:
                total_dyn += 1
        total_t0 += sum(1 for s in sites if s.tier0)
        total_drain += sum(1 for s in sites if not s.tier0)
        ai = absints.get(i)
        funcs.append(FuncAnalysis(
            idx=i, name=export_of.get(i, f"func{i}"),
            entry_pc=fn.entry_pc, end_pc=fn.end_pc, cfg=cfg,
            block_costs=block_costs[i],
            has_loop=cfg.has_loop, recursive=recursive[i],
            dynamic_calls=dynamic[i],
            cost_bound=cost_bound[i],
            value_stack_bound=stack_bound[i],
            call_depth_bound=depth_bound[i],
            divergence=max(div) if div else 0,
            block_divergence=div, block_ngrams=ngrams,
            hostcall_sites=sites,
            loops=[l.asdict() for l in ai.loops] if ai else [],
            mem_facts=[m.asdict() for m in ai.mem_facts] if ai else []))

    # -- module aggregate ---------------------------------------------------
    roots = [f for f in funcs
             if not exports or f.idx in set(exports.values())]
    roots = roots or funcs
    agg_cost: Optional[int] = 0
    agg_stack: Optional[int] = 0
    agg_depth: Optional[int] = 0
    for f in roots:
        if agg_cost is not None:
            agg_cost = None if f.cost_bound is None \
                else max(agg_cost, f.cost_bound)
        if agg_stack is not None:
            agg_stack = None if f.value_stack_bound is None \
                else max(agg_stack, f.value_stack_bound)
        if agg_depth is not None:
            agg_depth = None if f.call_depth_bound is None \
                else max(agg_depth, f.call_depth_bound)
    if mem_grow_sites == 0:
        pages_bound: Optional[int] = mem_pages_init
    elif mem_pages_max > 0:
        pages_bound = mem_pages_max
    else:
        pages_bound = None  # growable with no declared ceiling

    # -- proven max page touch + fusion licenses (absint aggregate) ---------
    all_facts = [m for f in funcs for m in f.mem_facts]
    licensed_pcs = frozenset(m["pc"] for m in all_facts
                             if m.get("licensed"))
    mem_sites = [m for m in all_facts
                 if m["kind"] in ("load", "store", "vload", "vstore")]
    licensed_sites = sum(1 for m in mem_sites if m["licensed"])
    # touch bound: every access site's end is proven finite AND no
    # hostcall can write guest memory at a guest-chosen pointer AND
    # every function's absint ran (dead-code sites carry no facts and
    # never execute, so their absence is fine)
    touch: Optional[int] = None
    if has_memory and total_t0 + total_drain == 0 \
            and all(absints.get(i) is not None and absints[i].ok
                    for i in defined):
        ends = [(m["hi"] or 0) + m["nbytes"] if m["hi"] is not None
                else None for m in all_facts]
        if all(e is not None for e in ends):
            touch = max(
                max((-(-e // 65536) for e in ends), default=0), 1)

    return ModuleAnalysis(
        funcs=funcs,
        imports=[{"func": idx, "import": qual, "tier0": t0,
                  "kind": kind}
                 for idx, (t0, kind, qual) in sorted(imports_info.items())],
        superinstructions=superinstructions,
        code_len=image.code_len, n_funcs=len(image.funcs),
        exports=dict(exports),
        bounded=agg_cost is not None,
        cost_bound=agg_cost,
        value_stack_bound=agg_stack,
        call_depth_bound=agg_depth,
        divergence=max((f.divergence for f in funcs), default=0),
        mem_pages_init=mem_pages_init, mem_pages_max=mem_pages_max,
        mem_grow_sites=mem_grow_sites, mem_pages_bound=pages_bound,
        tier0_sites=total_t0, drain_sites=total_drain,
        dynamic_call_sites=total_dyn,
        mem_pages_touch_bound=touch,
        licensed_sites=licensed_sites,
        unlicensed_sites=len(mem_sites) - licensed_sites,
        licensed_pcs=licensed_pcs,
    )


def _callgraph_cycles(defined: List[int], callees: Dict[int, set]) \
        -> Dict[int, bool]:
    """func -> participates in a static call-graph cycle (counting
    cycles through callees: f is 'recursive' if anything reachable from
    it can re-enter a function on the path)."""
    # Tarjan over the call graph (iterative — no recursion-depth
    # dependence), then propagate: a function is cycle-tainted if its
    # SCC is cyclic or any callee is tainted.
    index: Dict[int, int] = {}
    low: Dict[int, int] = {}
    on: Dict[int, bool] = {}
    stack: List[int] = []
    counter = [1]
    in_cycle = {i: False for i in defined}
    dset = set(defined)

    def strong(v):
        work = [(v, iter(sorted(callees[v] & dset)))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on[v] = True
        while work:
            x, it = work[-1]
            advanced = False
            for y in it:
                if y not in index:
                    index[y] = low[y] = counter[0]
                    counter[0] += 1
                    stack.append(y)
                    on[y] = True
                    work.append((y, iter(sorted(callees[y] & dset))))
                    advanced = True
                    break
                if on.get(y):
                    low[x] = min(low[x], index[y])
            if advanced:
                continue
            work.pop()
            if low[x] == index[x]:
                scc = []
                while True:
                    y = stack.pop()
                    on[y] = False
                    scc.append(y)
                    if y == x:
                        break
                if len(scc) > 1 or x in callees[x]:
                    for y in scc:
                        in_cycle[y] = True
            if work:
                px = work[-1][0]
                low[px] = min(low[px], low[x])

    for v in defined:
        if v not in index:
            strong(v)
    # propagate taint up the call graph to a fixpoint
    changed = True
    while changed:
        changed = False
        for i in defined:
            if in_cycle[i]:
                continue
            if any(in_cycle.get(k, False) for k in callees[i] & dset):
                in_cycle[i] = True
                changed = True
    return in_cycle


def _postorder(defined: List[int], callees: Dict[int, set]) -> List[int]:
    """Callees-first order (cycles broken arbitrarily — cyclic
    functions are unbounded anyway, their order never matters)."""
    dset = set(defined)
    seen = set()
    order: List[int] = []
    for root in defined:
        if root in seen:
            continue
        work = [(root, 0)]
        local_path = set()
        while work:
            v, ei = work[-1]
            if ei == 0:
                if v in seen:
                    work.pop()
                    continue
                local_path.add(v)
            nxt = sorted(callees[v] & dset)
            if ei < len(nxt):
                work[-1] = (v, ei + 1)
                k = nxt[ei]
                if k not in seen and k not in local_path:
                    work.append((k, 0))
                continue
            work.pop()
            local_path.discard(v)
            seen.add(v)
            order.append(v)
    return order
