#!/usr/bin/env python3
"""Static lint: no host-side nondeterminism inside jitted chunk bodies.

The jitted regions (the SIMT/uniform step builders and chunk loops, the
recycler's column-install, the block scheduler's surgery programs)
trace ONCE and replay: a `time.time()`,
`np.random.*`, or `print()` inside them either burns into the trace as
a compile-time constant (silent nondeterminism between compiles — the
bit-identical-output contracts would break run-to-run) or fires on
every retrace instead of every step (misleading side effects).  Those
calls belong on the host side of the launch boundary, where
t0_time_planes / the seeded PRNG planes / the flight recorder already
provide the sanctioned equivalents.

AST-based: every function/lambda nested inside a known jit-region
builder is scanned for calls whose dotted name matches the forbidden
list.  Wired into the tier-1 suite (tests/test_analysis.py) so a hit
fails CI, and runnable standalone:

    python tools/lint_jit_purity.py [repo_root]
"""

from __future__ import annotations

import ast
import os
import sys
from typing import List, Tuple

# file (repo-relative) -> top-level defs whose entire bodies are jit
# regions (the builders return traced callables; everything nested in
# them runs under trace)
TARGETS = {
    "wasmedge_tpu/batch/engine.py": ("_make_step", "_build",
                                     "_build_narrow_chunk"),
    "wasmedge_tpu/batch/uniform.py": ("make_uniform_step",
                                      "_build_uniform"),
    "wasmedge_tpu/serve/recycle.py": ("_install_fn",),
    # superinstruction fused-step builders: the specialized pattern
    # handlers trace inside make_fused_apply and — for the r19
    # absint-licensed memory runs — make_memfuse_apply (batch/fuse.py);
    # the missing-target guard below means a rename cannot silently
    # shrink this coverage
    "wasmedge_tpu/batch/fuse.py": ("make_fused_apply",
                                   "make_memfuse_apply"),
    # whole-function tier-up (r20): the compiled-body builder the step
    # merges in — lane-masked CFG bodies under bounded lax.while_loop
    "wasmedge_tpu/batch/tierup.py": ("make_tierup_apply",),
    # single-program mesh drive: the sharded jit wrapper around the
    # engine's chunk body (the body itself is covered by engine.py's
    # targets; this keeps the mesh-side wrapper honest too)
    "wasmedge_tpu/parallel/shard_drive.py": ("_build_shard_chunk",),
    # lane compaction (batch/compact.py): the jitted gather-permutation
    # builder; the narrowed chunk variant traces inside the engine's
    # _build_narrow_chunk, covered alongside the main builders
    "wasmedge_tpu/batch/compact.py": ("make_permute",),
    # block surgery (batch/scheduler.py): the column gather out of the
    # planes and the donated column set into a free slot
    "wasmedge_tpu/batch/scheduler.py": ("_surgery_fns",),
}

# Dotted-call prefixes that are host-side nondeterminism (or host
# I/O).  A trailing "." means "anything in this namespace"; otherwise
# suffix variants also match (time.time catches time.time_ns).
FORBIDDEN_PREFIXES = (
    "time.time", "time.monotonic", "time.perf_counter",
    "time.process_time",
    "np.random.", "numpy.random.", "jax.random.",  # use the PRNG planes
    "random.",
    "os.urandom", "secrets.",
)
FORBIDDEN_NAMES = {"print", "input", "open"}


def _forbidden(name: str) -> bool:
    if name in FORBIDDEN_NAMES:
        return True
    for p in FORBIDDEN_PREFIXES:
        if p.endswith("."):
            if name.startswith(p) or name == p[:-1]:
                return True
        elif name == p or name.startswith(p):
            return True
    return False


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _scan_region(fn: ast.AST, path: str) -> List[Tuple[str, int, str]]:
    out = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name and _forbidden(name):
            out.append((path, node.lineno, name))
    return out


def run_lint(root: str = ".") -> List[Tuple[str, int, str]]:
    """All violations as (file, line, call) triples; empty = clean."""
    violations: List[Tuple[str, int, str]] = []
    for rel, region_names in sorted(TARGETS.items()):
        path = os.path.join(root, rel)
        with open(path, "r") as f:
            tree = ast.parse(f.read(), filename=path)
        found = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in region_names:
                found.add(node.name)
                violations.extend(_scan_region(node, rel))
        missing = set(region_names) - found
        if missing:
            # a renamed/removed jit builder must update this table, not
            # silently shrink the lint's coverage
            violations.append((rel, 0,
                               f"lint target(s) not found: "
                               f"{sorted(missing)}"))
    return violations


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else os.path.join(os.path.dirname(__file__),
                                             "..")
    violations = run_lint(root)
    for path, line, what in violations:
        sys.stderr.write(f"{path}:{line}: forbidden in jit region: "
                         f"{what}\n")
    if violations:
        sys.stderr.write(f"lint_jit_purity: {len(violations)} "
                         f"violation(s)\n")
        return 1
    print("lint_jit_purity: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
