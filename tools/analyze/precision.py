"""precision pass: f32/FMA sign-safety invariants (rules FP001/FP002).

PR 3's fuzz-found regression: XLA contracts mul+add chains into FMAs below
the HLO level, so a near-zero orientation sign computed on device can
disagree with strict-IEEE numpy — and ``optimization_barrier`` cannot stop
it.  The repo-wide idiom is a *guard band*: every device sign test carries
an eps/tol band and borderline pairs escalate to the host oracle
(``spatial/refine.py``, ``kernels/refine``).  This pass flags device sign
tests that skip the idiom:

* **FP001** — in a jnp-using function, a sign comparison (``> 0`` /
  ``< 0`` / ``>= 0`` / ``<= 0``) of an orientation-style value (a local
  assigned from the cross-product idiom ``a*b - c*d``, directly or through
  a local helper returning one) in a function with no guard-band
  machinery (no ``eps`` / ``tol`` / ``guard`` / ``unc`` name in scope).
* **FP002** — ``jax.config.update("jax_enable_x64", ...)`` in library
  code: a process-global precision flip reachable from f32 paths (the
  pallas kernels run f32 by contract).  Use the scoped
  ``with jax.enable_x64(True):`` context manager instead.
"""
from __future__ import annotations

import ast
from pathlib import Path

from .core import (AnalysisPass, Finding, SourceFile, assigned_names,
                   call_name, iter_functions)

_GUARD_HINTS = ("eps", "tol", "guard", "unc", "borderline")


def _is_mul_sub(node: ast.AST) -> bool:
    """The cross-product / orientation idiom: ``<mult> - <mult>``."""
    return (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
            and isinstance(node.left, ast.BinOp)
            and isinstance(node.left.op, ast.Mult)
            and isinstance(node.right, ast.BinOp)
            and isinstance(node.right.op, ast.Mult))


def _uses_jnp(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and node.id == "jnp":
            return True
    return False


def _has_guard(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        name = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        elif isinstance(node, ast.arg):
            name = node.arg
        if name and any(h in name.lower() for h in _GUARD_HINTS):
            return True
    return False


def _orientation_names(fn: ast.AST) -> set[str]:
    """Locals assigned from a mul-sub expression, or from a call to a
    local helper whose body returns a mul-sub (the ``orient()`` idiom)."""
    helpers: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.FunctionDef):
            for stmt in ast.walk(node):
                if isinstance(stmt, ast.Return) and stmt.value is not None \
                        and _is_mul_sub(stmt.value):
                    helpers.add(node.name)
    names: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            v = node.value
            if _is_mul_sub(v) or (isinstance(v, ast.Call)
                                  and call_name(v) in helpers):
                for t in node.targets:
                    names.update(assigned_names(t))
    return names


class PrecisionPass(AnalysisPass):
    name = "precision"
    rules = {
        "FP001": "device sign test on an orientation value without the "
                 "guard-band idiom (FMA contraction can flip near-zero "
                 "signs vs strict IEEE)",
        "FP002": "process-global jax_enable_x64 flip in library code; use "
                 "the scoped jax.enable_x64(True) context manager",
    }

    _SCOPE = ("src/repro/spatial/", "src/repro/core/", "src/repro/kernels/")

    def scope(self, path: str) -> bool:
        return path.startswith(self._SCOPE)

    def run(self, files: list[SourceFile], root: Path) -> list[Finding]:
        out: list[Finding] = []
        for src in files:
            out.extend(self._fp001(src))
            out.extend(self._fp002(src))
        return out

    def _fp001(self, src: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        for fn in iter_functions(src.tree):
            if not _uses_jnp(fn) or _has_guard(fn):
                continue
            orient = _orientation_names(fn)
            if not orient:
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Compare)
                        and len(node.ops) == 1
                        and isinstance(node.ops[0],
                                       (ast.Gt, ast.Lt, ast.GtE, ast.LtE))):
                    continue
                left, right = node.left, node.comparators[0]
                zero_cmp = (isinstance(right, ast.Constant)
                            and right.value == 0)
                if zero_cmp and isinstance(left, ast.Name) \
                        and left.id in orient:
                    out.append(src.finding(
                        "FP001", node,
                        f"sign test on orientation value `{left.id}` with "
                        f"no guard band in `{fn.name}`: FMA contraction "
                        f"can flip near-zero signs; use the eps-band + "
                        f"host-escalation idiom (spatial/refine.py)"))
        return out

    def _fp002(self, src: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if not name.endswith("config.update"):
                continue
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and node.args[0].value == "jax_enable_x64":
                out.append(src.finding(
                    "FP002", node,
                    "process-global jax_enable_x64 update in library code "
                    "changes precision for every caller (including f32 "
                    "pallas paths); scope it with "
                    "`with jax.enable_x64(True):`"))
        return out
