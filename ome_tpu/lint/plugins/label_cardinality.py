"""metrics-label-cardinality: unbounded label VALUES at
``.labels(...)`` call sites.

`metrics-naming` rejects label NAMES that imply per-request
cardinality ("request_id", "user", ...), but a well-named label fed
an unbounded value is the same explosion one hop later: every new
value mints a time series that lives for the rest of the process.
This rule checks the value side. A label value passes when it is
statically bounded:

  * a literal constant (``labels(phase="dispatch")``);
  * a module-level string constant;
  * a loop or comprehension variable ranging over a literal sequence
    of constants, a module-level tuple/list-of-strings constant, the
    keys of a module-level string-keyed dict (``.items()`` /
    ``.keys()`` / the dict itself), or a fixed enum of another
    module (``PRIORITY_CLASSES`` — the tenant-class vocabulary of
    ome_tpu/priority.py; ``SCHED_PHASES``, ``STARTUP_PHASES``,
    ``COMPILE_STAGES``, ``COMPILE_WHEN``, ``COMPILE_OUTCOMES`` — the
    fixed tuples of ome_tpu/telemetry/scopes.py).

The dict-splat spelling ``labels(**{"class": c})`` — required because
``class`` is a Python keyword — is checked key-by-key the same way;
a non-literal splat cannot be checked and is itself a finding.

Anything else (attribute loads, function calls, parameters) is
reported. Intentionally dynamic labels whose cardinality is bounded
by the deployment rather than the code — the autoscaler's
``pool=<name>`` and the router's per-backend gauges — are
grandfathered in lint-baseline.json with their justification.
"""

from __future__ import annotations

import ast
from typing import List, Set

from ..context import Context
from ..core import Finding, Project, Rule, SourceFile

# enums defined outside the checked file that are bounded by
# construction: the tenant priority classes, and the fixed tuples of
# telemetry/scopes.py: the scheduler's step phases, the start-up
# phases, and the stages, times and cache outcomes of a compile
BOUNDED_ENUM_NAMES = frozenset({
    "PRIORITY_CLASSES", "SCHED_PHASES", "STARTUP_PHASES",
    "COMPILE_STAGES", "COMPILE_WHEN", "COMPILE_OUTCOMES"})


def _is_const_seq(node: ast.AST) -> bool:
    return (isinstance(node, (ast.Tuple, ast.List))
            and all(isinstance(el, ast.Constant) for el in node.elts))


def _module_bounded_names(tree: ast.Module) -> Set[str]:
    """Module-level names bound to a string constant or to a
    tuple/list of constants."""
    out: Set[str] = set()
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            if isinstance(node.value, ast.Constant) or \
                    _is_const_seq(node.value):
                out.add(node.targets[0].id)
    return out


def _module_str_dicts(tree: ast.Module) -> Set[str]:
    """Module-level dicts whose keys are all string constants."""
    out: Set[str] = set()
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Dict)
                and node.value.keys
                and all(isinstance(k, ast.Constant)
                        and isinstance(k.value, str)
                        for k in node.value.keys)):
            out.add(node.targets[0].id)
    return out


def _bounded_loop_vars(tree: ast.Module, module_names: Set[str],
                       str_dicts: Set[str]) -> Set[str]:
    """Loop / comprehension targets that range over a statically
    bounded iterable."""
    bounded: Set[str] = set()

    def iter_is_bounded(it: ast.AST) -> bool:
        if _is_const_seq(it):
            return True
        if isinstance(it, ast.Name):
            return (it.id in module_names or it.id in str_dicts
                    or it.id in BOUNDED_ENUM_NAMES)
        if (isinstance(it, ast.Call)
                and isinstance(it.func, ast.Attribute)
                and it.func.attr in ("items", "keys")
                and isinstance(it.func.value, ast.Name)
                and it.func.value.id in str_dicts):
            return True
        return False

    def note(target: ast.AST, it: ast.AST):
        if not iter_is_bounded(it):
            return
        # for `D.items()` only the KEY element is bounded
        if (isinstance(it, ast.Call)
                and isinstance(it.func, ast.Attribute)
                and it.func.attr == "items"
                and isinstance(target, ast.Tuple) and target.elts):
            target = target.elts[0]
        if isinstance(target, ast.Name):
            bounded.add(target.id)

    for node in ast.walk(tree):
        if isinstance(node, ast.For):
            note(node.target, node.iter)
        elif isinstance(node, ast.comprehension):
            note(node.target, node.iter)
    return bounded


class MetricsLabelCardinalityRule(Rule):
    name = "metrics-label-cardinality"
    description = ("label values at .labels() call sites must come "
                   "from a statically bounded set (literal, module "
                   "constant, or fixed enum like the priority "
                   "classes)")

    def __init__(self):
        self.site_count = 0

    def run(self, project: Project, ctx: Context = None
            ) -> List[Finding]:
        findings: List[Finding] = []
        self.site_count = 0
        for sf in project.files:
            if "telemetry" in sf.rel.split("/") and \
                    sf.path.name == "registry.py":
                continue  # the labels() implementation itself
            module_names = _module_bounded_names(sf.tree)
            str_dicts = _module_str_dicts(sf.tree)
            bounded = module_names | _bounded_loop_vars(
                sf.tree, module_names, str_dicts)
            for node in ast.walk(sf.tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "labels"
                        and node.keywords):
                    self.site_count += 1
                    self._check_call(node, bounded, sf, findings)
        findings.sort(key=lambda f: (f.path, f.line))
        return findings

    def _value_ok(self, node: ast.AST, bounded: Set[str]) -> bool:
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            return node.id in bounded
        return False

    def _check_call(self, call: ast.Call, bounded: Set[str],
                    sf: SourceFile, out: List[Finding]):
        for kw in call.keywords:
            if kw.arg is None:  # **splat
                if not (isinstance(kw.value, ast.Dict)
                        and all(isinstance(k, ast.Constant)
                                for k in kw.value.keys)):
                    out.append(self.finding(
                        sf, call.lineno,
                        "labels(**...) with a non-literal dict: "
                        "label values cannot be checked for bounded "
                        "cardinality"))
                    continue
                for k, v in zip(kw.value.keys, kw.value.values):
                    if not self._value_ok(v, bounded):
                        out.append(self.finding(
                            sf, call.lineno,
                            f"label {k.value!r} value is not "
                            "statically bounded; label values must "
                            "come from a fixed enum (literal, module "
                            "constant, or the priority-class enum)"))
            elif not self._value_ok(kw.value, bounded):
                out.append(self.finding(
                    sf, call.lineno,
                    f"label {kw.arg!r} value is not statically "
                    "bounded; label values must come from a fixed "
                    "enum (literal, module constant, or the "
                    "priority-class enum)"))
