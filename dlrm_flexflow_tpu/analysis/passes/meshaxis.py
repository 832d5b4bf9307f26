"""mesh-axis pass: axis names and shard_map spellings stay disciplined.

Mesh axes are stringly-typed: ``jax.lax.all_gather(x, "modell")``
parses, traces, and only dies (or silently degrades) when the axis is
looked up at lowering — and on a pod that failure costs a full-fleet
launch.  This tree's convention (parallel/mesh.py, docs/distributed.md)
makes the discipline checkable:

* every collective's axis name inside a ``shard_map`` body must be an
  axis the SITE declares — spelled in its ``in_specs``/``out_specs``
  ``P(...)`` entries or a statically-visible mesh shape
  (``_spmd.get_shard_map_sites`` resolves string literals and the
  ``DATA_AXIS``/``MODEL_AXIS`` module constants; wholly dynamic specs
  resolve to nothing and the site is skipped — silence over guessing);
* a device collective OUTSIDE every shard_map body and jit entry has
  no axis environment at all — it raises ``NameError: unbound axis``
  at trace time in the best case, and in the worst it sits in code a
  refactor is about to move onto a hot path;
* ``jax.shard_map`` / ``jax.experimental.shard_map`` must not be
  spelled outside ``parallel/mesh.py``: its one-line wrapper is the
  single place this tree names jax's shard_map surface (whose spelling
  has changed between releases — ``check_rep`` became ``check_vma``,
  the experimental module is deprecated), and the site resolver of
  this pass reads the wrapper's call sites.

Codes: ``undeclared-axis``, ``collective-outside-spmd``,
``direct-shard-map``.
"""

from __future__ import annotations

import ast
from typing import List, Set

from ..engine import (AnalysisPass, Finding, FunctionIndex, Module,
                      get_callgraph, iter_calls)
from ._entries import all_jit_entries
from ._spmd import (AXIS_USERS, DEVICE_COLLECTIVES, call_name,
                    get_shard_map_sites, get_spmd_contexts,
                    get_str_consts, resolve_str)

#: the one module allowed to touch jax's shard_map surface directly.
WRAPPER_MODULE = "dlrm_flexflow_tpu/parallel/mesh.py"


def _axis_names_used(call: ast.Call, name: str, module: Module, per,
                     uniq) -> Set[str]:
    """Axis names an axis-consuming call references: string (or
    resolvable-name) arguments and ``axis_name=`` keywords, tuples
    included.  Non-axis arguments (ints, arrays) resolve to nothing;
    the operand slot (``args[0]`` of every collective except
    ``axis_index``, whose only argument IS the axis) is skipped so a
    data variable sharing a name with some project string constant
    cannot masquerade as an axis."""
    out: Set[str] = set()
    pos = list(call.args) if name == "axis_index" else list(call.args[1:])
    exprs = pos + [k.value for k in call.keywords
                   if k.arg in (None, "axis_name")]
    for arg in exprs:
        parts = (arg.elts if isinstance(arg, (ast.Tuple, ast.List))
                 else [arg])
        for p in parts:
            s = resolve_str(p, module, per, uniq)
            if s is not None:
                out.add(s)
    return out


class MeshAxisPass(AnalysisPass):
    name = "mesh-axis"
    description = ("shard_map bodies only use axes their site "
                   "declares; no collectives outside SPMD contexts; "
                   "jax.shard_map only through the parallel/mesh.py "
                   "wrapper")

    def run(self, modules: List[Module],
            index: FunctionIndex) -> List[Finding]:
        findings: List[Finding] = []
        findings.extend(self._direct_spellings(modules, index))
        findings.extend(self._axis_discipline(modules, index))
        findings.extend(self._outside_spmd(modules, index))
        findings.sort(key=lambda f: (f.path, f.line, f.code))
        return findings

    # -------------------------------------------------- direct shard_map
    def _direct_spellings(self, modules: List[Module],
                          index: FunctionIndex) -> List[Finding]:
        out: List[Finding] = []
        for m in modules:
            if m.relpath == WRAPPER_MODULE:
                continue

            def flag(line: int, what: str, detail: str, _m=m,
                     _out=out):
                _out.append(self.finding(
                    _m.relpath, line, "direct-shard-map",
                    f"{what} outside parallel/mesh.py — only its "
                    f"wrapper may touch jax's shard_map surface (one "
                    f"spelling to keep current; docs/distributed.md)",
                    detail=detail))

            for node in ast.walk(m.tree):
                if isinstance(node, ast.ImportFrom):
                    src = node.module or ""
                    if src.startswith("jax.experimental.shard_map") or (
                            src in ("jax", "jax.experimental")
                            and any(a.name == "shard_map"
                                    for a in node.names)):
                        flag(node.lineno,
                             f"direct import from {src or 'jax'}",
                             "<module>")
                elif isinstance(node, ast.Import):
                    for a in node.names:
                        if a.name.startswith(
                                "jax.experimental.shard_map"):
                            flag(node.lineno,
                                 f"direct import of {a.name}",
                                 "<module>")
                elif isinstance(node, ast.Attribute) \
                        and node.attr == "shard_map" \
                        and not (isinstance(node.value, ast.Attribute)
                                 and node.value.attr == "shard_map"):
                    # jax.experimental.shard_map.shard_map nests two
                    # matching Attributes — only the INNER one (whose
                    # value is not itself a shard_map attribute)
                    # reports, one finding per expression
                    chain = self._attr_chain(node)
                    if chain and chain[0] == "jax":
                        owner = self._owner_qual(node, m, index)
                        flag(node.lineno,
                             f"direct {'.'.join(chain)}.shard_map use",
                             owner)
        return out

    @staticmethod
    def _attr_chain(node: ast.Attribute) -> List[str]:
        parts: List[str] = []
        cur: ast.AST = node.value
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if isinstance(cur, ast.Name):
            parts.append(cur.id)
            return list(reversed(parts))
        return []

    @staticmethod
    def _owner_qual(node: ast.AST, module: Module,
                    index: FunctionIndex) -> str:
        """The qualname of the innermost function containing ``node``
        (for a stable waiver key), or ``<module>``."""
        best, best_qual = None, "<module>"
        for fn, (mod, qual, _cls, _scope) in index.owner.items():
            if mod is not module:
                continue
            if any(n is node for n in ast.walk(fn)):
                if best is None or any(n is fn for n in ast.walk(best)):
                    best, best_qual = fn, qual
        return best_qual

    # ------------------------------------------------- axis declaration
    def _axis_discipline(self, modules: List[Module],
                         index: FunctionIndex) -> List[Finding]:
        per, uniq = get_str_consts(modules, index)
        contexts = get_spmd_contexts(modules, index)
        out: List[Finding] = []
        for fn, sites in contexts.items():
            if any(not s.axes_known for s in sites):
                # some reaching site declares nothing statically —
                # every axis might be legal there; stay silent
                continue
            declared: Set[str] = set()
            for s in sites:
                declared |= s.declared_axes
            mod, qual, _cls, _scope = index.owner[fn]
            site_note = ", ".join(sorted(
                f"{s.module.relpath}:{s.call.lineno}" for s in sites))
            for call in iter_calls(fn):
                nm = call_name(call)
                if nm not in AXIS_USERS:
                    continue
                for axis in sorted(
                        _axis_names_used(call, nm, mod, per, uniq)):
                    if axis not in declared:
                        out.append(self.finding(
                            mod.relpath, call.lineno, "undeclared-axis",
                            f"{nm}() uses axis {axis!r} inside a "
                            f"shard_map body, but the site(s) at "
                            f"{site_note} only declare "
                            f"{sorted(declared)} — an unbound (or "
                            f"misspelled) axis dies at lowering, on "
                            f"the full fleet", detail=qual))
        return out

    # ------------------------------------------------ outside-SPMD check
    def _outside_spmd(self, modules: List[Module],
                      index: FunctionIndex) -> List[Finding]:
        contexts = get_spmd_contexts(modules, index)
        cg = get_callgraph(modules, index)
        jit_reach = cg.reachable(all_jit_entries(modules, index),
                                 follow_nested=True)
        # shard_map bodies that did not resolve still mark their
        # lexical parents as SPMD-adjacent: a site whose body we could
        # not resolve must not convict its neighbors
        unresolved_parents: Set[ast.AST] = set()
        for site in get_shard_map_sites(modules, index):
            if site.body is None:
                for fn, (mod, _q, _c, _s) in index.owner.items():
                    if mod is site.module \
                            and any(n is site.call for n in
                                    ast.walk(fn)):
                        unresolved_parents.add(fn)
                        unresolved_parents.update(
                            cg.reachable({fn: "site"}))
        out: List[Finding] = []
        for fn, (mod, qual, _cls, _scope) in index.owner.items():
            if fn in contexts or fn in jit_reach \
                    or fn in unresolved_parents:
                continue
            for call in iter_calls(fn):
                nm = call_name(call)
                if nm not in DEVICE_COLLECTIVES:
                    continue
                # only flag spellings that are really jax.lax ops: a
                # bare name this project defines resolves elsewhere
                fnc = call.func
                if isinstance(fnc, ast.Name) and index.resolve_name(
                        mod, _scope + (qual.split(".")[-1],), fnc.id):
                    continue
                out.append(self.finding(
                    mod.relpath, call.lineno, "collective-outside-spmd",
                    f"{nm}() in {qual}, which no shard_map body or "
                    f"jit entry reaches — there is no axis "
                    f"environment here; the call raises at trace "
                    f"time (or this code is about to be moved "
                    f"somewhere it will)", detail=qual))
        return out
