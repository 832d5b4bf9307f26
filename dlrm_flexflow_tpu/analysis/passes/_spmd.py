"""SPMD-context discovery shared by the multi-host passes.

``collective-divergence``, ``mesh-axis``, and ``barrier-protocol``
agree on what the SPMD surface of this tree looks like:

* a **shard_map site** is any call named ``shard_map`` — the
  ``parallel/mesh.py`` wrapper is the only sanctioned spelling
  (docs/distributed.md), and sites thread their body as a bare name,
  an inline ``functools.partial(f, ...)``, or the local
  ``f = functools.partial(...)`` binding (the same three idioms
  ``_entries.py`` resolves for pallas kernels);
* a function "runs inside a shard_map body" when the engine's
  :class:`~..engine.CallGraph` closure reaches it from any site's
  resolved body — that relation (and the per-site declared-axis sets)
  is computed once and cached on the index like ``get_callgraph``;
* a **collective** is a ``jax.lax`` device collective
  (:data:`DEVICE_COLLECTIVES`), a ``multihost_utils`` process barrier
  (:data:`MULTIHOST_BARRIERS`), or an entry into the podshard
  file-barrier protocol (a function that *mints a fence directory* —
  recognized structurally from the ``.barrier-`` path constant feeding
  its ``os.makedirs``, not by name, so a renamed helper cannot dodge
  the passes).

Axis names are resolved like the tree spells them: string literals,
or names bound to module-level string constants (``MODEL_AXIS =
"model"`` in ``parallel/mesh.py``, re-imported everywhere) — a name
resolves in its own module first, then against the project-wide
constant map when exactly one module defines it.  Anything dynamic
(a ``spec`` variable, an ``axis_name=`` parameter) resolves to
nothing, and the consuming passes stay silent rather than guess
(docs/analysis.md's standing under-approximation rule).
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..engine import FunctionIndex, Module, get_callgraph, iter_calls
from ._entries import _partial_arg, _partial_binding

#: jax.lax device collectives — the ops that hang the step when the
#: participating processes disagree about reaching them.
DEVICE_COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "psum_scatter", "ppermute", "pshuffle", "pbroadcast"})

#: axis-name consumers that are not themselves communication (an
#: ``axis_index`` over an undeclared axis is the same spelling bug).
AXIS_USERS = DEVICE_COLLECTIVES | frozenset({"axis_index"})

#: jax.experimental.multihost_utils process-level barriers.
MULTIHOST_BARRIERS = frozenset({
    "sync_global_devices", "broadcast_one_to_all", "process_allgather"})

#: the filesystem marker every podshard commit fence lives under
#: (resilience/manager.py, docs/distributed.md).
FENCE_MARK = ".barrier"

#: parameter names that carry a process index by convention
#: (resilience/manager.py threads ``pidx`` through the protocol).
DIVERGENT_PARAMS = frozenset({"pidx", "process_index", "process_id"})


def own_statements(fn_node: ast.AST):
    """Descendants of this function excluding nested function/class
    bodies — the shared walk the SPMD passes agree on."""
    stack = [fn_node]
    while stack:
        n = stack.pop()
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            yield child
            stack.append(child)


def process_local_names(fn_node: ast.AST, expr_local) -> Set[str]:
    """THE one seeding rule for "this name holds a process-local
    value", shared by collective-divergence and barrier-protocol so
    the two passes cannot drift: conventional parameter names
    (:data:`DIVERGENT_PARAMS`) plus assignment targets whose source
    ``expr_local(expr, names)`` deems process-local.  A tuple assign
    with MATCHING arity taints elementwise — ``pidx, nproc =
    process_index(), process_count()`` taints ``pidx`` only, never
    the uniform ``nproc`` riding in the same statement; arity-opaque
    sources (a call returning a tuple) taint every target
    (conservative).  The assignment scan runs to a FIXED POINT over
    source-ordered statements — the tree walk yields nested-block
    statements out of source order, and alias chains (``rank = pidx``
    two hops from the ``process_index()`` assignment) must converge
    regardless of where each link sits."""
    names: Set[str] = set()
    args = getattr(fn_node, "args", None)
    if args is not None:
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)):
            if a.arg in DIVERGENT_PARAMS:
                names.add(a.arg)
    assigns = sorted(
        (st for st in own_statements(fn_node)
         if isinstance(st, ast.Assign)),
        key=lambda st: (st.lineno, st.col_offset))
    while True:
        before = len(names)
        for stmt in assigns:
            for t in stmt.targets:
                if isinstance(t, (ast.Tuple, ast.List)) \
                        and isinstance(stmt.value, (ast.Tuple,
                                                    ast.List)) \
                        and len(t.elts) == len(stmt.value.elts):
                    for el, src in zip(t.elts, stmt.value.elts):
                        if isinstance(el, ast.Name) \
                                and expr_local(src, names):
                            names.add(el.id)
                    continue
                els = (t.elts if isinstance(t, (ast.Tuple, ast.List))
                       else [t])
                if expr_local(stmt.value, names):
                    for el in els:
                        if isinstance(el, ast.Name):
                            names.add(el.id)
        if len(names) == before:
            return names


# ------------------------------------------------------- string constants
def get_str_consts(modules: List[Module], index: FunctionIndex
                   ) -> Tuple[Dict[Tuple[str, str], str], Dict[str, str]]:
    """(per-module, project-unique) maps of module-level ``NAME =
    "literal"`` string constants — how ``DATA_AXIS``/``MODEL_AXIS``
    (and ``MANIFEST``/``EXTRA``) resolve at their use sites.  Cached
    on the index; the project-wide map only keeps names every defining
    module agrees on (ambiguity -> absent, never a guess)."""
    cached = getattr(index, "_str_consts_cache", None)
    if cached is not None:
        return cached
    per: Dict[Tuple[str, str], str] = {}
    values: Dict[str, Set[str]] = {}
    for m in modules:
        for stmt in m.tree.body:
            tgts: List[ast.expr] = []
            value = None
            if isinstance(stmt, ast.Assign):
                tgts, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                tgts, value = [stmt.target], stmt.value
            if not isinstance(value, ast.Constant) \
                    or not isinstance(value.value, str):
                continue
            for t in tgts:
                if isinstance(t, ast.Name):
                    per[(m.name, t.id)] = value.value
                    values.setdefault(t.id, set()).add(value.value)
    uniq = {n: next(iter(vs)) for n, vs in values.items() if len(vs) == 1}
    index._str_consts_cache = (per, uniq)
    return per, uniq


def resolve_str(expr: ast.AST, module: Module,
                per: Dict[Tuple[str, str], str],
                uniq: Dict[str, str]) -> Optional[str]:
    """A string literal, or a Name bound to one (own module first,
    then the project-unique map); None for anything dynamic."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    if isinstance(expr, ast.Name):
        own = per.get((module.name, expr.id))
        if own is not None:
            return own
        return uniq.get(expr.id)
    return None


# ---------------------------------------------------------- shard_map sites
class ShardMapSite:
    """One resolved ``shard_map(body, mesh=..., in_specs=...,
    out_specs=...)`` call: where it is, which function is the body,
    and which mesh axes its specs/mesh declare.  ``axes_known`` is
    False when no spec component resolved statically — the mesh-axis
    pass skips such sites (silence over guessing)."""

    __slots__ = ("module", "call", "owner_qual", "body",
                 "declared_axes", "axes_known")

    def __init__(self, module: Module, call: ast.Call, owner_qual: str,
                 body: Optional[ast.AST], declared_axes: Set[str],
                 axes_known: bool):
        self.module = module
        self.call = call
        self.owner_qual = owner_qual
        self.body = body
        self.declared_axes = declared_axes
        self.axes_known = axes_known

    def __repr__(self):
        return (f"ShardMapSite({self.module.relpath}:{self.call.lineno}"
                f" axes={sorted(self.declared_axes)})")


def _is_shard_map_call(call: ast.Call) -> bool:
    fn = call.func
    return (isinstance(fn, ast.Name) and fn.id == "shard_map") or \
        (isinstance(fn, ast.Attribute) and fn.attr == "shard_map")


def _spec_axes(expr: Optional[ast.AST], module: Module,
               per: Dict[Tuple[str, str], str],
               uniq: Dict[str, str]) -> Tuple[Set[str], bool, bool]:
    """Axis names declared by one ``in_specs``/``out_specs``/``mesh``
    expression: every ``P(...)``/``PartitionSpec(...)`` argument that
    resolves to a string (tuples of axes included), plus the keys of
    an inline mesh-shape dict.  ``known`` is True only when the
    declaration is CLOSED: at least one ``P`` resolved and no ``P``
    argument stayed dynamic — ``P(axis)`` through a variable could
    declare anything, so such a site must be skipped, not convicted
    against a partial set."""
    axes: Set[str] = set()
    saw_p = False
    open_decl = False
    if expr is None:
        return axes, False, False
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if name in ("P", "PartitionSpec"):
                saw_p = True
                for arg in node.args:
                    parts = (arg.elts if isinstance(arg, (ast.Tuple,
                                                          ast.List))
                             else [arg])
                    for p in parts:
                        if isinstance(p, ast.Constant) \
                                and p.value is None:
                            continue  # replicated dim
                        s = resolve_str(p, module, per, uniq)
                        if s is not None:
                            axes.add(s)
                        else:
                            open_decl = True
        elif isinstance(node, ast.Dict):
            # inline mesh shape: make_mesh({"data": 2, "model": 2})
            for k in node.keys:
                s = resolve_str(k, module, per, uniq) if k is not None \
                    else None
                if s is not None:
                    saw_p = True
                    axes.add(s)
    return axes, saw_p, open_decl


def get_shard_map_sites(modules: List[Module],
                        index: FunctionIndex) -> List[ShardMapSite]:
    """Every ``shard_map(...)`` call in the project with its body and
    declared axes resolved; one walk, cached on the index."""
    cached = getattr(index, "_shard_map_sites_cache", None)
    if cached is not None:
        return list(cached)
    per, uniq = get_str_consts(modules, index)
    sites: List[ShardMapSite] = []

    def scan(calls: Iterable[ast.Call], module: Module,
             scope: Tuple[str, ...], encl: ast.AST, qual: str) -> None:
        for call in calls:
            if not _is_shard_map_call(call):
                continue
            body: Optional[ast.AST] = None
            if call.args:
                first = call.args[0]
                if isinstance(first, ast.Name):
                    # nearest PRECEDING same-named def in the enclosing
                    # function first: two branches defining their own
                    # ``def body`` (table_exchange's allgather vs
                    # all_to_all arms) collide in the scoped index
                    # (last def wins there), but each call site means
                    # the binding lexically above it
                    preceding = [
                        d for d in ast.walk(encl)
                        if isinstance(d, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))
                        and d.name == first.id
                        and d.lineno < call.lineno]
                    if preceding:
                        body = max(preceding, key=lambda d: d.lineno)
                    if body is None:
                        body = index.resolve_name(module, scope,
                                                  first.id)
                    if body is None:
                        body = _partial_binding(encl, module, index,
                                                scope, first.id)
                elif isinstance(first, ast.Call):
                    body = _partial_arg(first, module, index, scope)
            kw = {k.arg: k.value for k in call.keywords
                  if k.arg is not None}
            # the wrapper's positional order: (f, mesh, in_specs,
            # out_specs) — keyword spellings win when present
            pos = list(call.args[1:4]) + [None] * 3
            spec_exprs = (kw.get("in_specs", pos[1]),
                          kw.get("out_specs", pos[2]),
                          kw.get("mesh", pos[0]))
            axes: Set[str] = set()
            saw = opened = False
            for e in spec_exprs:
                a, s_, o_ = _spec_axes(e, module, per, uniq)
                axes |= a
                saw = saw or s_
                opened = opened or o_
            # an empty CLOSED set means every spec was replicated
            # P() and the mesh stayed dynamic — the mesh could declare
            # anything, so such a site is open (skipped), like a
            # dynamic P(axis): silence over guessing
            sites.append(ShardMapSite(
                module, call, qual, body, axes,
                saw and not opened and bool(axes)))

    for node, (mod, qual, _cls, def_scope) in index.owner.items():
        scope = def_scope + (qual.split(".")[-1],)
        scan(iter_calls(node), mod, scope, node, qual)
    for m in modules:
        scan(iter_calls(m.tree), m, (), m.tree, "<module>")
    index._shard_map_sites_cache = sites
    return list(sites)


def get_spmd_contexts(modules: List[Module], index: FunctionIndex
                      ) -> Dict[ast.AST, List[ShardMapSite]]:
    """THE SPMD-context relation: function node -> the shard_map sites
    whose bodies (transitively, via the engine's CallGraph closure)
    run it.  A function absent from the map never executes inside a
    shard_map body as far as the resolver can see.  Cached on the
    index — three passes share one closure walk."""
    cached = getattr(index, "_spmd_contexts_cache", None)
    if cached is not None:
        return {k: list(v) for k, v in cached.items()}
    cg = get_callgraph(modules, index)
    contexts: Dict[ast.AST, List[ShardMapSite]] = {}
    for site in get_shard_map_sites(modules, index):
        if site.body is None or site.body not in index.owner:
            continue
        note = (f"shard_map at {site.module.relpath}:"
                f"{site.call.lineno}")
        for fn in cg.reachable({site.body: note}, follow_nested=True):
            contexts.setdefault(fn, []).append(site)
    index._spmd_contexts_cache = contexts
    return {k: list(v) for k, v in contexts.items()}


# ------------------------------------------------------------- collectives
def call_name(call: ast.Call) -> Optional[str]:
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def iter_collective_calls(fn_node: ast.AST, *, axis_users: bool = False):
    """Direct device-collective (and multihost-barrier) calls in this
    function's own body; ``axis_users`` widens to every axis-name
    consumer (``axis_index``)."""
    names = AXIS_USERS if axis_users else DEVICE_COLLECTIVES
    for call in iter_calls(fn_node):
        nm = call_name(call)
        if nm in names or nm in MULTIHOST_BARRIERS:
            yield call, nm


def _mentions_fence(expr: ast.AST) -> bool:
    """A ``.barrier`` path constant anywhere inside ``expr`` (plain
    string or f-string piece)."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and FENCE_MARK in node.value:
            return True
    return False


def _fence_names(fn_node: ast.AST) -> Set[str]:
    """Local names assigned from expressions mentioning the fence
    marker (``bdir = os.path.join(dir, f".barrier-{tag}")``)."""
    out: Set[str] = set()
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign) and _mentions_fence(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def fence_creations(fn_node: ast.AST) -> List[ast.Call]:
    """``os.makedirs``/``os.mkdir`` calls whose target path derives
    from a ``.barrier`` constant — the act of minting a commit fence.
    Structural, not name-based: renaming ``_barrier`` cannot dodge
    the barrier-protocol pass."""
    fences = _fence_names(fn_node)
    out: List[ast.Call] = []
    for call in iter_calls(fn_node):
        if call_name(call) not in ("makedirs", "mkdir"):
            continue
        for arg in call.args:
            if _mentions_fence(arg) or (isinstance(arg, ast.Name)
                                        and arg.id in fences):
                out.append(call)
                break
    return out


def sweeps_fences(fn_node: ast.AST) -> bool:
    """Whether this function removes fence directories: an
    ``rmtree``/``rmdir`` call in a function that also spells the
    fence marker (the gc sweep's ``name.startswith(".barrier-")``
    gate, or a direct ``rmtree(join(dir, ".barrier-..."))``)."""
    has_rm = any(call_name(c) in ("rmtree", "rmdir")
                 for c in iter_calls(fn_node))
    return has_rm and _mentions_fence(fn_node)


def get_fence_creators(modules: List[Module], index: FunctionIndex
                       ) -> Dict[ast.AST, ast.Call]:
    """fn node -> its first fence-minting call; cached on the index
    (the divergence pass counts these as collectives, the barrier
    pass audits their lifecycle)."""
    cached = getattr(index, "_fence_creators_cache", None)
    if cached is not None:
        return dict(cached)
    out: Dict[ast.AST, ast.Call] = {}
    for node in index.owner:
        created = fence_creations(node)
        if created:
            out[node] = created[0]
    index._fence_creators_cache = out
    return dict(out)
