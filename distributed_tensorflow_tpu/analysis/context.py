"""Jit/scope context for dtlint: which defs trace, which args are static,
which buffers are donated, and which mesh axes are in scope.

The registry is built per-module in one sweep so every rule shares the same
answers to:

* "is this ``def`` traced?" — decorated by ``jit``/``pjit``/``pmap``/
  ``shard_map`` (directly or via ``functools.partial``), or referenced by
  name as the first argument of such a wrapper call anywhere in the module
  (the repo's dominant idiom: ``return jax.jit(step, donate_argnums=0)``).
  Everything lexically inside a traced def traces too.
* "which params are static / donated?" — literal ``static_argnums``/
  ``static_argnames``/``donate_argnums`` pulled from the wrapper call.
* "which mesh axis names exist?" — the canonical ``AXIS_ORDER`` parsed out
  of ``parallel/mesh.py`` (never imported: the linter stays JAX-free), plus
  any literal ``axis_name=...`` bindings in the module (``pmap``/``vmap``)
  and literal ``Mesh(..., ('a', 'b'))`` axis tuples.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .walker import Source, enclosing, literal_strings

__all__ = ["JitSite", "JitRegistry", "mesh_axes_for", "DEFAULT_MESH_AXES",
           "JIT_WRAPPERS", "TRACED_WRAPPERS"]

# Canonical dotted names (post alias expansion) that compile their operand.
# Bare "shard_map" covers a relative re-export — relative modules have no
# canonical prefix to expand.
JIT_WRAPPERS: Set[str] = {
    "jax.jit",
    "jax.pjit",
    "jax.experimental.pjit.pjit",
    "jax.pmap",
    "jax.experimental.shard_map.shard_map",
    "jax.shard_map",
    "shard_map",
}
# Wrappers that trace but take axis bindings rather than static/donate args.
TRACED_WRAPPERS: Set[str] = JIT_WRAPPERS | {"jax.vmap", "jax.checkpoint",
                                            "jax.remat"}

# Builders whose return value is a jitted step donating its first arg
# (train/step.py's make_train_step family) — the cross-module half of the
# "registered as a train step" contract.
_STEP_BUILDER_RE = re.compile(r"^make_.*train_step$")

# Fallback when parallel/mesh.py is not reachable from the analyzed paths.
DEFAULT_MESH_AXES: Tuple[str, ...] = ("pipe", "data", "fsdp", "expert",
                                      "seq", "tensor")


@dataclasses.dataclass
class JitSite:
    """One wrapper application: ``jax.jit(step, donate_argnums=0)`` or a
    decorator.  ``target`` is the wrapped def when it could be resolved."""

    call: Optional[ast.Call]          # None for bare @jax.jit decorators
    wrapper: str                      # canonical wrapper name
    target: Optional[ast.AST]         # FunctionDef / Lambda
    target_name: Optional[str]
    static_argnums: Tuple[int, ...] = ()
    static_argnames: Tuple[str, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    axis_names: Tuple[str, ...] = ()  # literal axis bindings (pmap/vmap)


def _literal_ints(node: Optional[ast.AST]) -> Tuple[int, ...]:
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out: List[int] = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
        return tuple(out)
    return ()


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _unwrap_partial(src: Source, call: ast.Call
                    ) -> Tuple[Optional[str], ast.Call]:
    """``functools.partial(jax.jit, static_argnums=0)`` -> ('jax.jit', call)
    with the partial's keywords visible on the returned call."""
    name = src.call_canonical(call)
    if name in ("functools.partial", "partial") and call.args:
        inner = call.args[0]
        inner_name = None
        if isinstance(inner, (ast.Name, ast.Attribute)):
            probe = ast.Call(func=inner, args=[], keywords=[])
            inner_name = src.call_canonical(probe)
        if inner_name in TRACED_WRAPPERS:
            return inner_name, call
    return name, call


class JitRegistry:
    """Per-module index of traced defs and their wrapper metadata."""

    def __init__(self, src: Source):
        self.src = src
        self.sites: List[JitSite] = []
        # def name -> all FunctionDefs with that name (module-wide)
        self.defs_by_name: Dict[str, List[ast.AST]] = {}
        self.traced_defs: Set[ast.AST] = set()
        # def name -> JitSite (for static/donate lookups at call sites)
        self.site_by_name: Dict[str, JitSite] = {}
        self.module_axis_bindings: Set[str] = set()
        self._build()

    # ------------------------------------------------------------ build

    def _build(self) -> None:
        tree = self.src.tree
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs_by_name.setdefault(node.name, []).append(node)

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_decorators(node)
            elif isinstance(node, ast.Call):
                self._scan_call(node)

        # transitive closure is lexical: nested defs inside traced defs
        # trace too, which the rules get via ``in_traced_scope``.

        # Cross-module train-step registration: the train.make_*train_step
        # builders all return jax.jit(step, donate_argnums=0) — a call
        # site in another module donates its first argument even though
        # the jit wrapper is out of lexical reach.
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            tgt = node.targets[0]
            if not isinstance(tgt, ast.Name) \
                    or not isinstance(node.value, ast.Call):
                continue
            name = self.src.call_canonical(node.value) or ""
            if _STEP_BUILDER_RE.search(name.rsplit(".", 1)[-1]):
                self.site_by_name.setdefault(tgt.id, JitSite(
                    call=None, wrapper="jax.jit", target=None,
                    target_name=None, donate_argnums=(0,)))

    def _scan_decorators(self, fn: ast.AST) -> None:
        for dec in fn.decorator_list:  # type: ignore[attr-defined]
            if isinstance(dec, ast.Call):
                name, call = _unwrap_partial(self.src, dec)
                if name in TRACED_WRAPPERS:
                    self._add_site(call, name, fn,
                                   fn.name)  # type: ignore[attr-defined]
            elif isinstance(dec, (ast.Name, ast.Attribute)):
                probe = ast.Call(func=dec, args=[], keywords=[])
                name = self.src.call_canonical(probe)
                if name in TRACED_WRAPPERS:
                    self._add_site(None, name, fn,
                                   fn.name)  # type: ignore[attr-defined]

    def _scan_call(self, call: ast.Call) -> None:
        name = self.src.call_canonical(call)
        if name not in TRACED_WRAPPERS or not call.args:
            return
        operand = call.args[0]
        target: Optional[ast.AST] = None
        target_name: Optional[str] = None
        if isinstance(operand, ast.Name):
            target_name = operand.id
            target = self._resolve_def(operand.id, call)
        elif isinstance(operand, ast.Lambda):
            target = operand
        self._add_site(call, name, target, target_name)

    def _resolve_def(self, name: str, at: ast.AST) -> Optional[ast.AST]:
        """Prefer a def sharing an enclosing function with the wrapper call
        (the builder idiom); fall back to any module-level def."""
        candidates = self.defs_by_name.get(name, [])
        if not candidates:
            return None
        if len(candidates) == 1:
            return candidates[0]
        encl = enclosing(at, (ast.FunctionDef, ast.AsyncFunctionDef))
        if encl is not None:
            from .walker import is_ancestor
            near = [c for c in candidates if is_ancestor(encl, c)]
            if near:
                return near[-1]
        return candidates[-1]

    def _add_site(self, call: Optional[ast.Call], wrapper: str,
                  target: Optional[ast.AST],
                  target_name: Optional[str]) -> None:
        static_nums: Tuple[int, ...] = ()
        static_names: Tuple[str, ...] = ()
        donate: Tuple[int, ...] = ()
        axes: Tuple[str, ...] = ()
        if call is not None:
            static_nums = _literal_ints(_kw(call, "static_argnums"))
            sa = _kw(call, "static_argnames")
            if sa is not None:
                static_names = tuple(literal_strings(sa))
            donate = _literal_ints(_kw(call, "donate_argnums"))
            ax = _kw(call, "axis_name")
            if ax is not None:
                axes = tuple(literal_strings(ax))
        site = JitSite(call=call, wrapper=wrapper, target=target,
                       target_name=target_name,
                       static_argnums=static_nums,
                       static_argnames=static_names,
                       donate_argnums=donate, axis_names=axes)
        self.sites.append(site)
        if target is not None and wrapper in JIT_WRAPPERS:
            self.traced_defs.add(target)
        if target_name and wrapper in JIT_WRAPPERS:
            self.site_by_name.setdefault(target_name, site)
        # `train_step = jax.jit(step, ...)` — call sites use the new name
        if call is not None and wrapper in JIT_WRAPPERS:
            parent = getattr(call, "parent", None)
            if isinstance(parent, ast.Assign) and len(parent.targets) == 1 \
                    and isinstance(parent.targets[0], ast.Name):
                self.site_by_name.setdefault(parent.targets[0].id, site)
        self.module_axis_bindings.update(axes)

    # ------------------------------------------------------------ query

    def in_traced_scope(self, node: ast.AST) -> Optional[ast.AST]:
        """The outermost traced def lexically containing ``node``, if any."""
        found = None
        cur = getattr(node, "parent", None)
        while cur is not None:
            if cur in self.traced_defs:
                found = cur
            cur = getattr(cur, "parent", None)
        return found

    def static_param_names(self, fn: ast.AST) -> Set[str]:
        """Param names marked static for a traced def (best effort)."""
        site = None
        for s in self.sites:
            if s.target is fn:
                site = s
                break
        if site is None or not isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return set()
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        out = set(site.static_argnames)
        for i in site.static_argnums:
            if 0 <= i < len(params):
                out.add(params[i])
        return out


def _parse_axis_order(mesh_path: str) -> Optional[Tuple[str, ...]]:
    try:
        with open(mesh_path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError, ValueError):
        return None
    for node in ast.walk(tree):
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
            value = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
            value = node.value
        else:
            continue
        for t in targets:
            if isinstance(t, ast.Name) and t.id == "AXIS_ORDER":
                names = literal_strings(value)
                if names:
                    return tuple(names)
    return None


def mesh_axes_for(path: str) -> Tuple[str, ...]:
    """Canonical axis names for the package owning ``path``.

    Walks up from ``path`` looking for ``<pkg>/parallel/mesh.py`` (or a
    sibling ``distributed_tensorflow_tpu/parallel/mesh.py``) and parses its
    ``AXIS_ORDER``; falls back to the baked-in default.
    """
    probe = os.path.abspath(path)
    if os.path.isfile(probe):
        probe = os.path.dirname(probe)
    for _ in range(8):
        for rel in (("parallel", "mesh.py"),
                    ("distributed_tensorflow_tpu", "parallel", "mesh.py")):
            cand = os.path.join(probe, *rel)
            if os.path.isfile(cand):
                axes = _parse_axis_order(cand)
                if axes:
                    return axes
        parent = os.path.dirname(probe)
        if parent == probe:
            break
        probe = parent
    return DEFAULT_MESH_AXES
