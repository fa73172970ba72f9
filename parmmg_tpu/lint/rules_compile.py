"""R1 jit-hygiene + R5 jaxcompat: the compile-governor contracts,
statically.

R1 — every ``jax.jit`` / ``jax.pmap`` / ``shard_map`` construction must
be module-level-cached so repeat calls reuse ONE traced program (jit
caches by function identity: a fresh jit object per call recompiles
forever — the exact churn the runtime ``--ledger`` gate prices in
minutes of XLA:CPU compile).  Accepted caching idioms, matched on the
AST (these are the idioms PRs 3-5 actually converged on):

- module scope: decorator on a module-level def, or a module-level
  assignment (``analyze_mesh = jax.jit(...)``);
- a builder whose result is bound at module level
  (``swapgen_wave_j = _make_swapgen_jit()``);
- an ``functools.lru_cache``-ed builder;
- a builder that stores into a module-level CAPS cache
  (``_GROUP_BLOCK_CACHE[key] = run``, ``_QPROBE.append(probe)``, or a
  ``global`` rebind);
- an instance cache (``self.x = ...`` — the DistSteps pattern);
- a ``governed(...)``-wrapped construction in the same statement (the
  ledger then bounds the variant count at runtime even if the caller
  caches); a bare ``shard_map`` wrapper also passes when its builder
  governs a product anywhere in the function — the compile object is
  the jit built around it (the dist_adapt_block idiom), while a
  per-call ``jax.jit``/``pmap`` must be governed in its own statement.

Anything else is a per-call construction and gets flagged.

R5 — ``utils/jaxcompat.py`` is the one module that names
``jax.shard_map``, ``jax.lax.axis_size`` and
``jax.lax.platform_dependent`` (and the retired
``jax.experimental.shard_map``): direct use anywhere else bypasses it,
so the next jax rename would be a many-file edit.
"""
from __future__ import annotations

import ast
import re

from .engine import Violation, dotted, rule, walk_scoped

_CAPS_RE = re.compile(r"^_?[A-Z][A-Z0-9_]*$")
_CACHED_DECOS = ("lru_cache", "cache")

# dotted spellings that construct a compiled-program object
_JIT_DOTTED = {"jax.jit", "jax.pmap"}
# local names bound by `from ... import X` that do the same
_JIT_FROM = {"shard_map": ("jax.experimental.shard_map", "jaxcompat"),
             "jit": ("jax",), "pmap": ("jax",)}

_R5_DOTTED = {
    "jax.experimental.shard_map.shard_map": "shard_map",
    "jax.shard_map": "shard_map",
    "jax.lax.axis_size": "axis_size",
    "jax.lax.platform_dependent": "platform_dependent",
}
_R5_MODULES = ("jax.experimental.shard_map",)
_SHIM_REL = "parmmg_tpu/utils/jaxcompat.py"


def _jit_aliases(tree) -> set:
    """Local names that are jit-like constructors in this module
    (``from jax import jit``, ``from ..utils.jaxcompat import
    shard_map``, ``from jax.experimental.shard_map import shard_map``)."""
    out = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom) or not node.module:
            continue
        for a in node.names:
            local = a.asname or a.name
            srcs = _JIT_FROM.get(a.name)
            if srcs and any(s in node.module for s in srcs):
                out.add(local)
    return out


def _decorated_cached(fn_node) -> bool:
    for d in fn_node.decorator_list:
        base = d.func if isinstance(d, ast.Call) else d
        name = dotted(base)
        if name.split(".")[-1] in _CACHED_DECOS:
            return True
    return False


def _module_cache_store(fn_node) -> bool:
    """Does the function body persist something into a module-level
    cache (CAPS subscript store / .append, a ``global`` rebind) or an
    instance attribute?"""
    globals_declared = set()
    for n in ast.walk(fn_node):
        if isinstance(n, ast.Global):
            globals_declared.update(n.names)
    for n in ast.walk(fn_node):
        if isinstance(n, ast.Assign):
            for t in n.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and _CAPS_RE.match(t.value.id)):
                    return True
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    return True
                if isinstance(t, ast.Name) and t.id in globals_declared:
                    return True
        if (isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "append"
                and isinstance(n.func.value, ast.Name)
                and _CAPS_RE.match(n.func.value.id)):
            return True
    return False


def _module_level_builders(tree) -> set:
    """Function names whose call result is bound at module scope
    (``x = _make_...()``)."""
    out = set()
    for stmt in tree.body:
        if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
            val = stmt.value
            if val is None:
                continue
            for n in ast.walk(val):
                if isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Name):
                    out.add(n.func.id)
    return out


def _governed_in(node) -> bool:
    """Any ``governed(...)`` application inside ``node`` (statement or
    decorator list) — the ledger-registration escape hatch."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            base = n.func.func if isinstance(n.func, ast.Call) \
                else n.func
            if dotted(base).split(".")[-1] == "governed":
                return True
    return False


@rule("R1")
def check_r1(ctx) -> list:
    out = []
    for sf in ctx.iter(("parmmg_tpu/",), exclude=(_SHIM_REL,)):
        if sf.tree is None:
            continue
        aliases = _jit_aliases(sf.tree)
        builders = _module_level_builders(sf.tree)

        # index: function node -> list of its body statements is free via
        # ast; we need, per offending node, its enclosing stmt + fn chain
        for node, qn, funcs in walk_scoped(sf.tree):
            name = None
            if isinstance(node, (ast.Attribute, ast.Name)):
                d = dotted(node)
                if d in _JIT_DOTTED:
                    name = d
                elif isinstance(node, ast.Name) and node.id in aliases \
                        and isinstance(node.ctx, ast.Load):
                    name = node.id
            if name is None:
                continue
            if not funcs:
                continue                      # module scope: cached
            fn = funcs[-1]
            # the mention may be a decorator of a nested def: walk_scoped
            # reports decorator nodes under the *enclosing* function, so
            # funcs[-1] is already the scope whose caching matters
            chain_cached = any(_decorated_cached(f) for f in funcs)
            stores = any(_module_cache_store(f) for f in funcs)
            built_once = any(f.name in builders for f in funcs)
            if chain_cached or stores or built_once:
                continue
            # governed() in the SAME statement registers this very
            # construction with the compile ledger, whose variant
            # budget bounds churn at runtime
            stmt = _enclosing_stmt(fn, node)
            if stmt is not None and _governed_in(stmt):
                continue
            # a bare shard_map wrapper is cheap by itself — the compile
            # object is the jit built around it; accept it when the
            # builder governs a product anywhere (the dist_adapt_block
            # idiom: fn = shard_map(...); return governed(...)(jit(fn)))
            # while a per-call jit/pmap still needs ITS OWN statement
            # governed or a cache
            if name.split(".")[-1] == "shard_map" and _governed_in(fn):
                continue
            out.append(Violation(
                "R1", sf.rel, node.lineno, qn, name,
                f"per-call {name} construction in {qn}(): cache at "
                "module level (CAPS cache dict / lru_cache / module "
                "assignment) or register via governed()"))
    return out


def _enclosing_stmt(fn_node, target):
    """Smallest statement within ``fn_node`` containing ``target``
    (walk order guarantees later matches are nested deeper)."""
    best = None
    for n in ast.walk(fn_node):
        if not isinstance(n, ast.stmt):
            continue
        for sub in ast.walk(n):
            if sub is target:
                best = n
                break
    return best


@rule("R5")
def check_r5(ctx) -> list:
    out = []
    for sf in ctx.iter(("parmmg_tpu/", "scripts/"),
                       exclude=(_SHIM_REL,)):
        if sf.tree is None:
            continue
        for node, qn, _funcs in walk_scoped(sf.tree):
            if isinstance(node, ast.ImportFrom) and node.module and \
                    any(node.module.startswith(m) for m in _R5_MODULES):
                out.append(Violation(
                    "R5", sf.rel, node.lineno, qn, node.module,
                    f"direct import of {node.module} — use the "
                    "utils/jaxcompat.py shim"))
                continue
            if isinstance(node, ast.Import):
                for a in node.names:
                    if any(a.name.startswith(m) for m in _R5_MODULES):
                        out.append(Violation(
                            "R5", sf.rel, node.lineno, qn, a.name,
                            f"direct import of {a.name} — use the "
                            "utils/jaxcompat.py shim"))
                continue
            if isinstance(node, ast.Attribute):
                d = dotted(node)
                sym = _R5_DOTTED.get(d)
                if sym:
                    out.append(Violation(
                        "R5", sf.rel, node.lineno, qn, sym,
                        f"direct use of {d} — shimmed symbol; import "
                        f"{sym} from utils/jaxcompat.py"))
    return out
