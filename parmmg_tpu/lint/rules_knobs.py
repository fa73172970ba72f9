"""R4 knob-registry: every ``PARMMG_*`` env knob declared exactly once.

``parmmg_tpu/api/knobs.py`` is the registry (type + default + one-line
doc per knob).  R4 cross-checks it against the live tree in BOTH
directions, with NO baseline (the registry ships clean):

- every env READ of a ``PARMMG_*`` name (``os.environ.get`` /
  ``os.environ[...]`` / ``os.getenv`` / ``setdefault`` / ``pop`` /
  helper functions whose name contains ``env``, e.g. the serve pool's
  ``_env_int``) must name a registered knob;
- a read through a non-literal name expression is flagged outright
  (an f-string env key is an unauditable surface);
- every registered knob must have at least one AST usage anywhere in
  the tree (env access, kwarg, or string literal outside docstrings) —
  otherwise it is dead and fails;
- every registered knob must appear in README.md, and every
  ``PARMMG_*`` token README mentions must be registered — the README
  knob tables stay a *verified* rendering of the registry
  (``python -m parmmg_tpu.api.knobs`` prints the canonical table).
"""
from __future__ import annotations

import ast
import re

from .engine import (KNOBS_REL, Violation, dotted, rule, str_const,
                     walk_scoped)

_KNOB_RE = re.compile(r"^PARMMG_[A-Z0-9_]+$")
_KNOB_TOKEN_RE = re.compile(r"PARMMG_[A-Z0-9_]+")

_SCOPE = ("parmmg_tpu/", "scripts/", "tests/")

_ENV_GET_ATTRS = ("get", "setdefault", "pop", "__getitem__")


def _env_read_name_node(call):
    """If ``call`` is an env access, return its name-argument node."""
    f = call.func
    if isinstance(f, ast.Attribute):
        base = dotted(f.value)
        if f.attr in _ENV_GET_ATTRS and base.endswith("environ"):
            return call.args[0] if call.args else None
        if f.attr == "getenv" and base in ("os", ""):
            return call.args[0] if call.args else None
        if "env" in f.attr.lower():
            return call.args[0] if call.args else None
    if isinstance(f, ast.Name) and "env" in f.id.lower() and call.args:
        return call.args[0]
    return None


def _docstring_nodes(tree) -> set:
    """ids of docstring Constant nodes (excluded from usage evidence)."""
    out = set()
    for n in ast.walk(tree):
        if isinstance(n, (ast.Module, ast.FunctionDef,
                          ast.AsyncFunctionDef, ast.ClassDef)):
            body = getattr(n, "body", [])
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                out.add(id(body[0].value))
    return out


@rule("R4")
def check_r4(ctx) -> list:
    registry = ctx.knob_registry()
    out: list[Violation] = []
    used: set = set()

    for sf in ctx.iter(_SCOPE, exclude=(KNOBS_REL,)):
        if sf.tree is None:
            continue
        docstrings = _docstring_nodes(sf.tree)
        for node, qn, _funcs in walk_scoped(sf.tree):
            # env accesses: literal name must be registered
            if isinstance(node, ast.Call):
                nm = _env_read_name_node(node)
                if nm is not None:
                    s = str_const(nm)
                    if s is None:
                        # dynamic name: only flag when it visibly
                        # builds a PARMMG key
                        if any(_KNOB_TOKEN_RE.search(c.value)
                               for c in ast.walk(nm)
                               if isinstance(c, ast.Constant)
                               and isinstance(c.value, str)):
                            out.append(Violation(
                                "R4", sf.rel, node.lineno, qn,
                                "dynamic-env-read",
                                "PARMMG_* env access through a "
                                "non-literal name — unauditable"))
                        continue
                    if _KNOB_RE.match(s):
                        used.add(s)
                        if s not in registry:
                            out.append(Violation(
                                "R4", sf.rel, node.lineno, qn, s,
                                f"env read of unregistered knob {s} — "
                                "declare it in parmmg_tpu/api/knobs.py"))
            # subscript access os.environ["PARMMG_X"] (read or write)
            if isinstance(node, ast.Subscript) and \
                    dotted(node.value).endswith("environ"):
                s = str_const(node.slice)
                if s and _KNOB_RE.match(s):
                    used.add(s)
                    if s not in registry:
                        out.append(Violation(
                            "R4", sf.rel, node.lineno, qn, s,
                            f"env access of unregistered knob {s} — "
                            "declare it in parmmg_tpu/api/knobs.py"))
            # usage evidence: kwargs + non-docstring literals
            if isinstance(node, ast.keyword) and node.arg and \
                    _KNOB_RE.match(node.arg):
                used.add(node.arg)
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    id(node) not in docstrings and \
                    _KNOB_RE.match(node.value):
                used.add(node.value)

    # dead registered knobs
    for name, info in sorted(registry.items()):
        if name not in used:
            out.append(Violation(
                "R4", KNOBS_REL, info.get("line", 0), "KNOBS", name,
                f"registered knob {name} has no usage anywhere in the "
                "tree — dead; delete it or wire it"))

    # README two-way check
    readme = ctx.readme_text or ""
    readme_knobs = set(_KNOB_TOKEN_RE.findall(readme))
    for name, info in sorted(registry.items()):
        if name not in readme_knobs:
            out.append(Violation(
                "R4", KNOBS_REL, info.get("line", 0), "KNOBS", name,
                f"registered knob {name} missing from README.md — "
                "regenerate the knob table "
                "(python -m parmmg_tpu.api.knobs)"))
    for name in sorted(readme_knobs - set(registry)):
        out.append(Violation(
            "R4", "README.md", 0, "<doc>", name,
            f"README mentions unregistered knob {name} — register it "
            "or fix the doc"))
    return out
