"""PUR — the core-purity pass.

Layer-2 pure steps are the functions that DECLARE an `xp` backend
parameter (engine.py's `apply_model`, `reorganize`, `catch_up`,
`hybrid_probe`, ... — the rule is self-applying, so a fixture or a new
module claiming purity via `xp` is held to the same standard), plus
every Pallas kernel module (`kernels/*/kernel.py`). For those:

    PUR001  direct `np.` use — the backend must come in through `xp`.
            The ONE blessed exception is a backend dispatch guarded by
            `if xp is np:` (numpy-only fast paths like stable argsort);
            kernels get no exception (jnp/lax/pl only).
    PUR002  Python side effects: `print`, `global`/`nonlocal`
            statements, `.item()` host syncs, `time.*`, `input`,
            `os.*` — a jitted step must be a pure function of its
            arguments.
    PUR003  in-place mutation of a parameter (`state_arr[...] = x`,
            `param += y`) — pure steps return new values; mutating an
            argument breaks jit tracing and value semantics. Writes to
            LOCAL arrays and to Pallas `*_ref` output references are
            fine.

Shells (everything outside engine.py in `core/`, `rdbms/`, `storage/`):

    PUR004  in-place mutation of an `EngineState` field on a non-`self`
            object (`state.labels[i] = y`, `eng.lw[v] = 0`).
            `EngineState` is an immutable pytree; shells own their OWN
            mirrors (`self.lw[...] = ...` is their state, fine) but must
            never reach into an engine state they were handed. The field
            list is read from engine.py's `EngineState` class at scan
            time, not hardcoded.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import List, Optional, Set

from repro.analysis.common import (Finding, ModuleSet, PKG_ROOT, root_name,
                                   trailing_name)

_SIDE_EFFECT_MODULES = {"time", "os", "sys"}


def _is_engine(path: Path) -> bool:
    return path.name == "engine.py" and path.parent.name == "core"


def _is_kernel(path: Path) -> bool:
    return path.name == "kernel.py" and "kernels" in path.parts


def engine_state_fields() -> Set[str]:
    """`EngineState._fields`, read from the real engine.py's AST."""
    engine = PKG_ROOT / "core" / "engine.py"
    if not engine.exists():
        return set()
    tree = ast.parse(engine.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "EngineState":
            return {item.target.id for item in node.body
                    if isinstance(item, ast.AnnAssign)
                    and isinstance(item.target, ast.Name)}
    return set()


def _xp_functions(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            names = [a.arg for a in args.args + args.kwonlyargs
                     + args.posonlyargs]
            if "xp" in names:
                yield node


def _np_guarded_lines(fn: ast.AST) -> Set[int]:
    """Line numbers inside `if xp is np:` bodies (the blessed numpy
    fast-path dispatch) — `np.` use there is allowed."""
    lines: Set[int] = set()

    def is_xp_is_np(test: ast.AST) -> Optional[bool]:
        # returns True for `xp is np`, False for `xp is not np`
        if (isinstance(test, ast.Compare) and len(test.ops) == 1
                and isinstance(test.left, ast.Name)
                and test.left.id == "xp"
                and isinstance(test.comparators[0], ast.Name)
                and test.comparators[0].id == "np"):
            if isinstance(test.ops[0], ast.Is):
                return True
            if isinstance(test.ops[0], ast.IsNot):
                return False
        return None

    for node in ast.walk(fn):
        if not isinstance(node, ast.If):
            continue
        guard = is_xp_is_np(node.test)
        if guard is None:
            continue
        branch = node.body if guard else node.orelse
        for stmt in branch:
            for sub in ast.walk(stmt):
                if hasattr(sub, "lineno"):
                    lines.add(sub.lineno)
    return lines


def _check_pure_function(modules: ModuleSet, path: Path, fn: ast.AST,
                         kernel: bool) -> List[Finding]:
    findings: List[Finding] = []
    where = "Pallas kernel" if kernel else f"pure step {fn.name!r}"
    guarded = set() if kernel else _np_guarded_lines(fn)
    args = fn.args
    params = {a.arg for a in args.args + args.kwonlyargs + args.posonlyargs}

    for node in ast.walk(fn):
        # PUR001: host numpy outside the xp seam
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "np"
                and node.lineno not in guarded):
            findings.append(modules.finding(
                path, node, "PUR001",
                f"direct np.{node.attr} in {where} — use the xp backend "
                f"parameter (or guard with `if xp is np:`)"))
        # PUR002: side effects
        elif isinstance(node, (ast.Global, ast.Nonlocal)):
            findings.append(modules.finding(
                path, node, "PUR002",
                f"{type(node).__name__.lower()} statement in {where}"))
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id in ("print", "input"):
                findings.append(modules.finding(
                    path, node, "PUR002", f"{f.id}() call in {where}"))
            elif isinstance(f, ast.Attribute):
                if f.attr == "item":
                    findings.append(modules.finding(
                        path, node, "PUR002",
                        f".item() host sync in {where}"))
                elif (isinstance(f.value, ast.Name)
                      and f.value.id in _SIDE_EFFECT_MODULES):
                    findings.append(modules.finding(
                        path, node, "PUR002",
                        f"{f.value.id}.{f.attr}() call in {where}"))
        # PUR003: in-place parameter mutation
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for tgt in targets:
                if isinstance(tgt, (ast.Subscript, ast.Attribute)):
                    root = root_name(tgt)
                    name = trailing_name(tgt)
                    if root in params and not (
                            kernel and name and name.endswith("_ref")):
                        findings.append(modules.finding(
                            path, tgt, "PUR003",
                            f"in-place mutation of parameter {root!r} "
                            f"in {where} — return a new value"))
    return findings


def check_purity(modules: ModuleSet) -> List[Finding]:
    findings: List[Finding] = []
    fields = engine_state_fields()
    shell_dirs = {"core", "rdbms", "storage"}

    for path, tree in modules.trees.items():
        kernel = _is_kernel(path)
        if kernel:
            for node in ast.walk(tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    findings.extend(_check_pure_function(
                        modules, path, node, kernel=True))
            continue
        for fn in _xp_functions(tree):
            findings.extend(_check_pure_function(modules, path, fn,
                                                 kernel=False))
        # PUR004: shells mutating EngineState fields on non-self objects.
        # Applies to core/rdbms/storage modules (engine.py excepted) and
        # to out-of-tree files (the fixture corpus simulates shells);
        # the package's other directories are not EngineState shells.
        if _is_engine(path):
            continue
        try:
            rel_parts = set(path.relative_to(PKG_ROOT).parts[:-1])
        except ValueError:
            rel_parts = None               # outside the package: a shell
        if rel_parts is not None and not (shell_dirs & rel_parts):
            continue
        for node in ast.walk(tree):
            if not isinstance(node, (ast.Assign, ast.AugAssign)):
                continue
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for tgt in targets:
                base = tgt.value if isinstance(tgt, ast.Subscript) else tgt
                if not isinstance(base, ast.Attribute):
                    continue
                if base.attr in fields and root_name(base) != "self":
                    findings.append(modules.finding(
                        path, tgt, "PUR004",
                        f"shell mutates EngineState field "
                        f"{base.attr!r} on {root_name(base)!r} — "
                        f"EngineState is immutable; go through an "
                        f"engine rule / _replace"))
    return findings
