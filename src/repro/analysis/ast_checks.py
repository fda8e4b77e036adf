"""Pass 3: repo lint rules (REPRO00x) -- pure-AST, no jax import needed.

  REPRO001  ``os.environ`` / ``os.getenv`` read inside a function
            reachable from a jit-traced body.  A live env read under
            trace desynchronizes from jit's executable cache (keyed on
            shapes + statics only, never on the environment), which is
            how the same process silently runs two different configs.
            ``repro/hostenv.py`` is the single sanctioned chokepoint
            (trace-frozen snapshot semantics) and is exempt.
            Reachability is an over-approximation: any function whose
            NAME is referenced inside a reachable function body counts
            as called (decorator jits, ``jax.jit(f)`` assignments, and
            functions handed to scan/cond/shard_map/grad/... seed the
            root set).  The tree is expected to be exactly clean, so
            over-approximating costs nothing and misses nothing.
  REPRO002  dense VQ materializations in the hot modules: ``one_hot``
            under ``core/``, ``kernels/`` and ``models/gnn.py`` outside
            a Pallas kernel body (the [n, k] indicator materialized in
            HBM is the O(n*k) form the paper's Sec. 4 sparse-assignment
            design exists to avoid; a one-hot block inside a kernel
            lives in VMEM for one tile), and ``einsum``
            in ``core/codebook.py`` / ``core/conv.py`` (the [n, b, k]
            contraction path; the sketch-form einsums of
            ``message_passing.py`` and the oracle einsums of
            ``kernels/ref.py`` are the sanctioned exceptions).
  REPRO003  Python ``for``/``while`` inside a Pallas kernel body (a
            function taking ``*_ref`` parameters): trace-time loops
            unroll into the kernel and break the static block schedule.
            Host-side per-branch dispatch loops (``_context_ell_loop``)
            are outside kernel bodies and untouched.
  REPRO004  a class defining ``tree_flatten`` without
            ``register_pytree_node_class`` (decorator or module-level
            registration call): it traces as a leaf or errors only at
            the first jit boundary that receives it.
  REPRO005  import-time process mutation: assigning/updating
            ``os.environ`` (or ``os.putenv``) at module top level.
            Mutations under ``if __name__ == "__main__":`` are the CLI
            pattern and exempt.
"""
from __future__ import annotations

import ast
import os
from typing import Iterator

from repro.analysis import Finding

# modules where the [n, k] one-hot indicator is banned
_HOT_PREFIXES = ("core/", "kernels/", "models/gnn.py")
# modules where einsum itself is banned (dense-assignment contraction)
_NO_EINSUM = ("core/codebook.py", "core/conv.py")
_ENV_EXEMPT = ("hostenv.py",)

_ROOT_TAKERS = {
    "scan", "fori_loop", "while_loop", "cond", "switch", "shard_map",
    "grad", "value_and_grad", "vjp", "jvp", "custom_vjp", "custom_jvp",
    "defvjp", "defjvp", "checkpoint", "remat", "pallas_call", "vmap",
    "pmap",
}


def _py_files(root: str) -> Iterator[tuple[str, str]]:
    src = os.path.join(root, "src", "repro")
    for dirpath, _, names in os.walk(src):
        for name in sorted(names):
            if name.endswith(".py"):
                full = os.path.join(dirpath, name)
                yield full, os.path.relpath(full, root)


def _callee_name(func) -> str | None:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _is_jit_deco(deco) -> bool:
    """jax.jit / functools.partial(jax.jit, ...) decorators."""
    if _callee_name(deco) == "jit" or (
            isinstance(deco, ast.Name) and deco.id == "jit"):
        return True
    if isinstance(deco, ast.Call):
        if _callee_name(deco.func) == "jit":
            return True
        if _callee_name(deco.func) == "partial" and deco.args and \
                _callee_name(deco.args[0]) == "jit":
            return True
    return False


class _FnInfo:
    def __init__(self, rel: str, node: ast.AST):
        self.rel = rel
        self.node = node
        self.refs: set[str] = set()      # every identifier referenced
        self.env_reads: list[int] = []   # lines touching os.environ

    def scan(self):
        for sub in ast.walk(self.node):
            if isinstance(sub, ast.Name):
                self.refs.add(sub.id)
            elif isinstance(sub, ast.Attribute):
                self.refs.add(sub.attr)
                if sub.attr == "environ" and \
                        isinstance(sub.value, ast.Name) and \
                        sub.value.id == "os":
                    self.env_reads.append(sub.lineno)
            elif isinstance(sub, ast.Call) and \
                    _callee_name(sub.func) == "getenv":
                self.env_reads.append(sub.lineno)


def _collect(tree: ast.Module, rel: str, fns: dict, roots: set):
    """Index every function; seed jit roots from decorators, jax.jit(f)
    assignments, and names passed to trace-entering combinators."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            info = _FnInfo(rel, node)
            info.scan()
            fns.setdefault(node.name, []).append(info)
            if any(_is_jit_deco(d) for d in node.decorator_list):
                roots.add(node.name)
        elif isinstance(node, ast.Call):
            callee = _callee_name(node.func)
            if callee == "jit":
                for arg in node.args[:1]:
                    if (n := _callee_name(arg)):
                        roots.add(n)
            elif callee in _ROOT_TAKERS:
                for arg in list(node.args) + [k.value for k in
                                              node.keywords]:
                    if (n := _callee_name(arg)):
                        roots.add(n)


def _reachable(fns: dict, roots: set) -> set:
    seen: set[str] = set()
    frontier = [r for r in roots if r in fns]
    while frontier:
        name = frontier.pop()
        if name in seen:
            continue
        seen.add(name)
        for info in fns[name]:
            for ref in info.refs:
                if ref in fns and ref not in seen:
                    frontier.append(ref)
    return seen


def _env_findings(parsed: list) -> list[Finding]:
    fns: dict[str, list[_FnInfo]] = {}
    roots: set[str] = set()
    for rel, tree in parsed:
        _collect(tree, rel, fns, roots)
    findings = []
    for name in sorted(_reachable(fns, roots)):
        for info in fns[name]:
            if info.rel.endswith(_ENV_EXEMPT) or not info.env_reads:
                continue
            for line in sorted(set(info.env_reads)):
                findings.append(Finding(
                    "REPRO001", info.rel, line,
                    f"os.environ read in '{name}', reachable from a "
                    f"jit-traced body -- route it through "
                    f"repro.hostenv.env_knob (trace-frozen snapshot)"))
    return findings


def _banned_call_findings(rel: str, tree: ast.Module) -> list[Finding]:
    sub = rel.split("src/repro/", 1)[-1]
    findings = []
    hot = sub.startswith(_HOT_PREFIXES)
    no_einsum = sub in _NO_EINSUM
    if not (hot or no_einsum):
        return findings
    in_kernel = {id(n) for body in _kernel_bodies(tree)
                 for n in ast.walk(body)}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _callee_name(node.func)
        if hot and callee == "one_hot" and id(node) not in in_kernel:
            findings.append(Finding(
                "REPRO002", rel, node.lineno,
                "one_hot in a hot module materializes the dense [n, k] "
                "assignment indicator; use gather/segment ops on the "
                "sparse assignment instead"))
        if no_einsum and callee == "einsum":
            findings.append(Finding(
                "REPRO002", rel, node.lineno,
                "einsum in the codebook/conv hot path (dense [n, b, k] "
                "contraction form); use the kernel dispatchers"))
    return findings


def _kernel_bodies(tree: ast.Module) -> list:
    """Pallas kernel bodies: the functions taking ``*_ref`` parameters."""
    bodies = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        args = node.args
        names = [a.arg for a in (args.posonlyargs + args.args +
                                 args.kwonlyargs)]
        if any(n.endswith("_ref") for n in names):
            bodies.append(node)
    return bodies


def _kernel_loop_findings(rel: str, tree: ast.Module) -> list[Finding]:
    findings = []
    for node in _kernel_bodies(tree):
        for sub in ast.walk(node):
            if isinstance(sub, (ast.For, ast.While)):
                findings.append(Finding(
                    "REPRO003", rel, sub.lineno,
                    f"Python loop inside Pallas kernel body "
                    f"'{node.name}' unrolls at trace time; use "
                    f"lax.fori_loop or grid steps"))
    return findings


def _pytree_findings(rel: str, tree: ast.Module) -> list[Finding]:
    registered: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and (
                _callee_name(node.func) or "").startswith(
                    "register_pytree"):
            for arg in node.args[:1]:
                if (n := _callee_name(arg)):
                    registered.add(n)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        has_flatten = any(
            isinstance(m, ast.FunctionDef) and m.name == "tree_flatten"
            for m in node.body)
        if not has_flatten:
            continue
        decorated = any(
            (_callee_name(d) or getattr(d, "id", "")) ==
            "register_pytree_node_class" for d in node.decorator_list)
        if not decorated and node.name not in registered:
            findings.append(Finding(
                "REPRO004", rel, node.lineno,
                f"class '{node.name}' defines tree_flatten but is never "
                f"registered as a pytree node; it crosses jit "
                f"boundaries as an opaque leaf"))
    return findings


def _import_side_effect_findings(rel: str,
                                 tree: ast.Module) -> list[Finding]:
    findings = []

    def _is_main_guard(node) -> bool:
        return (isinstance(node, ast.If) and
                isinstance(node.test, ast.Compare) and
                isinstance(node.test.left, ast.Name) and
                node.test.left.id == "__name__")

    def _visit(body):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if _is_main_guard(node):
                continue
            if isinstance(node, (ast.If, ast.Try, ast.With)):
                for attr in ("body", "orelse", "finalbody"):
                    _visit(getattr(node, attr, []) or [])
                for h in getattr(node, "handlers", []):
                    _visit(h.body)
                continue
            for sub in ast.walk(node):
                target = None
                if isinstance(sub, (ast.Assign, ast.AugAssign)):
                    tgts = (sub.targets if isinstance(sub, ast.Assign)
                            else [sub.target])
                    for t in tgts:
                        if isinstance(t, ast.Subscript) and \
                                isinstance(t.value, ast.Attribute) and \
                                t.value.attr == "environ":
                            target = sub
                elif isinstance(sub, ast.Call):
                    cn = _callee_name(sub.func)
                    if cn == "putenv" or (
                            cn in ("update", "setdefault", "pop") and
                            isinstance(sub.func, ast.Attribute) and
                            isinstance(sub.func.value, ast.Attribute) and
                            sub.func.value.attr == "environ"):
                        target = sub
                if target is not None:
                    findings.append(Finding(
                        "REPRO005", rel, target.lineno,
                        "process environment mutated at import time; "
                        "move it under `if __name__ == '__main__':` "
                        "(importing a module must be side-effect free)"))

    _visit(tree.body)
    return findings


def run(root: str | None = None) -> list[Finding]:
    root = root or os.getcwd()
    parsed = []
    findings: list[Finding] = []
    for full, rel in _py_files(root):
        with open(full) as fh:
            try:
                tree = ast.parse(fh.read(), filename=rel)
            except SyntaxError as exc:
                findings.append(Finding(
                    "REPRO005", rel, exc.lineno or 0,
                    f"unparseable module: {exc.msg}"))
                continue
        parsed.append((rel, tree))
        findings.extend(_banned_call_findings(rel, tree))
        findings.extend(_kernel_loop_findings(rel, tree))
        findings.extend(_pytree_findings(rel, tree))
        findings.extend(_import_side_effect_findings(rel, tree))
    findings.extend(_env_findings(parsed))
    return findings
