"""trace-discipline: jit call sites stay in one-time construction
contexts, and their static/donate metadata matches the traced function.

The engine compiles each traced shape family ONCE (module-level jit,
``_build_*`` factories, ``functools.cached_property``); a ``jax.jit``
call reached per step would retrace/recompile per call and silently
turn a bucketed shape family into a compile-per-shape path. Dispatchers
that broadcast the shape-family ops must also derive their batch shape
through the bucketing helpers (``pad_to_bucket``) or consume prestaged
arrays — an ad-hoc shape is a new compile per distinct batch size.

Rules:

- TD001: ``jax.jit``/``functools.partial(jax.jit, ...)`` called outside
  a construction context (module level, ``__init__``, ``_build_*`` /
  ``_alloc_*`` / ``_warm_*`` methods, ``cached_property`` bodies).
- TD002: ``static_argnames`` naming a parameter the wrapped function
  does not have (jit silently ignores it; the arg is then traced and
  every distinct value compiles a new program).
- TD003: ``donate_argnums`` index out of range for the wrapped function.
- TD004: a method dispatching a shape-family opcode (``_sync`` with
  ``_OP_PREFILL``/``_OP_DECODE``/``_OP_VERIFY``/``_OP_UNIFIED``/
  ``_OP_FLAT``/``_OP_EMBED``) that neither buckets its
  shapes (``pad_to_bucket``) nor consumes a prestaged ``Staged*`` batch
  nor is a declared warmup (``_warm_*``). The flattened-token family
  (``_OP_FLAT``) is shape-disciplined on its T axis alone: the stream
  must ride the fine-grained flat T buckets (staging derives it via
  ``pad_to_bucket`` over ``flat_t_buckets``) with the row-metadata
  width FIXED — an ad-hoc stream length would compile a new program per
  distinct step size, exactly what the one-shape-family design removes.
"""

from __future__ import annotations

import ast

from llmd_tpu.analysis.core import Checker, Finding, Repo, register

_CONSTRUCTION_PREFIXES = ("_build_", "_alloc_", "_warm_")
_CONSTRUCTION_NAMES = {"__init__"}
_SHAPE_FAMILY_OPS = {
    "_OP_PREFILL", "_OP_DECODE", "_OP_VERIFY", "_OP_UNIFIED", "_OP_FLAT",
    "_OP_EMBED",
}


def _is_jax_jit(node: ast.expr) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and node.attr == "jit"
        and isinstance(node.value, ast.Name)
        and node.value.id == "jax"
    )


def _is_partial_jit(node: ast.Call) -> bool:
    """functools.partial(jax.jit, ...) / partial(jax.jit, ...)."""
    f = node.func
    name_ok = (
        isinstance(f, ast.Attribute) and f.attr == "partial"
    ) or (isinstance(f, ast.Name) and f.id == "partial")
    return name_ok and bool(node.args) and _is_jax_jit(node.args[0])


def _is_cached_property(deco: ast.expr) -> bool:
    if isinstance(deco, ast.Attribute):
        return deco.attr == "cached_property"
    return isinstance(deco, ast.Name) and deco.id == "cached_property"


def _const_strings(node: ast.expr | None) -> list[str] | None:
    """Names from a static_argnames value, or None when not statically
    resolvable (conditional expressions etc. are skipped, not guessed)."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
                return None
            out.append(e.value)
        return out
    return None


def _const_ints(node: ast.expr | None) -> list[int] | None:
    """Indices from donate_argnums; conditional forms contribute every
    branch (a donated index must be valid whichever branch ran)."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, int)):
                return None
            out.append(e.value)
        return out
    if isinstance(node, ast.IfExp):
        a = _const_ints(node.body)
        b = _const_ints(node.orelse)
        if a is None or b is None:
            return None
        return a + b
    return None


def _fn_params(fn: ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda):
    a = fn.args
    positional = [p.arg for p in (*a.posonlyargs, *a.args)]
    keyword = positional + [p.arg for p in a.kwonlyargs]
    return positional, keyword


class _Visitor(ast.NodeVisitor):
    def __init__(self, sf) -> None:
        self.sf = sf
        self.findings: list[Finding] = []
        # Stack of (function name, is construction context) frames.
        self.frames: list[tuple[str, bool]] = []
        self.module_defs: dict[str, ast.FunctionDef] = {}

    # -------------------------------------------------------------- #

    def _in_construction_context(self) -> bool:
        if not self.frames:
            return True  # module level (incl. decorator lists)
        return any(ok for _, ok in self.frames)

    def _flag(self, node: ast.AST, code: str, msg: str) -> None:
        self.findings.append(
            Finding("trace-discipline", code, self.sf.path, node.lineno, msg)
        )

    def _check_jit_meta(self, call: ast.Call, fn) -> None:
        """Validate static_argnames/donate_argnums against a visible def."""
        positional, keyword = _fn_params(fn)
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                names = _const_strings(kw.value)
                for n in names or ():
                    if n not in keyword:
                        self._flag(
                            call, "TD002",
                            f"static_argnames names {n!r} which is not a "
                            "parameter of the jitted function — jit ignores "
                            "it and the argument is traced (a new compile "
                            "per distinct value)",
                        )
            elif kw.arg == "donate_argnums":
                idxs = _const_ints(kw.value)
                for i in idxs or ():
                    if not (0 <= i < len(positional)):
                        self._flag(
                            call, "TD003",
                            f"donate_argnums index {i} out of range for the "
                            f"jitted function ({len(positional)} positional "
                            "parameters)",
                        )

    # -------------------------------------------------------------- #

    def visit_Module(self, node: ast.Module) -> None:
        for stmt in node.body:
            if isinstance(stmt, ast.FunctionDef):
                self.module_defs[stmt.name] = stmt
        self.generic_visit(node)

    def _enter_function(self, node) -> None:
        cached = any(_is_cached_property(d) for d in node.decorator_list)
        # Decorator expressions evaluate in the ENCLOSING scope; a
        # partial(jax.jit, ...) decorator on this def is checked against
        # this def's signature.
        for d in node.decorator_list:
            call = d if isinstance(d, ast.Call) else None
            if call is not None and (_is_partial_jit(call)):
                self._check_jit_meta(call, node)
            elif _is_jax_jit(d):
                pass  # plain @jax.jit: nothing to cross-check
            else:
                self.visit(d)
        construction = (
            cached
            or node.name in _CONSTRUCTION_NAMES
            or node.name.startswith(_CONSTRUCTION_PREFIXES)
        )
        self.frames.append((node.name, construction))
        for stmt in node.body:
            self.visit(stmt)
        self.frames.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._enter_function(node)
        self._check_dispatch_bucketing(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._enter_function(node)
        self._check_dispatch_bucketing(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        # A jit(lambda: ...) at construction scope stays construction.
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        jit_call = _is_jax_jit(node.func) or _is_partial_jit(node)
        if jit_call and not self._in_construction_context():
            where = self.frames[-1][0] if self.frames else "<module>"
            self._flag(
                node, "TD001",
                f"jax.jit called inside {where!r}, which is not a one-time "
                "construction context (module scope, __init__, _build_*/"
                "_alloc_*/_warm_*, cached_property) — a per-call jit "
                "retraces instead of reusing the traced shape family",
            )
        if jit_call:
            # Call-form wrapping of a visible def or inline lambda. A
            # kwargs-only partial(jax.jit, ...) names no target here; its
            # metadata is checked at the decorator/apply site instead.
            if _is_partial_jit(node):
                target = node.args[1] if len(node.args) > 1 else None
            else:
                target = node.args[0] if node.args else None
            fn = None
            if isinstance(target, ast.Lambda):
                fn = target
            elif isinstance(target, ast.Name):
                fn = self.module_defs.get(target.id)
            if fn is not None:
                self._check_jit_meta(node, fn)
        self.generic_visit(node)

    # -------------------------------------------------------------- #

    def _check_dispatch_bucketing(self, fn) -> None:
        """TD004 over a completed function body."""
        if fn.name.startswith("_warm_"):
            return
        ops_dispatched = set()
        calls_pad_to_bucket = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name) and f.id == "pad_to_bucket":
                calls_pad_to_bucket = True
            if (
                isinstance(f, ast.Attribute)
                and f.attr == "_sync"
                and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in _SHAPE_FAMILY_OPS
            ):
                ops_dispatched.add(node.args[0].id)
        if not ops_dispatched or calls_pad_to_bucket:
            return
        for a in (*fn.args.posonlyargs, *fn.args.args, *fn.args.kwonlyargs):
            ann = a.annotation
            if isinstance(ann, ast.Name) and ann.id.startswith("Staged"):
                return  # consumes a prestaged (already bucketed) batch
            if (
                isinstance(ann, ast.Constant)
                and isinstance(ann.value, str)
                and ann.value.startswith("Staged")
            ):
                return
        self._flag(
            fn, "TD004",
            f"{fn.name!r} dispatches {sorted(ops_dispatched)} without "
            "deriving its batch shape via pad_to_bucket (or consuming a "
            "prestaged Staged* batch) — ad-hoc shapes compile a new "
            "program per distinct batch size",
        )


@register
class TraceDisciplineChecker(Checker):
    name = "trace-discipline"
    description = (
        "jit stays in one-time construction contexts; static/donate "
        "metadata matches the traced function; dispatches are bucketed"
    )

    def run(self, repo: Repo) -> list[Finding]:
        findings: list[Finding] = []
        for sf in repo.files:
            if not sf.is_python or not sf.hot_path or sf.tree is None:
                continue
            v = _Visitor(sf)
            v.visit(sf.tree)
            findings.extend(v.findings)
        return findings
