"""OR010: recompile hazard at a jit call site.

A jitted kernel recompiles whenever a *static* argument takes a value
it has never seen or a *traced* argument arrives with a new shape. Both
are invisible locally — the call site looks identical, the first call
works, and the cost only shows up as a compile storm under churn
(a rebuild stalled for a whole compile, once per variant). The
codebase's defense is
quantization: every jit-facing capacity goes through a bucket helper
(``pad_batch``/``pad_bucket`` power-of-two buckets, ``tight_nodes``
node grid, the ``pick_*`` selectors with small fixed codomains —
ops/spf_split.py, common/util.py), so the variant count is
O(log churn), not O(churn). This rule cross-checks call sites of every
project-jitted entry point against that discipline:

  * a **static argument** must be stable: a literal, config attribute,
    module constant, or an expression visibly routed through a bucket
    helper. ``k=len(jobs)`` is the canonical violation — one compile
    per distinct job count.
  * a **traced argument** built by an ``np.array/full/empty/arange/
    resize`` whose size expression references per-call-varying names
    with no bucket-stable name anywhere in reach is an unpadded
    shape-varying feed — one compile per distinct size.

The fix is never to suppress: route the size through
``pad_batch``/``tight_nodes`` (padding slots are dead by construction
in every kernel here) or hoist the value into a static with a bounded
codomain.
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.orlint import Finding, ModuleCtx, Rule
from tools.orlint.astutil import dotted_name, walk_in_scope
from tools.orlint.jaxutil import (
    JitInfo,
    collect_jit_registry,
    expr_has_bucket_token,
)

#: np constructors whose first argument is a size/content that fixes
#: the produced array's shape
_NP_CTORS = frozenset(
    {
        "np.array",
        "np.asarray",
        "np.full",
        "np.zeros",
        "np.ones",
        "np.empty",
        "np.arange",
        "np.resize",
        "numpy.array",
        "numpy.full",
        "numpy.zeros",
        "numpy.empty",
        "numpy.arange",
    }
)

#: calls considered stable when their arguments are stable
_STABLE_CALLS = frozenset({"min", "max", "int", "abs", "round"})


class _FnIndex:
    """Per-function single-pass assignment index: {name: [value exprs]}."""

    def __init__(self, fn: ast.AST):
        self.assigns: dict[str, list[ast.AST]] = {}
        for node in walk_in_scope(fn):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    self._bind(tgt, node.value)
            elif isinstance(node, ast.AugAssign) and isinstance(
                node.target, ast.Name
            ):
                self.assigns.setdefault(node.target.id, []).append(
                    node.value
                )

    def _bind(self, tgt: ast.AST, value: ast.AST) -> None:
        if isinstance(tgt, ast.Name):
            self.assigns.setdefault(tgt.id, []).append(value)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for e in tgt.elts:
                # tuple unpack: conservatively attribute the whole RHS
                self._bind(e, value)


def _enclosing_functions(tree: ast.Module):
    """(fn_node) for every function, plus the module itself for
    module-level call sites."""
    yield tree
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class RecompileHazardRule(Rule):
    code = "OR010"
    name = "recompile-hazard"
    description = (
        "per-call-varying static arg / unpadded shape-varying feed at a "
        "jitted call site"
    )

    # all work happens in finalize: the jit registry spans files
    def finalize(self, ctxs, root: str) -> Iterable[Finding]:
        registry = collect_jit_registry(ctxs)
        if not registry:
            return
        for ctx in ctxs:
            if "tools" in ctx.part_set():
                continue
            for fn in _enclosing_functions(ctx.tree):
                idx = _FnIndex(fn)
                scope = getattr(fn, "name", "<module>")
                # in-scope walk only: call sites in nested defs are
                # checked by their own iteration, against their own
                # assignment index
                for node in walk_in_scope(fn):
                    if isinstance(node, ast.Call):
                        yield from self._check_site(
                            ctx, scope, idx, registry, node
                        )

    # ---------------------------------------------------------- call sites

    def _check_site(self, ctx, scope, idx, registry, call: ast.Call):
        dn = dotted_name(call.func) or ""
        name = dn.rsplit(".", 1)[-1]
        info = registry.get(name)
        if info is None or not dn:
            return
        if call.lineno == info.node.lineno:
            return
        static_pos = self._static_positions(info)
        bounded = self._bounded_statics(info)
        for i, arg in enumerate(call.args):
            if isinstance(arg, ast.Starred):
                return  # arity unknown past a *splat
            if i in static_pos:
                if static_pos[i] not in bounded:
                    yield from self._check_static(
                        ctx, scope, idx, name, static_pos[i], arg
                    )
            else:
                yield from self._check_traced(
                    ctx, scope, idx, name, arg
                )
        for kw in call.keywords:
            if kw.arg is None:
                continue
            if kw.arg in info.static_argnames:
                if kw.arg not in bounded:
                    yield from self._check_static(
                        ctx, scope, idx, name, kw.arg, kw.value
                    )
            else:
                yield from self._check_traced(
                    ctx, scope, idx, name, kw.value
                )

    @staticmethod
    def _static_positions(info: JitInfo) -> dict[int, str]:
        args = info.node.args
        pos = [*args.posonlyargs, *args.args]
        return {
            i: a.arg
            for i, a in enumerate(pos)
            if a.arg in info.static_argnames
        }

    @staticmethod
    def _bounded_statics(info: JitInfo) -> frozenset[str]:
        """Static params whose codomain is bounded by declaration — a
        `bool` annotation or bool default can take two values and never
        storms the cache, whatever expression feeds it."""
        args = info.node.args
        pos = [*args.posonlyargs, *args.args, *args.kwonlyargs]
        defaults = [
            *([None] * (len([*args.posonlyargs, *args.args])
                        - len(args.defaults))),
            *args.defaults,
            *args.kw_defaults,
        ]
        out = set()
        for a, d in zip(pos, defaults):
            ann_bool = (
                isinstance(a.annotation, ast.Name)
                and a.annotation.id == "bool"
            )
            dflt_bool = isinstance(d, ast.Constant) and isinstance(
                d.value, bool
            )
            if ann_bool or dflt_bool:
                out.add(a.arg)
        return frozenset(out)

    def _check_static(self, ctx, scope, idx, callee, argname, expr):
        if not self._stable(idx, expr, set()):
            yield self.finding(
                ctx,
                expr,
                f"static arg {argname}= of jitted {callee}() fed a "
                f"per-call-varying value — every distinct value is a "
                f"full recompile; bucket it (pad_batch/pick_* family) "
                f"or bound its codomain",
                scope=scope,
                subject=f"static:{callee}:{argname}",
            )

    def _check_traced(self, ctx, scope, idx, callee, expr):
        # unwrap jnp.asarray(X) — the transfer wrapper at every call site
        target = expr
        dn = dotted_name(getattr(expr, "func", ast.Constant(value=0)))
        if (
            isinstance(expr, ast.Call)
            and dn in ("jnp.asarray", "jnp.array")
            and expr.args
        ):
            target = expr.args[0]
        if not isinstance(target, ast.Name):
            return
        hazard = self._unbucketed_ctor(idx, target.id)
        if hazard is not None:
            yield self.finding(
                ctx,
                expr,
                f"traced arg {target.id!r} of jitted {callee}() is built "
                f"by {hazard} with a per-call-varying size and no "
                f"padding bucket in reach — one compile per distinct "
                f"shape; pad through pad_batch/tight_nodes (padding "
                f"slots are dead by kernel construction)",
                scope=scope,
                subject=f"shape:{callee}:{target.id}",
            )

    # ---------------------------------------------------------- stability

    def _stable(self, idx: _FnIndex, expr: ast.AST, seen: set[str]) -> bool:
        if isinstance(expr, ast.Constant):
            return True
        if isinstance(expr, ast.Attribute):
            return True  # config/module attributes: stable per topology
        if isinstance(expr, ast.Name):
            if expr.id.isupper() or expr.id in ("None", "True", "False"):
                return True
            if expr.id in seen:
                return True
            assigns = idx.assigns.get(expr.id)
            if not assigns:
                return True  # parameter / global: caller's contract
            seen = seen | {expr.id}
            return all(self._stable(idx, a, seen) for a in assigns)
        if isinstance(expr, ast.Call):
            if expr_has_bucket_token(expr.func):
                return True
            dn = dotted_name(expr.func) or ""
            if dn == "bool":
                return True  # two values can't storm the cache
            if dn in _STABLE_CALLS:
                return all(
                    self._stable(idx, a, seen) for a in expr.args
                )
            return False
        if isinstance(expr, ast.BinOp):
            return self._stable(idx, expr.left, seen) and self._stable(
                idx, expr.right, seen
            )
        if isinstance(expr, ast.UnaryOp):
            return self._stable(idx, expr.operand, seen)
        if isinstance(expr, ast.IfExp):
            return self._stable(idx, expr.body, seen) and self._stable(
                idx, expr.orelse, seen
            )
        if isinstance(expr, ast.Compare):
            return True  # bool-valued: bounded codomain
        if isinstance(expr, (ast.Tuple, ast.List)):
            return all(self._stable(idx, e, seen) for e in expr.elts)
        if isinstance(expr, ast.Subscript):
            # a constant key is a field access (t["vp"], shape[0]):
            # stable like an Attribute; a varying index inherits the
            # container's stability
            if isinstance(expr.slice, ast.Constant):
                return True
            return self._stable(idx, expr.value, seen)
        return False

    # ------------------------------------------------------ shape hazards

    def _unbucketed_ctor(self, idx: _FnIndex, name: str) -> str | None:
        """The np-ctor description if `name` is only ever built by an
        np constructor whose size expression is per-call-varying with no
        bucket-stable name in reach; None when fine/unknown."""
        assigns = idx.assigns.get(name)
        if not assigns:
            return None
        hazard = None
        for value in assigns:
            if not isinstance(value, ast.Call):
                return None  # some other producer: out of our depth
            dn = dotted_name(value.func) or ""
            if dn not in _NP_CTORS:
                return None
            size = value.args[0] if value.args else None
            if size is None or self._size_ok(idx, size):
                continue
            hazard = f"{dn}()"
        return hazard

    def _size_ok(self, idx: _FnIndex, size: ast.AST) -> bool:
        """A size expression passes when it is constant-stable or any
        name it references is bucket-stable (the visible-padding rule:
        `rows_all + [pad] * (nb - n)` passes because nb came from
        pad_batch)."""
        if expr_has_bucket_token(size):
            return True
        if self._stable(idx, size, set()):
            return True
        for n in ast.walk(size):
            if isinstance(n, ast.Name):
                for a in idx.assigns.get(n.id, []):
                    if expr_has_bucket_token(a):
                        return True
        return False
