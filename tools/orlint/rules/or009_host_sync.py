"""OR009: device→host sync on a hot path.

Scope: the kernel-adjacent modules (``ops/``, ``parallel/``,
``decision/``). A host materialization waits for the whole dispatch
queue and serializes the dispatch pipeline;
the kernels are designed so each solve ends in exactly ONE packed
transfer (ops/spf_split.py). What this rule hunts is the *per-iteration*
sync — the pattern that turns an O(1)-transfer solve into an
O(rounds)-round-trip one:

  * ``.item()`` anywhere in scope — a scalar readback; on a hot path it
    blocks on the whole dispatch queue.
  * ``.block_until_ready()`` anywhere in scope — a timing/bench
    primitive; production code must let transfers (np.asarray at the
    seam) do the synchronizing. Benchmarks live outside this rule's
    scope and keep using it.
  * ``int()/bool()/float()`` inside a loop on a value produced by a call
    in that same loop — the classic read-back-per-sweep host loop.
  * ``np.asarray(...)`` inside a loop with no kernel dispatch in the
    same loop — a transfer per iteration with nothing pipelined against
    it. Loops that also dispatch (the double-buffered chunk pipeline in
    ``decision/fleet.py``) overlap the previous chunk's transfer with
    the current chunk's compute and are deliberately allowed.

Fix patterns: fuse the loop into the kernel (``lax.while_loop`` — how
spf_split keeps its whole fixpoint on device), return packed outputs
and decode host-side once, or move the decision the scalar feeds onto
the device. A deliberate readback (e.g. the interpreter-only Pallas
reference kernel) carries an inline suppression with the reasoning.
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.orlint import Finding, ModuleCtx, Rule
from tools.orlint.astutil import dotted_name, walk_in_scope
from tools.orlint.jaxutil import collect_jit_registry

SCOPE_DIRS = ("ops", "parallel", "decision")

#: callee-name substrings that mark a loop as a dispatch pipeline
#: (chunked transfer overlapped with compute) in addition to the
#: project jit registry
_DISPATCH_TOKENS = ("solve", "sssp", "relax", "kernel", "dispatch")

_SCALARIZERS = frozenset({"int", "bool", "float"})


def _in_scope(ctx: ModuleCtx) -> bool:
    return bool(ctx.part_set() & set(SCOPE_DIRS))


def _loops(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
            yield node


def _call_bound_names(loop: ast.AST) -> dict[str, ast.Call]:
    """{name: producing call} for names assigned (incl. tuple targets)
    from a Call inside the loop body's own scope."""
    out: dict[str, ast.Call] = {}

    def bind(tgt: ast.AST, call: ast.Call):
        if isinstance(tgt, ast.Name):
            out[tgt.id] = call
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for e in tgt.elts:
                bind(e, call)

    for n in walk_in_scope(loop):
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
            for t in n.targets:
                bind(t, n.value)
    return out


class HostSyncRule(Rule):
    code = "OR009"
    name = "host-sync"
    description = (
        "per-iteration device→host sync (.item/int()/np.asarray/"
        "block_until_ready) in kernel-path code"
    )

    # ------------------------------------------------------------ per-file

    def check(self, ctx: ModuleCtx) -> Iterable[Finding]:
        if not _in_scope(ctx):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            is_method = (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            )
            if is_method or (
                dotted_name(node.func) == "jax.block_until_ready"
            ):
                yield self.finding(
                    ctx,
                    node,
                    "block_until_ready() in production kernel code "
                    "— a timing primitive; let the seam's transfer "
                    "synchronize (benches are outside this scope)",
                    subject=f"block_until_ready:{node.lineno}",
                )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "item"
                and not node.args
            ):
                yield self.finding(
                    ctx,
                    node,
                    ".item() scalar readback on the kernel path — "
                    "blocks on the dispatch queue; keep the value on "
                    "device or read it once at the transfer seam",
                    subject=f"item:{node.lineno}",
                )

    # ------------------------------------------------------ whole-project

    def finalize(self, ctxs, root: str) -> Iterable[Finding]:
        """The per-iteration sync checks: both need the cross-file jit
        registry to know what a kernel dispatch looks like."""
        jit_names = set(collect_jit_registry(ctxs))
        for ctx in ctxs:
            if not _in_scope(ctx):
                continue
            for loop in _loops(ctx.tree):
                produced = _call_bound_names(loop)
                calls = [
                    n for n in walk_in_scope(loop)
                    if isinstance(n, ast.Call)
                ]
                for n in calls:
                    dn = dotted_name(n.func)
                    if (
                        dn in _SCALARIZERS
                        and len(n.args) == 1
                        and isinstance(n.args[0], ast.Name)
                        and self._is_dispatch(
                            produced.get(n.args[0].id), jit_names
                        )
                    ):
                        yield self.finding(
                            ctx,
                            n,
                            f"{dn}({n.args[0].id}) inside a loop on a "
                            f"kernel result computed in that loop — a "
                            f"device→host readback per iteration; fuse "
                            f"the loop into the kernel (lax.while_loop) "
                            f"or batch the readback",
                            subject=f"{dn}:{n.args[0].id}",
                        )
                if any(self._is_dispatch(c, jit_names) for c in calls):
                    continue  # pipelined chunk loop: transfer overlaps
                for c in calls:
                    dn = dotted_name(c.func) or ""
                    if dn in ("np.asarray", "numpy.asarray"):
                        yield self.finding(
                            ctx,
                            c,
                            "np.asarray() transfer inside a loop that "
                            "dispatches no kernel — a blocking "
                            "device→host copy per iteration with no "
                            "compute overlapped; hoist the transfer out "
                            "of the loop or pipeline it against the "
                            "next dispatch",
                            subject=f"asarray:{c.lineno}",
                        )

    @staticmethod
    def _is_dispatch(call: ast.Call | None, jit_names: set[str]) -> bool:
        if call is None:
            return False
        dn = dotted_name(call.func) or ""
        last = dn.rsplit(".", 1)[-1]
        return last in jit_names or any(
            tok in last for tok in _DISPATCH_TOKENS
        )
