"""GL002: host-device sync in jitted code and per-step syncs in host loops.

Two tiers of the same hazard:

* Inside a jit-traced body (`@jax.jit` functions, `lax.scan`/`lax.cond`
  bodies), `.item()`, `float()`/`int()`/`bool()` on a traced value,
  `np.asarray`, and `jax.device_get` either raise a tracer-conversion error
  at trace time or — when they slip through on a leaked concrete value —
  serialize the TPU pipeline on every step. These are definite bugs.

* In host code, `.item()` fetches one scalar per call (a full round
  trip to the device), and `jax.device_get`/`jax.block_until_ready`
  inside a `for`/`while` loop is a per-iteration sync. The fix is coalescing:
  keep metrics device-resident and do ONE `jax.device_get` per log interval.
  Structurally necessary per-step transfers (actions feeding `env.step`)
  carry an explicit `# graftlint: disable=GL002` with a justifying comment.

The host-side tier is what the train-loop burn-down tracks in the baseline:
its count may only decrease.
"""

from __future__ import annotations

import ast
from typing import Dict, Set

from sheeprl_tpu.analysis.context import LintContext
from sheeprl_tpu.analysis.registry import Rule, register_rule

_HOST_FETCH_CALLS = {
    "numpy.asarray": "numpy.asarray",
    "numpy.array": "numpy.array",
    "jax.device_get": "jax.device_get",
}
_SCALAR_BUILTINS = {"float", "int", "bool"}
_LOOP_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}


@register_rule
class HostSyncRule(Rule):
    id = "GL002"
    name = "host-sync"
    rationale = (
        "Host<->device transfers inside traced code break tracing; per-step "
        "transfers in host loops serialize the device pipeline."
    )
    hazard = (
        "@jax.jit\n"
        "def step(x):\n"
        "    return float(x.mean())  # device->host sync inside the trace"
    )

    def check(self, ctx: LintContext) -> None:
        jit_nodes = self._check_jit_bodies(ctx)
        self._check_host_code(ctx, jit_nodes)

    # ------------------------------------------------------ definite: in-jit
    def _check_jit_bodies(self, ctx: LintContext) -> Set[int]:
        jit_nodes: Set[int] = set()
        for jf, body in ctx.iter_jit_bodies():
            traced = jf.traced_params()
            for node in ast.walk(body):
                jit_nodes.add(id(node))
                if not isinstance(node, ast.Call):
                    continue
                if isinstance(node.func, ast.Attribute) and node.func.attr == "item" and not node.args:
                    ctx.report(
                        self.id,
                        node,
                        f"`.item()` inside jit-traced `{jf.name}` forces a "
                        "device->host sync; return the array and fetch it "
                        "outside the jit",
                    )
                    continue
                path = ctx.resolver.resolve(node.func)
                if path in _HOST_FETCH_CALLS:
                    ctx.report(
                        self.id,
                        node,
                        f"`{_HOST_FETCH_CALLS[path]}` inside jit-traced "
                        f"`{jf.name}` materializes the value on host; use "
                        "jnp ops in-graph and transfer after the call",
                    )
                    continue
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in _SCALAR_BUILTINS
                    and node.func.id not in ctx.resolver.aliases
                    and len(node.args) == 1
                    and isinstance(node.args[0], ast.Name)
                    and node.args[0].id in traced
                ):
                    ctx.report(
                        self.id,
                        node,
                        f"`{node.func.id}()` on traced parameter "
                        f"`{node.args[0].id}` of `{jf.name}` is a concretization "
                        "sync; keep it a jnp scalar or mark the parameter static",
                    )
        return jit_nodes

    # ------------------------------------------------- hazard: host hot path
    def _check_host_code(self, ctx: LintContext, jit_nodes: Set[int]) -> None:
        in_loop = _loop_membership(ctx.tree)
        for node in ast.walk(ctx.tree):
            if id(node) in jit_nodes or not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute) and node.func.attr == "item" and not node.args:
                ctx.report(
                    self.id,
                    node,
                    "host-side `.item()` fetches one scalar per call (a full "
                    "device round trip on jax arrays); batch values and fetch "
                    "once with jax.device_get",
                )
                continue
            path = ctx.resolver.resolve(node.func)
            if path in _LOOP_SYNC_CALLS and in_loop.get(id(node), False):
                short = path.rsplit(".", 1)[1]
                ctx.report(
                    self.id,
                    node,
                    f"`{short}` inside a host loop syncs the device every "
                    "iteration; keep values device-resident and coalesce into "
                    "one transfer per log interval",
                )


def _loop_membership(tree: ast.Module) -> Dict[int, bool]:
    """id(node) -> whether the node sits inside a for/while loop body."""
    membership: Dict[int, bool] = {}

    def visit(node: ast.AST, in_loop: bool) -> None:
        for child in ast.iter_child_nodes(node):
            child_in_loop = in_loop or isinstance(node, (ast.For, ast.AsyncFor, ast.While))
            # A nested function redefines the hot path: its body is only
            # "in a loop" if the loop is inside the function itself.
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                child_in_loop = False
            membership[id(child)] = child_in_loop
            visit(child, child_in_loop)

    membership[id(tree)] = False
    visit(tree, False)
    return membership
