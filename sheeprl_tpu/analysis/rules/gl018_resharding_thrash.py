"""GL018: resharding thrash — producer and consumer disagree on a value's
sharding, so every step pays a hidden cross-device reshuffle.

``jax.jit(..., in_shardings=...)`` does not *check* an argument's layout;
it silently **reshards** to the requested one. When a buffer is produced
under ``NamedSharding(mesh, P("data"))`` and the train step declares
``in_shardings=P("model")`` (or a stale spec after a mesh refactor), each
call inserts an all-to-all the profiler attributes to "infeed" and no
error ever surfaces — the classic goodput sink roofline accounting cannot
see past. The disagreement is fully static: both
sides are written down as ``PartitionSpec`` literals in the same program.

Analysis (project-wide, on the :mod:`~sheeprl_tpu.analysis.meshmodel`):

* **producers** — within each function/module scope, names assigned from
  ``jax.device_put(x, <sharding>)`` or ``with_sharding_constraint(x,
  <sharding>)`` whose sharding resolves to a static spec (``NamedSharding``
  wrappers and module-level spec aliases are dereferenced). A later
  non-sharding reassignment drops the tracking.
* **consumers** — jit-decorated/wrapped functions whose ``in_shardings=``
  (captured on :class:`~sheeprl_tpu.analysis.context.JitFunction`) parses
  to static specs, positionally aligned with the function's parameters; a
  single non-tuple spec broadcasts to every argument, mirroring jax.
* **flag** — a call passing a tracked name into a consumer position whose
  specs disagree after normalization (trailing ``None`` entries are
  equivalent). An explicit ``device_put`` to the consumer's spec before
  the call simply retracks the name and silences the finding — that *is*
  the sanctioned fix when the transfer is intentional.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from sheeprl_tpu.analysis.dataflow import walk_scope
from sheeprl_tpu.analysis.meshmodel import (
    Spec,
    format_spec,
    mesh_model,
    normalize_spec,
    spec_is_static,
)
from sheeprl_tpu.analysis.project import AnalysisContext, ModuleInfo
from sheeprl_tpu.analysis.registry import ProjectRule, register_rule

_PUT_PATHS = {"jax.device_put"}
_CONSTRAINT_PATHS = {
    "jax.lax.with_sharding_constraint",
    "jax.experimental.pjit.with_sharding_constraint",
}


@register_rule
class ReshardingThrashRule(ProjectRule):
    id = "GL018"
    name = "resharding-thrash"
    rationale = (
        "A value produced under one NamedSharding is consumed by a jit "
        "whose in_shardings disagrees: jax silently reshards on every "
        "call, paying a hidden cross-device transfer each step."
    )
    hazard = (
        'batch = jax.device_put(batch, NamedSharding(mesh, P("data")))\n'
        '@partial(jax.jit, in_shardings=(P("model"),))  # disagreement\n'
        "def train_step(batch): ...                     # resharded every call"
    )

    def check_project(self, actx: AnalysisContext) -> None:
        model = mesh_model(actx)
        consumers = self._jit_consumers(actx, model)
        if not consumers:
            return
        for info, sym in actx.iter_functions():
            self._check_scope(actx, model, info, sym.node, consumers, enclosing=sym)
        for info in actx.modules:
            self._check_scope(actx, model, info, info.ctx.tree, consumers, enclosing=None)

    # --------------------------------------------------------------- consumers
    def _jit_consumers(self, actx: AnalysisContext, model):
        """SymbolKey -> (positional param names, spec per position).

        A single non-tuple in_shardings broadcasts: the spec list holds one
        entry reused for every position (mirrored by ``_spec_at``)."""
        consumers: Dict[object, Tuple[List[str], List[Optional[Spec]], bool]] = {}
        for info in actx.modules:
            by_node = {id(sym.node): sym for sym in info.symbols.values()}
            for jf in info.ctx.jitted_functions():
                if jf.in_shardings is None:
                    continue
                sym = by_node.get(id(jf.node))
                if sym is None:
                    continue
                args = jf.node.args
                params = [a.arg for a in args.posonlyargs + args.args]
                node = jf.in_shardings
                if isinstance(node, (ast.Tuple, ast.List)):
                    specs = [model.parse_spec(e, info) for e in node.elts]
                    broadcast = False
                else:
                    specs = [model.parse_spec(node, info)]
                    broadcast = True
                if any(s is not None for s in specs):
                    consumers[sym.key] = (params, specs, broadcast)
        return consumers

    # ---------------------------------------------------------------- per-scope
    def _check_scope(self, actx, model, info: ModuleInfo, scope, consumers, enclosing):
        events = self._scope_events(actx, model, info, scope, consumers, enclosing)
        tracked: Dict[str, Tuple[Spec, int]] = {}
        for lineno, kind, payload in sorted(events, key=lambda e: e[0]):
            if kind == "assign":
                names, spec = payload
                for name in names:
                    if spec is not None and spec_is_static(spec):
                        tracked[name] = (normalize_spec(spec), lineno)
                    else:
                        tracked.pop(name, None)
                continue
            call, key = payload
            params, specs, broadcast = consumers[key]
            for idx, arg in enumerate(call.args):
                if not isinstance(arg, ast.Name) or arg.id not in tracked:
                    continue
                want = self._spec_at(specs, idx, broadcast)
                if want is None or not spec_is_static(want):
                    continue
                want = normalize_spec(want)
                have, have_line = tracked[arg.id]
                if have == want:
                    continue
                pname = params[idx] if idx < len(params) else f"arg {idx}"
                info.ctx.report(
                    self.id,
                    call,
                    f"`{arg.id}` is placed with {format_spec(have)} (line "
                    f"{have_line}) but `{key.qualname}` declares "
                    f"in_shardings {format_spec(want)} for `{pname}`: jit "
                    "silently reshards it on every call — align the specs, "
                    "or device_put to the consumer's sharding once, "
                    "outside the step loop",
                )

    def _scope_events(self, actx, model, info, scope, consumers, enclosing):
        events: List[Tuple[int, str, object]] = []
        for node in walk_scope(scope):
            if isinstance(node, ast.Assign):
                names = [
                    n.id
                    for t in node.targets
                    for n in ast.walk(t)
                    if isinstance(n, ast.Name)
                ]
                if names:
                    spec = self._placement_spec(model, info, node.value)
                    events.append((node.lineno, "assign", (names, spec)))
            elif isinstance(node, ast.Call):
                callee = actx.resolve_call(info, node, enclosing=enclosing)
                if callee is not None and callee.key in consumers:
                    events.append((node.lineno, "call", (node, callee.key)))
        return events

    def _placement_spec(self, model, info, value: ast.AST) -> Optional[Spec]:
        """Spec when `value` is device_put/with_sharding_constraint with a
        statically-parsable sharding, else None (which drops tracking)."""
        if not isinstance(value, ast.Call):
            return None
        path = info.ctx.resolver.resolve(value.func)
        if path not in _PUT_PATHS | _CONSTRAINT_PATHS:
            return None
        sharding_node: Optional[ast.AST] = None
        if len(value.args) >= 2:
            sharding_node = value.args[1]
        for kw in value.keywords:
            if kw.arg in ("device", "shardings"):
                sharding_node = kw.value
        if sharding_node is None:
            return None
        return model.parse_spec(sharding_node, info)

    @staticmethod
    def _spec_at(specs: List[Optional[Spec]], idx: int, broadcast: bool):
        if broadcast:
            return specs[0]
        return specs[idx] if idx < len(specs) else None
