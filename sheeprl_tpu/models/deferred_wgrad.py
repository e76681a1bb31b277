"""`lax.scan` whose Dense kernels get their gradient after the backward scan.

The transpose of a plain ``lax.scan`` makes the cotangent of every
closed-over parameter a carry of the backward loop: each step adds its own
``x[t]^T . dy[t]`` to a kernel-sized float32 array, so a ``[in, out]`` kernel
is read and written ``T`` times (at DreamerV3-XL the GRU kernel alone is
252 MB, 64 times a step). The sum over time is one matmul.

:func:`scan_deferred_wgrad` runs the same forward scan and gives it a backward
pass of its own:

- the backward scan still runs once, in reverse, and still carries the
  cotangents of the carry and of every other leaf of ``variables`` (norm
  scales, biases, learned initial states: small);
- for each ``nn.Dense`` called in the step it emits that call's output
  cotangent ``dy[t]``, stacked over time, beside the call's input ``x[t]``
  (stacked by the forward scan, or handed back by the caller where the caller
  already holds it: ``given``);
- after the scan each kernel's gradient is one contraction over time and
  batch with float32 accumulation.

How the taps work: the step is traced under ``nn.intercept_methods``; a Dense
whose kernel *is* a leaf of ``variables`` has its output passed through
:func:`_tap` together with a ``[T, ..., out]`` scanned input of zeros.
``_tap`` is the identity on the output and ignores the zeros, so the forward
program is the plain scan's; its backward rule hands the output's cotangent to
the zeros as well, and the cotangent of a scanned input is a stacked output of
the backward scan. The deferred kernels are closed over as constants of that
inner differentiation, so they have no cotangent to carry.

Contract: the step reads a deferred kernel only through its ``nn.Dense`` (a
kernel that is also read some other way would lose that read's gradient).
Kernels read by anything else than ``nn.Dense.__call__`` (the fused Pallas
GRU's ``_DenseParams``) are not deferred and keep the plain accumulation.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.custom_derivatives import CustomVJPPrimal, SymbolicZero

Step = Callable[[Any, Any, Any], Tuple[Any, Any]]


@jax.custom_vjp
def _tap(y: jax.Array, token: jax.Array) -> jax.Array:
    return y


_tap.defvjp(lambda y, token: (y, None), lambda _, g: (g, g))


class DenseCall(NamedTuple):
    """One ``nn.Dense`` call of the step whose kernel is a leaf of ``variables``."""

    leaf: int  # index of the kernel among the leaves of `variables`
    out: jax.ShapeDtypeStruct  # the call's output for one step


class _DenseTaps:
    """The interceptor of one traced step: finds the Dense calls whose kernel
    is one of ``kernels`` (by identity), taps their outputs with ``tokens``
    (when given) and keeps their inputs."""

    def __init__(self, kernels: Dict[int, int], tokens: Optional[List[jax.Array]] = None):
        self.kernels = kernels  # id(kernel leaf) -> leaf index
        self.tokens = tokens
        self.calls: List[DenseCall] = []
        self.inputs: List[jax.Array] = []

    def __call__(self, next_fun, args, kwargs, context):
        y = next_fun(*args, **kwargs)
        if not (isinstance(context.module, nn.Dense) and context.method_name == "__call__"):
            return y
        leaf = self.kernels.get(id(context.module.get_variable("params", "kernel")))
        if leaf is None:
            return y
        if self.tokens is not None:
            y = _tap(y, self.tokens[len(self.calls)])
        self.calls.append(DenseCall(leaf, jax.ShapeDtypeStruct(y.shape, y.dtype)))
        self.inputs.append(args[0])
        return y


def dense_calls(step: Step, variables: Any, carry0: Any, xs: Any) -> List[DenseCall]:
    """The ``nn.Dense`` calls one step makes on kernels that are leaves of
    ``variables``, in call order (an abstract trace: nothing is computed)."""
    found: List[DenseCall] = []

    def one_step(variables, carry0, xs):
        leaves = jax.tree_util.tree_leaves(variables)
        taps = _DenseTaps({id(leaf): i for i, leaf in enumerate(leaves)})
        with nn.intercept_methods(taps):
            out = step(variables, carry0, jax.tree_util.tree_map(lambda x: x[0], xs))
        found.extend(taps.calls)
        return out

    jax.eval_shape(one_step, variables, carry0, xs)
    return found


def scan_deferred_wgrad(
    step: Step,
    variables: Any,
    carry0: Any,
    xs: Any,
    *,
    given: Optional[Dict[str, Callable[[Any, Any], jax.Array]]] = None,
    report: Optional[Callable[[int, int], None]] = None,
) -> Tuple[Any, Any]:
    """``lax.scan(lambda c, x: step(variables, c, x), carry0, xs)`` with the
    gradient of every Dense kernel of ``variables`` that the step applies
    contracted once, after the backward scan (module docstring).

    ``step(variables, carry, x) -> (carry, y)`` applies flax modules to
    ``variables`` (any pytree that holds their parameters). ``given`` maps a
    kernel's name (the keys of its path joined by ``/``, e.g.
    ``params/transition_model/dense_0/kernel``) to ``fn(ys, xs)`` returning the
    ``[T, ..., in]`` input its Dense saw at every step, for a Dense fed by what
    the scan is given or returns anyway: that input is then not stacked a
    second time. ``report(n_kernels, float32_bytes)`` is called once per trace
    with what was deferred.
    """
    given = given or {}
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(variables)
    names = [jax.tree_util.keystr(path, simple=True, separator="/") for path, _ in paths_leaves]
    leaves = [leaf for _, leaf in paths_leaves]
    calls = dense_calls(step, variables, carry0, xs)
    deferred = sorted({c.leaf for c in calls})
    unknown = set(given) - {names[i] for i in deferred}
    if unknown:
        raise ValueError(f"`given` names no Dense kernel applied in the step: {sorted(unknown)}")
    for i in deferred:
        if names[i] in given and sum(c.leaf == i for c in calls) != 1:
            raise ValueError(f"{names[i]} is applied more than once per step: its input cannot be `given`")
    if report is not None:
        report(len(deferred), sum(4 * leaves[i].size for i in deferred))
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    stacked = [names[c.leaf] not in given for c in calls]  # whose input the forward scan stacks

    def merge(kernels, rest):
        full = list(rest)
        for i, kernel in zip(deferred, kernels):
            full[i] = kernel
        return jax.tree_util.tree_unflatten(treedef, full)

    @jax.custom_vjp
    def run(kernels, rest, carry0, xs):
        v = merge(kernels, rest)
        return jax.lax.scan(lambda c, x: step(v, c, x), carry0, xs)

    def fwd(kernels, rest, carry0, xs):
        kernels = [k.value for k in kernels]
        # Differentiate only what the caller differentiates: everything else
        # (keys, actions, masks) gets no backward computation in the scan.
        args = jax.tree_util.tree_map(
            lambda a: a.value if a.perturbed else jax.lax.stop_gradient(a.value),
            (rest, carry0, xs),
            is_leaf=lambda a: isinstance(a, CustomVJPPrimal),
        )
        tokens = [jnp.zeros((length, *c.out.shape), c.out.dtype) for c in calls]

        def tapped(args, tokens):
            rest, carry0, xs = args
            v = merge(kernels, rest)

            def body(c, x_tokens):
                x, step_tokens = x_tokens
                taps = _DenseTaps({id(k): i for i, k in zip(deferred, kernels)}, step_tokens)
                with nn.intercept_methods(taps):
                    c, y = step(v, c, x)
                return c, (y, [x_in for x_in, keep in zip(taps.inputs, stacked) if keep])

            carry, (ys, dense_in) = jax.lax.scan(body, carry0, (xs, tokens))
            return (carry, ys), dense_in

        out, pullback, dense_in = jax.vjp(tapped, args, tokens, has_aux=True)
        return out, (pullback, dense_in, (out[1], args[2]) if given else None)

    def bwd(res, cts):
        pullback, dense_in, ys_xs = res
        cts = jax.tree_util.tree_map(
            lambda ct: jnp.zeros(ct.shape, ct.dtype) if isinstance(ct, SymbolicZero) else ct,
            cts,
            is_leaf=lambda ct: isinstance(ct, SymbolicZero),
        )
        (d_rest, d_carry0, d_xs), d_tokens = pullback(cts)
        stacked_in = iter(dense_in)
        d_kernels: Dict[int, jax.Array] = {}
        for call, keep, dy in zip(calls, stacked, d_tokens):
            x = next(stacked_in) if keep else given[names[call.leaf]](*ys_xs)
            # One contraction over time and batch: the operands the per-step
            # matmul had, float32 accumulation, one rounding.
            dw = jnp.einsum("...i,...o->io", x, dy, preferred_element_type=jnp.float32)
            d_kernels[call.leaf] = d_kernels[call.leaf] + dw if call.leaf in d_kernels else dw
        return [d_kernels[i].astype(leaves[i].dtype) for i in deferred], d_rest, d_carry0, d_xs

    run.defvjp(fwd, bwd, symbolic_zeros=True)
    rest = [None if i in deferred else leaf for i, leaf in enumerate(leaves)]
    return run([leaves[i] for i in deferred], rest, carry0, xs)
