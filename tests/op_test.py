"""Per-op test harness: forward vs numpy reference, analytic vs numeric grads.

≙ reference python/paddle/fluid/tests/unittests/op_test.py (OpTest base with
get_numeric_gradient :29-120, check_output_with_place, check_grad_with_place).
TPU translation: ops lower to jax functions, so the analytic gradient comes
from jax.grad of the lowering and is compared against central finite
differences; the forward is compared against a numpy reference impl.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.framework.registry import LowerCtx, lookup_op


def run_op(op_type: str, inputs: Dict[str, Any], attrs=None, is_test=False,
           seed=0):
    """Run a single op's lowering eagerly. inputs values may be np arrays or
    lists of np arrays (multi-input slots)."""
    opdef = lookup_op(op_type)
    ins = {k: [jnp.asarray(x) for x in (v if isinstance(v, list) else [v])]
           for k, v in inputs.items()}
    ctx = LowerCtx(rng_key=jax.random.PRNGKey(seed), is_test=is_test)
    outs = opdef.lower(ctx, ins, dict(attrs or {}))
    return {k: [np.asarray(x) for x in v] for k, v in outs.items()}


def check_output(op_type: str, inputs: Dict[str, Any],
                 expected: Dict[str, Any], attrs=None, atol=1e-5, rtol=1e-5,
                 is_test=False):
    """Forward check against numpy reference (≙ check_output_with_place)."""
    got = run_op(op_type, inputs, attrs, is_test=is_test)
    for slot, exp in expected.items():
        exp_list = exp if isinstance(exp, list) else [exp]
        assert slot in got, f"{op_type}: missing output slot {slot}"
        for i, e in enumerate(exp_list):
            np.testing.assert_allclose(
                got[slot][i], e, atol=atol, rtol=rtol,
                err_msg=f"{op_type} output {slot}[{i}] mismatch")
    return got


def _numeric_grad(f: Callable[[np.ndarray], float], x: np.ndarray,
                  eps: float) -> np.ndarray:
    """Central finite differences (≙ get_numeric_gradient, op_test.py:29)."""
    g = np.zeros_like(x, dtype=np.float64)
    flat = x.reshape(-1)
    gf = g.reshape(-1)
    for i in range(flat.size):
        orig = flat[i]
        flat[i] = orig + eps
        hi = f(x)
        flat[i] = orig - eps
        lo = f(x)
        flat[i] = orig
        gf[i] = (hi - lo) / (2 * eps)
    return g


def _grad_case(op_type: str, inputs: Dict[str, Any], slot: str, idx: int,
               out_slot: str = "Out", attrs=None, seed=0, reduce_fn=None):
    """-> (f, x0): the lowering's reduced output as a jax function of
    `inputs[slot][idx]` alone (float inputs fed as float32), and that
    input's value in float64."""
    opdef = lookup_op(op_type)
    attrs = dict(attrs or {})
    base = {k: [np.asarray(x, dtype=np.float64 if
                           np.issubdtype(np.asarray(x).dtype, np.floating)
                           else None) for x in
                (v if isinstance(v, list) else [v])]
            for k, v in inputs.items()}
    if reduce_fn is None:
        reduce_fn = jnp.sum

    def f(x):
        ins = {k: [jnp.asarray(np.asarray(v, dtype=np.float32)
                               if np.issubdtype(
                                   np.asarray(v).dtype, np.floating)
                               else v) for v in vs]
               for k, vs in base.items()}
        ins[slot] = list(ins[slot])
        ins[slot][idx] = x
        ctx = LowerCtx(rng_key=jax.random.PRNGKey(seed))
        out = opdef.lower(ctx, ins, attrs)[out_slot][0]
        return reduce_fn(out)

    return f, base[slot][idx]


def _on_host(f):
    """`f` as `_numeric_grad` calls it: float64 host array in, float out."""
    return lambda x: float(f(jnp.asarray(x.astype(np.float32))))


def check_grad(op_type: str, inputs: Dict[str, Any],
               grad_slots: Sequence[str], out_slot: str = "Out",
               attrs=None, eps=1e-3, atol=5e-3, rtol=5e-3, seed=0,
               reduce_fn=None):
    """Compare jax.grad of the lowering against numeric finite differences
    (≙ check_grad_with_place). grad_slots name the input slots to check."""
    for slot in grad_slots:
        n = len(inputs[slot]) if isinstance(inputs[slot], list) else 1
        for idx in range(n):
            f, x0 = _grad_case(op_type, inputs, slot, idx, out_slot, attrs,
                               seed, reduce_fn)
            analytic = np.asarray(
                jax.grad(f)(jnp.asarray(x0.astype(np.float32))),
                dtype=np.float64)
            # ONE executable for the 2*N evaluations: un-jitted, a lowering
            # with a Python loop (the recurrent ops, the CRF, CTC)
            # dispatches every primitive of it twice an input element
            numeric = _numeric_grad(_on_host(jax.jit(f)), x0.copy(), eps)
            np.testing.assert_allclose(
                analytic, numeric, atol=atol, rtol=rtol,
                err_msg=f"{op_type} grad wrt {slot}[{idx}] mismatch")
