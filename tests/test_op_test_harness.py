"""`op_test.check_grad` differences ONE compiled function (tests/op_test.py):
compiled or eager, the difference quotient it hands the comparison is the
same number up to what float32 rounding of the function's value allows,
which is far inside the comparison's tolerance."""

import jax
import numpy as np
import pytest

import op_test

EPS, ATOL = 1e-3, 5e-3            # check_grad's defaults

CASES = {
    # reductions that XLA may fuse and order differently than op by op
    "layer_norm": dict(
        inputs=lambda r: {"X": r.uniform(0.2, 2.0, (3, 4)),
                          "Scale": r.uniform(0.2, 2.0, (4,)),
                          "Bias": r.uniform(0.2, 2.0, (4,))},
        attrs={"epsilon": 1e-5, "begin_norm_axis": 1}, slot="X",
        out_slot="Y"),
    # a Python loop over time: the kind of lowering the compile is for
    "dynamic_gru": dict(
        inputs=lambda r: {"Input": r.uniform(-1.0, 1.0, (1, 2, 6)),
                          "Weight": r.uniform(-1.0, 1.0, (2, 6)),
                          "SeqLen": np.array([2], "int32")},
        attrs={}, slot="Input", out_slot="Hidden"),
}


@pytest.mark.parametrize("op", sorted(CASES))
def test_the_compiled_quotient_is_the_eager_one(op):
    case = CASES[op]
    inputs = case["inputs"](np.random.RandomState(0))
    f, x0 = op_test._grad_case(op, inputs, case["slot"], 0,
                               out_slot=case["out_slot"],
                               attrs=case["attrs"])
    eager = op_test._numeric_grad(op_test._on_host(f), x0.copy(), EPS)
    compiled = op_test._numeric_grad(op_test._on_host(jax.jit(f)),
                                     x0.copy(), EPS)
    assert np.abs(eager).max() > 10 * ATOL      # a gradient to speak of
    # two evaluations, each within an ulp of float32 at the function's
    # value, over 2 * eps
    value = abs(op_test._on_host(f)(x0))
    bound = 2 * np.spacing(np.float32(value)) / (2 * EPS)
    assert np.abs(compiled - eager).max() <= bound
    assert bound < ATOL / 2
    assert x0.dtype == np.float64 and compiled.dtype == np.float64


def test_check_grad_still_refuses_a_wrong_gradient(monkeypatch):
    """The harness compares what it computes: with the analytic side
    doubled the same call fails."""
    inputs = CASES["layer_norm"]["inputs"](np.random.RandomState(0))
    kw = dict(out_slot="Y", attrs=CASES["layer_norm"]["attrs"],
              reduce_fn=lambda o: (o * np.cos(np.arange(o.size,
                                                        dtype=np.float32)
                                              ).reshape(o.shape)).sum())
    op_test.check_grad("layer_norm", inputs, ["X", "Scale"], **kw)
    grad = jax.grad
    monkeypatch.setattr(jax, "grad",
                        lambda f: lambda x: 2.0 * grad(f)(x))
    with pytest.raises(AssertionError, match="layer_norm grad wrt X"):
        op_test.check_grad("layer_norm", inputs, ["X"], **kw)
