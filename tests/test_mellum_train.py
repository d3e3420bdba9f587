"""The spec'd block on the TRAINING graph (`transformer_lm(model=spec)`): a
window and a rotation a kind of layer, grouped heads, softmax-routed experts
with a gradient and a balance term, held to the benchmark's plain reference
(benchmark/models/mellum_reference.py) in float32 at tiny widths: 4 layers
S S S F, window 8 over T = 32, 8 heads over 2, 8 experts top-2, two ranks of
4.

Tolerances: float32 both sides, so what separates program and reference is
the order of float32 sums: logits and loss within 1e-4 relative (measured
<= 1e-6), every parameter's gradient within 1e-3 of its norm (measured
<= 1e-5): a wrong mask, a wrong rotation, a missing term or bfloat16
operands read 1e-2 and more.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import flags
from paddle_tpu.fusion import moe
from paddle_tpu.models import transformer
from paddle_tpu.models.decoder_spec import DecoderSpec, MoESpec, RopeSpec
from paddle_tpu.ops import pallas_kernels as pk

from benchmark.models import mellum

T, B = 32, 2
S = jax.ShapeDtypeStruct
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
TINY = dict(
    model="mellum", hidden_size=64, intermediate_size=128,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, num_hidden_layers=4, num_layers=4,
    sliding_window=8,
    rope_parameters={"full_attention": YARN,
                     "sliding_attention": {"rope_type": "default",
                                           "rope_theta": 500000}},
    num_experts=4, router_width=8, expert_rank=0, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True, rms_norm_eps=1e-6,
    vocab=96, vocab_size=96, aux_coef=0.01, qk_init_gain=2.0,
    matmul_dtype="float32")     # the tests run with the bf16 switch off
LOSS_TOL, GRAD_TOL = 1e-4, 1e-3


@contextlib.contextmanager
def float32_matmuls():
    was = flags.get_flag("use_bf16_matmul")
    flags.set_flag("use_bf16_matmul", False)
    try:
        yield
    finally:
        flags.set_flag("use_bf16_matmul", was)


def _feed(seed=0, vocab=TINY["vocab"]):
    t = np.random.default_rng(seed).integers(0, vocab, (B, T + 1))
    t = t.astype("int64")
    return {"tokens": t[:, :-1].copy(),
            "tokens@SEQLEN": np.full((B,), T, "int32"),
            "targets": t[:, 1:].copy()}


def _build(cfg, lr=1e-3, seed=7):
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss = mellum.build_train(cfg, {"seq_len": T})
        pt.optimizer.AdamOptimizer(learning_rate=lr).minimize(loss)
    pt.default_startup_program().random_seed = seed
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return exe, loss


@pytest.fixture(scope="module")
def step():
    """One float32 step of the tiny configuration: (cfg, names, the weights
    it started from, the feed, loss, gradients by name, the weights after,
    the counters)."""
    with float32_matmuls():
        exe, loss = _build(TINY)
        scope, names = pt.global_scope(), mellum.param_names(TINY)
        before = {n: np.asarray(scope.get(n)) for n in names}
        feed = _feed()
        out = exe.run(feed=feed,
                      fetch_list=[loss] + [n + "@GRAD" for n in names])
        after = {n: np.asarray(scope.get(n)) for n in names}
        counters = mellum.counters(TINY)
    return dict(cfg=TINY, names=names, before=before, feed=feed, scope=scope,
                loss=float(out[0]), after=after, counters=counters,
                grads=dict(zip(names, (np.asarray(g) for g in out[1:]))))


@pytest.fixture(scope="module")
def reference(step):
    with jax.default_matmul_precision("highest"):
        return mellum.reference_grads(step["cfg"], step["before"],
                                      {"feed": step["feed"]})


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                 / (np.linalg.norm(np.asarray(b)) + 1e-30))


def test_the_loss_is_the_references(step, reference):
    assert abs(step["loss"] - reference[0]) <= LOSS_TOL * abs(reference[0])


@pytest.mark.parametrize("name", mellum.param_names(TINY))
def test_every_parameters_gradient_is_the_references(step, reference, name):
    assert np.linalg.norm(np.asarray(reference[1][name])) > 0
    assert _rel(step["grads"][name], reference[1][name]) <= GRAD_TOL, name


def test_the_logits_are_the_references():
    with float32_matmuls():
        pt.reset_default_programs()
        pt.reset_global_scope()
        with pt.core.unique_name.guard():
            _, logits = transformer.transformer_lm(
                max_len=T, model=mellum.spec_of(TINY))
        pt.default_startup_program().random_seed = 3
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        feed = _feed(5)
        got = exe.run(feed=feed, fetch_list=[logits])[0]
        params = {n: jnp.asarray(pt.global_scope().get(n))
                  for n in mellum.param_names(TINY)}
    with jax.default_matmul_precision("highest"):
        for row in range(B):
            want, _, _ = mellum.ref.forward(
                params, jnp.asarray(feed["tokens"][row], jnp.int32), TINY)
            assert _rel(got[row], want) <= LOSS_TOL


@pytest.mark.parametrize("fault", ["window_as_full", "full_as_window",
                                   "plain_rope_on_full", "no_balance_term",
                                   "norm_over_held"])
def test_a_planted_fault_fails_a_tolerance(step, fault):
    """A reference that lacks one mechanism is off the program by more than
    the loss's or a gradient's tolerance: the comparison sees each."""
    with mellum.planted(fault, step["cfg"]) as cfg, \
            jax.default_matmul_precision("highest"):
        loss, grads = mellum.reference_grads(cfg, step["before"],
                                             {"feed": step["feed"]})
    worst = max(_rel(step["grads"][n], grads[n]) for n in step["names"])
    assert (abs(step["loss"] - loss) > LOSS_TOL * abs(loss)
            or worst > GRAD_TOL), (fault, worst)


def test_one_precision_down_fails_a_tolerance(step):
    with mellum.one_precision_below(step["cfg"]) as cfg:
        loss, grads = mellum.reference_grads(cfg, step["before"],
                                             {"feed": step["feed"]})
    worst = max(_rel(step["grads"][n], grads[n]) for n in step["names"])
    assert worst > GRAD_TOL


def test_one_adam_step_moves_router_and_expert_stacks(step):
    for name in step["names"]:
        moved = np.abs(step["after"][name] - step["before"][name]).max()
        assert moved > 0, name
        if "router" in name or "experts" in name:
            # Adam's first step is lr * sign(g) wherever g is not tiny
            assert moved == pytest.approx(1e-3, rel=1e-2), name


def test_the_step_keeps_its_counters_and_drops_no_row(step):
    c = step["counters"]
    n_pairs = B * T * TINY["num_experts_per_tok"]
    assert c["rows"].shape == (4, 4) and c["pairs"].shape == (4, 3)
    assert (c["pairs"][:, 0] == n_pairs).all()
    assert (c["pairs"][:, 1] == c["rows"].sum(1)).all()
    assert (c["pairs"][:, 2] == 0).all()
    assert (0 < c["pairs"][:, 1]).all() and (c["pairs"][:, 1] < n_pairs).all()
    assert (c["aux"] >= 1.0 - 1e-6).all()      # 1 is an even spread


def test_the_counters_read_through_the_registry(step):
    from paddle_tpu.observability import metrics
    registry = metrics.MetricsRegistry()
    prefixes = [f"l{i}_moe" for i in range(4)]
    gauges = metrics.train_expert_gauges(step["scope"], prefixes, registry)
    c = step["counters"]
    for i, p in enumerate(prefixes):
        assert gauges["routed_rows", p].value == c["pairs"][i, 1]
        assert gauges["routed_pairs", p].value == c["pairs"][i, 0]
        assert gauges["dropped_rows", p].value == 0
        assert gauges["experts_touched", p].value == \
            np.count_nonzero(c["rows"][i])
        assert gauges["balance_term", p].value == pytest.approx(
            float(c["aux"][i, 0]))
    assert 'ptpu_train_routed_rows{layer="l0_moe"}' in registry.expose()


# -- the routed layer alone ---------------------------------------------------

N, D, F, E, K = 48, 32, 16, 8, 2


def _layer_operands(seed=0, d=D, f=F):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    return dict(
        x=jax.random.normal(ks[0], (N, d)),
        router=jax.random.normal(ks[1], (d, E)) * 0.5,
        gate=jax.random.normal(ks[2], (E, d, f)) * d ** -0.5,
        up=jax.random.normal(ks[3], (E, d, f)) * d ** -0.5,
        down=jax.random.normal(ks[4], (E, f, d)) * f ** -0.5,
        probe=jax.random.normal(ks[5], (N, d)))


def _routed_part(ops, held, backend="xla"):
    """(probe . the held experts' part of the layer, gradients wrt x, the
    router and the held stacks)."""
    held = tuple(held)
    sel = jnp.asarray(held)

    def f(x, router, gate, up, down):
        _, idx, w = moe.train_route(x, router, K)
        out, sizes = moe.train_experts(x, idx, w, held, E, gate[sel],
                                       up[sel], down[sel], backend=backend,
                                       compute_dtype=jnp.float32)
        return jnp.sum(out * ops["probe"]), (out, sizes)

    (_, (out, sizes)), grads = jax.value_and_grad(
        f, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            ops["x"], ops["router"], ops["gate"], ops["up"], ops["down"])
    return out, sizes, grads


@pytest.fixture(params=[512, 8])
def pair_tile(request, monkeypatch):
    """The pair buffer's tile: at 512 the tiny layer's 96 pairs pad to one
    tile of 512 rows, most of them behind the pairs; at 8 the buffer is the
    96 pairs and a routing that holds them all leaves no dead row."""
    monkeypatch.setattr(moe, "_PAIR_TILE", request.param)
    return request.param


def test_the_ranks_shares_sum_to_the_uncut_layer(pair_tile):
    """Two ranks of four: their routed parts add up to the layer that holds
    all eight experts, and so do their gradients: of the rows, of the ROUTER
    (each rank's weights are normalised over all the selected, held or not)
    and of the stacks (a rank's stack gets the uncut layer's slice)."""
    assert moe.sort_pairs(jnp.zeros((N, K), jnp.int32), range(4), E)[0].shape \
        == ((512,) if pair_tile == 512 else (N * K,))
    ops = _layer_operands()
    whole, sizes, g_whole = _routed_part(ops, range(8))
    parts = [_routed_part(ops, r) for r in (range(0, 4), range(4, 8))]
    assert int(sizes.sum()) == N * K
    assert sum(int(p[1].sum()) for p in parts) == N * K
    assert _rel(parts[0][0] + parts[1][0], whole) < 1e-5
    for i in (0, 1):        # rows, router
        assert _rel(parts[0][2][i] + parts[1][2][i], g_whole[i]) < 1e-5
    for i in (2, 3, 4):     # a rank's stacks: zero outside its experts
        assert _rel(parts[0][2][i] + parts[1][2][i], g_whole[i]) < 1e-5
        assert float(jnp.abs(parts[0][2][i][4:]).max()) == 0.0


def test_no_row_is_dropped_where_one_expert_takes_every_row(pair_tile):
    """A router that sends every row to expert 0 first and expert 1 second:
    held here, they get all 2 N pairs (at a tile of 8 the buffer then has
    no dead row at all), and the layer and its gradients are the two
    experts' dense products'."""
    ops = _layer_operands(1)
    # scores that grow with the expert's index reversed: expert 0, then 1
    router = jnp.zeros((D, E)).at[0].set(jnp.arange(E, 0, -1.0))
    x = ops["x"].at[:, 0].set(3.0)
    _, idx, w = moe.train_route(x, router, K)
    assert (np.asarray(idx) == np.array([0, 1])).all()
    held = (0, 1, 5, 7)
    sel = jnp.asarray(held)

    def routed(x, w, gate, up, down):
        out, sizes = moe.train_experts(x, idx, w, held, E, gate[sel], up[sel],
                                       down[sel], backend="xla",
                                       compute_dtype=jnp.float32)
        return jnp.sum(out * ops["probe"]), (out, sizes)

    def dense(x, w, gate, up, down):
        out = sum((jax.nn.silu(x @ gate[e]) * (x @ up[e]) * w[:, e:e + 1])
                  @ down[e] for e in (0, 1))
        return jnp.sum(out * ops["probe"]), (out, None)

    args = (x, w, ops["gate"], ops["up"], ops["down"])
    (_, (got, sizes)), g_got = jax.value_and_grad(
        routed, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    (_, (want, _)), g_want = jax.value_and_grad(
        dense, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    assert np.asarray(sizes).tolist() == [N, N, 0, 0]
    assert 2 * N == moe.sort_pairs(idx, held, E)[0].shape[0] or \
        pair_tile == 512
    assert _rel(got, want) < 1e-5
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) < 1e-5


def _routing_that_holds(pairs, n=N, k=K, held=4, routed=E):
    """idx [n, k] of distinct experts a row with exactly `pairs` selections
    among the first `held` ids."""
    rng = np.random.RandomState(pairs)
    base, extra = divmod(pairs, n)
    idx = np.empty((n, k), np.int32)
    for row in range(n):
        mine = base + (row < extra)
        idx[row, :mine] = rng.choice(held, mine, replace=False)
        idx[row, mine:] = held + rng.choice(routed - held, k - mine,
                                            replace=False)
    return jnp.asarray(idx[rng.permutation(n)])


def _layer_at(ops, idx, backend, compute_dtype=jnp.float32):
    """(the layer, sizes, gradients wrt x, w and the three held stacks) for
    a routing given as indices, the first four experts held."""
    w = jax.nn.softmax(ops["x"][:, :K] * 3.0, axis=-1)

    def f(x, w, gate, up, down):
        out, sizes = moe.train_experts(x, idx, w, (0, 1, 2, 3), E, gate, up,
                                       down, backend=backend,
                                       compute_dtype=compute_dtype)
        return jnp.sum(out * ops["probe"]), (out, sizes)

    (_, (out, sizes)), grads = jax.value_and_grad(
        f, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            ops["x"], w, ops["gate"][:4], ops["up"][:4], ops["down"][:4])
    return out, sizes, grads


def _poison_dead_rows(monkeypatch):
    """The chip's dead rows on a CPU: every row past the held pairs comes
    back NaN from the row gather, from the four row products and from the
    elementwise step's kernel (the interpreter would hand back zeros, and a
    grid it cannot cut short writes every tile)."""
    def dead_as_nan(out, count):
        live = jnp.arange(out.shape[0])[:, None] < count
        return jnp.where(live, out, jnp.nan)

    product, rows, gated = moe._product, moe._rows_pallas, moe._gated_pallas
    # (the sum back's kernel reads the poisoned rows: it writes none)

    def poisoned_product(lhs, rhs, sizes, *, role, interpret):
        out = product(lhs, rhs, sizes, role=role, interpret=interpret)
        return out if role.endswith("_w") else dead_as_nan(out, sizes.sum())

    def poisoned_rows(x, index, count, *, dtype, interpret):
        return dead_as_nan(rows(x, index, count, dtype=dtype,
                                interpret=interpret), count[0])

    def poisoned_gated(gu, ws, d_hidden, count, *, interpret):
        out = gated(gu, ws, d_hidden, count, interpret=interpret)
        if d_hidden is None:
            return dead_as_nan(out, count[0])
        return tuple(dead_as_nan(o, count[0]) for o in out)

    monkeypatch.setattr(moe, "_product", poisoned_product)
    monkeypatch.setattr(moe, "_gated_pallas", poisoned_gated)
    monkeypatch.setattr(moe, "_rows_pallas", poisoned_rows)


@pytest.mark.parametrize("case", [
    "routed", "held-0", "held-25", "held-72", "held-100",
    "poisoned-0", "poisoned-25", "poisoned-72", "poisoned-100",
    "poisoned-bf16-0", "poisoned-bf16-72", "poisoned-bf16-100"])
def test_the_kernel_product_is_the_composites(monkeypatch, case):
    """The layer's kernels (interpreted: megablox's six products and the row
    gather) under fusion/moe.py's custom derivative against
    `jax.lax.ragged_dot`'s own and `x[index]`: the layer and all five
    gradients. `routed`: the tiny layer as its router draws it (rows too
    narrow for the row gather's kernel). `held-<share>`: rows of 128 and a
    buffer of three tiles of 32, that share of the 96 pairs on the four held
    experts, so none, a partial last tile, or every row of the buffer is
    live. `poisoned-<share>`: the same with every dead row NaN, as the chip
    may leave it: output and gradients are still finite and the same.
    `poisoned-bf16-<share>`: bfloat16 operands as a step has them, where
    the sum back is its kernel too; both sides round their products to
    bfloat16, so they agree to a few of its steps (2 ** -8 each)."""
    if case == "routed":
        monkeypatch.setattr(moe, "_TILINGS",
                            {k: (128, 128, 128) for k in moe._TILINGS})
        ops = _layer_operands(2)
        want = _routed_part(ops, (1, 2, 4, 6))
        got = _routed_part(ops, (1, 2, 4, 6), backend="pallas_interpret")
    else:
        monkeypatch.setattr(moe, "_PAIR_TILE", 32)
        monkeypatch.setattr(moe, "_TILINGS",
                            {k: (32, 128, 128) for k in moe._TILINGS})
        kind, *bf16, share = case.split("-")
        dtype, tol = (jnp.bfloat16, 2e-2) if bf16 else (jnp.float32, 1e-5)
        pairs = N * K * int(share) // 100
        ops = _layer_operands(3, d=128, f=128)
        idx = _routing_that_holds(pairs)
        assert moe.rows_lowering(ops["x"], N * K, jnp.float32,
                                 "pallas_interpret") == moe.KERNEL
        assert (moe.total_lowering(S((N * K, 128), dtype), N,
                                   "pallas_interpret") == moe.KERNEL) \
            == bool(bf16)
        want = _layer_at(ops, idx, "xla", dtype)
        if kind == "poisoned":
            _poison_dead_rows(monkeypatch)
        got = _layer_at(ops, idx, "pallas_interpret", dtype)
        assert int(got[1].sum()) == int(want[1].sum()) == pairs
    tol = 1e-5 if case == "routed" else tol
    assert np.isfinite(np.asarray(got[0])).all()
    assert _rel(got[0], want[0]) < tol
    for a, b in zip(got[2], want[2]):
        assert np.isfinite(np.asarray(a)).all() and _rel(a, b) < tol


@pytest.mark.parametrize("count", [0, 1, 70, 96])
def test_the_row_gather_is_x_at_the_index(monkeypatch, count):
    """The row gather's kernel (interpreted) against `x[index]` over three
    tiles of 32 rows: every live row, with none live, one, a partial last
    tile and the whole buffer; float32 rows out as bfloat16 and as they
    are."""
    monkeypatch.setattr(moe, "_PAIR_TILE", 32)
    rng = np.random.RandomState(count)
    x = jnp.asarray(rng.randn(40, 256).astype(np.float32))
    index = jnp.asarray(rng.randint(0, 40, 96).astype(np.int32))
    for dtype in (jnp.bfloat16, jnp.float32):
        assert moe.rows_lowering(x, 96, dtype, "pallas_interpret") == \
            moe.KERNEL
        got = moe.pair_rows(x, index, jnp.asarray([count], jnp.int32), dtype,
                            "pallas_interpret")
        assert got.dtype == dtype and got.shape == (96, 256)
        want = x[index].astype(dtype)
        assert (np.asarray(got[:count], np.float32)
                == np.asarray(want[:count], np.float32)).all()
    # what the kernel does not serve goes the plain way: narrow rows, a
    # 16-bit source, a buffer of no whole tiles, backend "xla"
    for src, m, backend in ((x[:, :32], 96, "pallas_interpret"),
                            (x.astype(jnp.bfloat16), 96, "pallas_interpret"),
                            (x, 80, "pallas_interpret"), (x, 96, "xla")):
        assert moe.rows_lowering(src, m, jnp.bfloat16, backend) == \
            moe.COMPOSITE


@pytest.mark.parametrize("count", [0, 1, 33, 70, 96])
def test_the_sum_back_is_the_rows_totals(monkeypatch, count):
    """The sum back's kernel (interpreted) against `_pair_total` over three
    tiles of 32 bfloat16 rows with every dead row NaN: none live, one, an
    odd count (a word row of which one half is live), a partial last tile,
    the whole buffer. Both sum in float32; a row's pairs in another order."""
    monkeypatch.setattr(moe, "_PAIR_TILE", 32)
    rng = np.random.RandomState(count)
    n, k, d = 24, 4, 256
    perm = jnp.asarray(rng.permutation(n * k).astype(np.int32))
    inv = jnp.zeros_like(perm).at[perm].set(jnp.arange(n * k, dtype=jnp.int32))
    y = jnp.asarray(rng.randn(n * k, d), jnp.bfloat16)
    y = jnp.where(jnp.arange(n * k)[:, None] < count, y, jnp.nan)
    at = jnp.asarray([count], jnp.int32)
    assert moe.total_lowering(y, n, "pallas_interpret") == moe.KERNEL
    got = moe.pair_total(y, perm // k, inv, at, k, "pallas_interpret")
    want = moe._pair_total(y, inv, at, k)
    assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    assert _rel(got, want) < 1e-6 if count else not np.asarray(got).any()
    for other, backend in ((y.astype(jnp.float32), "pallas_interpret"),
                           (y[:, :64], "pallas_interpret"), (y, "xla")):
        assert moe.total_lowering(other, n, backend) == moe.COMPOSITE


def test_four_layers_trace_each_kernel_body_once():
    """`moe_train/body_traced` counts one body a kernel (the row gather, the
    sum back, the elementwise step and its derivative, megablox's six
    products) for four layers' forward and backward, where `moe_train/call`
    counts every call site: thirteen a layer (the forward's gather,
    elementwise step, two products and sum back; the backward's two
    gathers, the step's derivative, two transposed products, two weight
    gradients and the sum back of the rows' cotangent)."""
    from paddle_tpu.observability import tracing
    ops = _layer_operands(4, d=128, f=128)
    idx = _routing_that_holds(40)

    def four_layers(x, w, gate, up, down):
        for _ in range(4):
            x = x + moe.train_experts(x, idx, w, (0, 1, 2, 3), E, gate, up,
                                      down, backend="pallas_interpret")[0]
        return jnp.sum(x)

    jax.clear_caches()
    tracing.force_enable(True)
    try:
        mark = tracing.mark()
        jax.make_jaxpr(jax.grad(four_layers, argnums=(0, 1, 2, 3, 4)))(
            ops["x"], jnp.full((N, K), 0.5), ops["gate"][:4], ops["up"][:4],
            ops["down"][:4])
        spans = tracing.spans_since(mark)
    finally:
        tracing.force_enable(False)
    bodies = [s.attrs["scope"] for s in spans
              if s.name == "moe_train/body_traced"]
    calls = [s.attrs["scope"] for s in spans if s.name == "moe_train/call"]
    kernels = {"moe_train_" + role for role in
               ("rows", "total", "gate", "gate_bwd", "in", "out", "in_t",
                "out_t", "in_w", "out_w")}
    assert sorted(bodies) == sorted(kernels)
    assert len(calls) == 4 * 13 and set(calls) == kernels
    assert calls.count("moe_train_rows") == 4 * 3
    assert calls.count("moe_train_total") == 4 * 2


def test_balance_term_is_one_under_an_even_spread():
    p = jnp.full((N, E), 1.0 / E)
    idx = jnp.stack([jnp.arange(N) % E, (jnp.arange(N) + 1) % E], axis=1)
    term, chosen = moe.balance_term(p, idx)
    assert float(term) == pytest.approx(1.0)
    assert np.asarray(chosen).tolist() == [N * K / E] * E


# -- the flash kernels: a head group and a window -------------------------------

@pytest.mark.parametrize("shape,window,blocks", [
    ((1, 8, 2, 32, 32, 16), 8, (None, None)),      # window < block
    ((2, 8, 2, 200, 200, 16), 8, (128, 128)),      # T not a block multiple
    ((1, 4, 1, 300, 300, 16), 100, (128, 128)),
    ((1, 4, 2, 512, 512, 16), 128, (128, 128)),    # window = block
    ((1, 4, 2, 512, 512, 16), 200, (256, 128)),
    ((1, 4, 2, 384, 384, 16), 130, (128, 256)),
    ((1, 4, 2, 384, 384, 16), 0, (128, 128)),      # the group alone
    ((1, 2, 2, 384, 384, 16), 50, (128, 128)),     # the window alone
    ((1, 4, 2, 256, 384, 16), 64, (128, 128)),     # Tq < Tk
    # a window of two blocks: the dK / dV pass's q axis runs past the last
    # q-block under the last key blocks, over tiles no mask would touch
    ((1, 1, 1, 512, 512, 16), 256, (128, 128)),
    ((1, 2, 1, 512, 512, 16), 300, (128, 128)),
])
def test_flash_with_a_head_group_and_a_window(shape, window, blocks):
    """Forward and backward of the streamed kernels (interpreted) against
    `_attention_reference`: key/value heads fewer than query heads (dK, dV
    summed over the group inside the kernel) and a key walk that starts at
    the window's first block."""
    Bq, H, KV, Tq, Tk, Dh = shape
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (Bq, H, Tq, Dh))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (Bq, KV, Tk, Dh))
            for i in (1, 2))
    probe = jax.random.normal(jax.random.fold_in(key, 3), q.shape)

    def run(backend):
        def loss(q, k, v):
            out = pk._fused_attention(q, k, v, None, Dh ** -0.5, True,
                                      backend, *blocks, None, window)
            return jnp.sum(out * probe), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
        return (out,) + grads

    plan = pk._plan_for(q, k, False, *blocks, None, window)
    assert not plan.resident and plan.group == H // KV
    assert plan.window == window
    for got, want in zip(run("pallas_interpret"), run("xla")):
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_a_window_walks_its_own_blocks_alone():
    """Under a window the streamed grid's key axis is as long as the blocks
    one q-block can see, not the sequence: 3 of 16 at the cell's shape."""
    plan = pk._flash_plan(8192, 8192, 128, 2, 32, group=8, window=1024)
    assert (plan.block_q, plan.block_k) == (512, 512) and not plan.resident
    see = pk._visible(plan, True, 8192, 8192, 16, 16, 1024)
    assert (see.k_steps, see.q_steps) == (3, 3)
    full = pk._flash_plan(8192, 8192, 128, 2, 32, group=8)
    assert pk._visible(full, True, 8192, 8192, 8, 8).k_steps == 8
    assert plan.scope("fwd").endswith("_g8_w1024")
    assert full.scope("bwd_dkv").endswith("_g8")
    with pytest.raises(ValueError):
        pk.flash_attention(jnp.zeros((1, 2, 8, 8)), jnp.zeros((1, 2, 8, 8)),
                           jnp.zeros((1, 2, 8, 8)), window=4)


# -- the spec and the graphs ------------------------------------------------------

def test_a_rotation_a_kind_and_the_family_before_it():
    spec = mellum.spec_of(TINY)
    assert [spec.rope_of(i).factor for i in range(4)] == [1.0] * 3 + [16.0]
    assert all(spec.rotates(i) for i in range(4)) and not spec.qk_norm
    assert spec.rope_full.table_scale == pytest.approx(1.2772588722239782)
    assert spec.moe.scoring == "softmax" and spec.moe.n_shared == 0
    # the K-EXAONE meaning stays: the window layers rotated, the full not
    old = DecoderSpec.window_gqa_moe(
        97, 64, 96, 8, 2, 16, ["window", "full"], 8, RopeSpec(dim=16))
    assert [old.rotates(i) for i in range(2)] == [True, False]
    assert old.qk_norm and old.rope_full is None
    with pytest.raises(ValueError):
        DecoderSpec.window_gqa_moe(97, 64, 96, 8, 2, 16, ["window", "full"],
                                   8, RopeSpec(dim=16),
                                   rope_full=RopeSpec(dim=8))
    with pytest.raises(NotImplementedError):
        MoESpec(8, 2, 16, (0, 1), scoring="tanh")


def test_a_serving_tick_refuses_softmax_scoring():
    from paddle_tpu import serving
    spec = mellum.spec_of(TINY)
    with pytest.raises(NotImplementedError, match="softmax"):
        serving.PagedKVEngine(n_slots=2, max_len=32, block_size=4,
                              n_blocks=16, n_window_blocks=16, model=spec)


def _ops_of(program):
    return [(op.type, sorted(op.inputs), sorted(op.outputs),
             sorted((k, repr(v)) for k, v in op.attrs.items()))
            for op in program.global_block().ops]


def test_the_classic_program_is_op_for_op_what_it_was():
    """`transformer_lm` by its dims, and by the classic spec of the same
    dims: one program, whose digest is the parent commit's (PR 49)."""
    import hashlib
    dims = dict(vocab=211, d_model=32, d_inner=64, num_heads=2, num_layers=2)
    programs = []
    for kw in (dims, dict(model=DecoderSpec.classic(**dims))):
        pt.reset_default_programs()
        with pt.core.unique_name.guard():
            loss, _ = transformer.transformer_lm(max_len=16, **kw)
            pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
        programs.append((_ops_of(pt.default_main_program()),
                         _ops_of(pt.default_startup_program())))
    assert programs[0] == programs[1]
    digest = hashlib.sha256(repr(programs[0]).encode()).hexdigest()
    assert digest == CLASSIC_DIGEST


CLASSIC_DIGEST = (
    "133374002567ec95ed09f15ab7e49730a794175bb3421e9af3ed1945eed19309")
