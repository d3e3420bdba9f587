"""NN op lowerings: matmul/fc, conv, pool, norms, softmax/losses.

≙ reference operators/{mul,matmul,conv,conv_transpose,pool,batch_norm,
layer_norm,softmax,cross_entropy,softmax_with_cross_entropy,lrn,fc}_op.*
(SURVEY §2.2 NN family). MXU notes: matmuls/convs go through
lax.dot_general/lax.conv_general_dilated so XLA tiles them onto the systolic
array; `use_bf16` attr lets layers request bfloat16 accumulation inputs while
keeping fp32 params (the TPU-native analogue of the reference's fp16 kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.registry import dim_prod, register_op


def _maybe_bf16(x, attrs):
    if attrs.get("use_bf16", False) and x.dtype == jnp.float32:
        from ..core import flags
        if not flags.get_flag("use_bf16_matmul"):
            return x   # global kill-switch (PTPU_USE_BF16_MATMUL=0)
        return x.astype(jnp.bfloat16)
    return x


def _bf16_active(attrs):
    if not attrs.get("use_bf16", False):
        return False
    from ..core import flags
    return bool(flags.get_flag("use_bf16_matmul"))


def _matmul_out_dtype(in_dtype, attrs):
    """Output dtype for a use_bf16 matmul/conv: bfloat16 stays bfloat16.

    Keeping activations in bf16 END TO END (params fp32, fp32 MXU
    accumulation) is the TPU-native mixed-precision recipe: it halves the
    HBM traffic of every downstream elementwise/norm op and removes the
    per-op bf16<->fp32 convert pairs, which profiling showed cost ~30% of
    a ResNet-50 train step. Norm statistics and the loss still compute in
    fp32 (see _batch_norm/_softmax_with_cross_entropy)."""
    if attrs.get("out_dtype"):      # asked for by name (a float32 head
        return jnp.dtype(attrs["out_dtype"])    # over bfloat16 activations)
    if _bf16_active(attrs):
        return jnp.bfloat16
    return in_dtype


@register_op("mul")
def _mul(ctx, ins, attrs):
    """≙ mul_op.cc — the fc matmul core: flattens x to 2-D by x_num_col_dims."""
    x, y = ins["X"][0], ins["Y"][0]
    xd = attrs.get("x_num_col_dims", 1)
    yd = attrs.get("y_num_col_dims", 1)
    xs, ys = x.shape, y.shape
    x2 = jnp.reshape(x, (dim_prod(xs[:xd]), -1))
    y2 = jnp.reshape(y, (dim_prod(ys[:yd]), -1))
    x2, y2 = _maybe_bf16(x2, attrs), _maybe_bf16(y2, attrs)
    out = jnp.dot(x2, y2, preferred_element_type=jnp.float32)
    out = jnp.reshape(out, xs[:xd] + ys[yd:]).astype(
        _matmul_out_dtype(x.dtype, attrs))
    return {"Out": [out]}


@register_op("qmatmul")
def _qmatmul(ctx, ins, attrs):
    """Weight-only quantized fc matmul (quantize_params_pass rewrite of
    `mul`): dequantizes the block-scaled int8/int4 payload per-tile inside
    the kernel — XLA fuses the scale-multiply into the dot's operand read,
    so no f32 copy of the weight ever lands in HBM — then follows the
    `mul` path exactly (same bf16 policy, same accumulation dtype), so
    quantized decode differs from f32 only by the quantization error."""
    from ..parallel.collective import dequantize_blocks_2d
    x, qw, scales = ins["X"][0], ins["QW"][0], ins["Scales"][0]
    y = dequantize_blocks_2d(qw, scales, bits=attrs.get("bits", 8))
    xd = attrs.get("x_num_col_dims", 1)
    xs = x.shape
    x2 = jnp.reshape(x, (dim_prod(xs[:xd]), -1))
    x2, y2 = _maybe_bf16(x2, attrs), _maybe_bf16(y, attrs)
    out = jnp.dot(x2, y2, preferred_element_type=jnp.float32)
    out = jnp.reshape(out, xs[:xd] + y.shape[1:]).astype(
        _matmul_out_dtype(x.dtype, attrs))
    return {"Out": [out]}


@register_op("matmul")
def _matmul(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    if attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2)
    if attrs.get("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2)
    x, y = _maybe_bf16(x, attrs), _maybe_bf16(y, attrs)
    out = jnp.matmul(x, y, preferred_element_type=jnp.float32)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out.astype(
        _matmul_out_dtype(ins["X"][0].dtype, attrs))]}


def _conv_dimension_numbers(data_format, ndim):
    if ndim == 4:
        if data_format == "NHWC":
            return ("NHWC", "HWIO", "NHWC")
        return ("NCHW", "OIHW", "NCHW")
    if data_format == "NDHWC":
        return ("NDHWC", "DHWIO", "NDHWC")
    return ("NCDHW", "OIDHW", "NCDHW")


@register_op("conv2d")
def _conv2d(ctx, ins, attrs):
    """≙ conv_op.cc / conv_cudnn_op.cu.cc. Filter layout is OIHW as in the
    reference; groups>1 supported (depthwise = groups == C_in)."""
    x, w = ins["Input"][0], ins["Filter"][0]
    nd = x.ndim - 2  # spatial rank: 2 for conv2d, 3 for conv3d
    strides = tuple(attrs.get("strides", [1] * nd))
    pads = attrs.get("paddings", [0] * nd)
    dilations = tuple(attrs.get("dilations", [1] * nd))
    groups = attrs.get("groups", 1) or 1
    data_format = attrs.get("data_format", "NCHW")
    dn = _conv_dimension_numbers(data_format, x.ndim)
    if data_format in ("NHWC", "NDHWC"):
        # framework stores filters OI<spatial>; convert to <spatial>IO
        w = jnp.transpose(w, tuple(range(2, 2 + nd)) + (1, 0))
    padding = [(p, p) for p in pads]
    x, w = _maybe_bf16(x, attrs), _maybe_bf16(w, attrs)
    # No preferred_element_type here: a f32-upcast output makes the conv vjp
    # see a f32 cotangent against bf16 operands, which lax.conv rejects. The
    # MXU accumulates bf16 convs in fp32 internally regardless; the explicit
    # astype below restores the program dtype.
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        rhs_dilation=dilations, dimension_numbers=dn,
        feature_group_count=groups)
    return {"Output": [out.astype(
        _matmul_out_dtype(ins["Input"][0].dtype, attrs))]}


register_op("conv3d")(_conv2d.__wrapped__ if hasattr(_conv2d, "__wrapped__")
                      else _conv2d)


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    attrs = dict(attrs)
    x = ins["Input"][0]
    c_in = x.shape[1] if attrs.get("data_format", "NCHW") == "NCHW" else x.shape[-1]
    attrs["groups"] = c_in
    return _conv2d(ctx, ins, attrs)


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    nd = x.ndim - 2  # spatial rank: 2 for conv2d_transpose, 3 for conv3d_
    strides = tuple(attrs.get("strides", [1] * nd))
    pads = attrs.get("paddings", [0] * nd)
    dilations = tuple(attrs.get("dilations", [1] * nd))
    # filter stored as (C_in, C_out, *spatial) per reference
    # conv_transpose_op; transpose_kernel=True expects the *forward* conv
    # kernel layout, i.e. <spatial>IO with O = C_in of x (the forward conv
    # maps C_out -> C_in). jax applies `padding` to the stride-dilated
    # input, so the reference's deconv padding p becomes kernel_extent-1-p,
    # giving out = (i-1)*s - 2p + kernel_extent as in conv_transpose_op.cc.
    ks = w.shape[2:]
    padding = [(d * (k - 1) - p, d * (k - 1) - p)
               for k, p, d in zip(ks, pads, dilations)]
    dn = (("NCHW", "HWIO", "NCHW") if nd == 2
          else ("NCDHW", "DHWIO", "NCDHW"))
    out = jax.lax.conv_transpose(
        x, jnp.transpose(w, tuple(range(2, 2 + nd)) + (1, 0)),
        strides=strides, padding=padding,
        rhs_dilation=dilations,
        dimension_numbers=dn,
        transpose_kernel=True)
    return {"Output": [out]}


register_op("conv3d_transpose")(_conv2d_transpose)


@register_op("pool2d")
def _pool2d(ctx, ins, attrs):
    """≙ pool_op.cc: max/avg, global_pooling, ceil_mode, exclusive avg.
    Rank-general: serves pool3d too (NCDHW / NDHWC)."""
    x = ins["X"][0]
    nd = x.ndim - 2  # spatial rank
    ptype = attrs.get("pooling_type", "max")
    ksize = list(attrs.get("ksize", [2] * nd))
    strides = list(attrs.get("strides", ksize))
    pads = list(attrs.get("paddings", [0] * nd))
    data_format = attrs.get("data_format", "NCHW")
    channels_last = data_format in ("NHWC", "NDHWC")
    spatial = tuple(range(1, 1 + nd)) if channels_last \
        else tuple(range(2, 2 + nd))
    if attrs.get("global_pooling", False):
        ksize = [x.shape[d] for d in spatial]
        strides = ksize
        pads = [0] * nd
    window = [1] * x.ndim
    stride_full = [1] * x.ndim
    pad_full = [(0, 0)] * x.ndim
    ceil_mode = attrs.get("ceil_mode", False)
    for i, d in enumerate(spatial):
        window[d] = ksize[i]
        stride_full[d] = strides[i]
        hi = pads[i]
        if ceil_mode:
            # extra high padding so the last partial window is included
            span = x.shape[d] + 2 * pads[i] - ksize[i]
            rem = span % strides[i]
            if rem != 0:
                hi += strides[i] - rem
        pad_full[d] = (pads[i], hi)
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window,
                                    stride_full, pad_full)
    else:
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, stride_full,
                                  pad_full)
        padded = any(lo > 0 or hi > 0 for lo, hi in pad_full)
        if attrs.get("exclusive", True) and padded:
            ones = jnp.ones_like(x)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        stride_full, pad_full)
            out = s / cnt
        else:
            out = s / float(np.prod(ksize))
    return {"Out": [out]}


register_op("pool3d")(_pool2d)


def _bn_stats(x, shift, reduce_axes, bshape):
    """Shifted single-pass fp32 moments over `reduce_axes`.

    Statistics always accumulate in fp32 — with bf16 activations the
    variance would otherwise lose most of its bits to cancellation. Both
    reductions are independent so XLA fuses them into one read of x (BN is
    bandwidth-bound and x is the big activation tensor). The shift is the
    running mean, which kills the E[x^2]-E[x]^2 cancellation for data with
    |mean| >> std; early steps, when the running mean still lags, have
    near-zero-mean conv activations anyway."""
    x32 = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
    xs_ = x32 - shift.reshape(bshape)
    m1s = jnp.mean(xs_, axis=reduce_axes)
    m2s = jnp.mean(jnp.square(xs_), axis=reduce_axes)
    mean = m1s + shift
    var = jnp.maximum(m2s - jnp.square(m1s), 0.0)
    return mean, var


def _bn_apply_math(x, scale, bias, shift, reduce_axes, bshape, eps):
    mean, var = _bn_stats(x, shift, reduce_axes, bshape)
    inv = jax.lax.rsqrt(var + eps)
    # ONE per-channel fma in the activation dtype: a/b are precomputed in
    # fp32 ([C]-sized, cheap) so the only activation-sized work stays bf16.
    a32 = inv * scale
    b32 = bias - mean * a32
    y = x * a32.astype(x.dtype).reshape(bshape) \
        + b32.astype(x.dtype).reshape(bshape)
    return y, mean, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bn_train_apply(reduce_axes, bshape, eps, x, scale, bias, shift):
    """Train-mode BN normalize+affine with a closed-form backward.

    Plain autodiff of the stats path stores the fp32 activation-sized
    intermediate (x32 - shift) as a residual for the variance backward —
    on ResNet-50 bs256 those are 822 MB f32 buffers and the top source of
    HBM traffic (round-3 profile). The closed-form VJP saves only x (bf16,
    already live) plus [C]-sized stats and recomputes xhat inside fused
    backward loops, so fwd+bwd each read the activations exactly once at
    activation width."""
    y, _, _ = _bn_apply_math(x, scale, bias, shift, reduce_axes, bshape, eps)
    return y


def _bn_train_apply_fwd(reduce_axes, bshape, eps, x, scale, bias, shift):
    y, mean, inv = _bn_apply_math(x, scale, bias, shift, reduce_axes, bshape,
                                  eps)
    return y, (x, mean, inv, scale, shift)


def _bn_train_apply_bwd(reduce_axes, bshape, eps, res, dy):
    x, mean, inv, scale, shift = res
    n = float(np.prod([x.shape[a] for a in reduce_axes]))
    # Reductions accumulate in f32; the elementwise operands convert inside
    # the fused reduction loops, so x/dy are each read once at bf16 width.
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    xc = x32 - mean.reshape(bshape)
    sum_dy = jnp.sum(dy32, axis=reduce_axes)
    sum_dy_xc = jnp.sum(dy32 * xc, axis=reduce_axes)
    dscale = inv * sum_dy_xc
    dbias = sum_dy
    # dx = (scale*inv) * (dy - mean(dy) - xhat * mean(dy*xhat))
    c0 = (scale * inv).reshape(bshape)
    c1 = (sum_dy / n).reshape(bshape)
    c2 = (inv * inv * sum_dy_xc / n).reshape(bshape)
    dx = (c0 * (dy32 - c1 - xc * c2)).astype(x.dtype)
    return (dx, dscale.astype(scale.dtype), dbias.astype(dy32.dtype),
            jnp.zeros_like(shift))


_bn_train_apply.defvjp(_bn_train_apply_fwd, _bn_train_apply_bwd)


@register_op("batch_norm")
def _batch_norm(ctx, ins, attrs):
    """≙ batch_norm_op.cc: train mode uses batch stats and emits updated
    moving stats; test mode uses the running estimates."""
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    data_layout = attrs.get("data_layout", "NCHW")
    is_test = attrs.get("is_test", False) or ctx.is_test
    axis = 1 if data_layout == "NCHW" else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    bshape = tuple(x.shape[i] if i == axis else 1 for i in range(x.ndim))

    if is_test:
        use_mean, use_var = mean, var
        inv = jax.lax.rsqrt(use_var + eps)
        a32 = inv * scale
        b32 = bias - use_mean * a32
        y = x * a32.astype(x.dtype).reshape(bshape) \
            + b32.astype(x.dtype).reshape(bshape)
        return {"Y": [y], "MeanOut": [mean], "VarianceOut": [var],
                "SavedMean": [use_mean], "SavedVariance": [inv]}

    shift_v = jax.lax.stop_gradient(mean)
    y = _bn_train_apply(reduce_axes, bshape, eps, x, scale, bias, shift_v)
    # Stats for the running-average update and the Saved* outputs: computed
    # from stop_gradient(x) so no second differentiable path (and no second
    # set of residuals) exists — HLO-wise these reductions are identical to
    # the ones inside the custom-vjp forward, so XLA CSEs them away.
    use_mean, use_var = _bn_stats(jax.lax.stop_gradient(x), shift_v,
                                  reduce_axes, bshape)
    inv = jax.lax.rsqrt(use_var + eps)
    mean_out = momentum * mean + (1 - momentum) * use_mean
    var_out = momentum * var + (1 - momentum) * use_var
    return {"Y": [y], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [use_mean], "SavedVariance": [inv]}


@register_op("layer_norm")
def _layer_norm(ctx, ins, attrs):
    """≙ layer_norm_op.cc: normalize over dims >= begin_norm_axis."""
    x = ins["X"][0]
    begin = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    norm_shape = x.shape[begin:]
    if ins.get("Scale"):
        y = y * jnp.reshape(ins["Scale"][0], norm_shape)
    if ins.get("Bias"):
        y = y + jnp.reshape(ins["Bias"][0], norm_shape)
    return {"Y": [y], "Mean": [jnp.reshape(mean, mean.shape[:begin])],
            "Variance": [jnp.reshape(var, var.shape[:begin])]}


@register_op("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """x / sqrt(mean(x^2) + eps) * scale over the last axis; the statistics
    in float32 whatever x is, the result in x's dtype."""
    x = ins["X"][0]
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + attrs.get("epsilon", 1e-6))
    return {"Y": [(y * ins["Scale"][0].astype(jnp.float32)).astype(x.dtype)]}


@register_op("rotary", stop_gradient=True)
def _rotary(ctx, ins, attrs):
    """Rotate X [N, .., heads*dim] (each head's `dim` values paired (i,
    i + dim/2): rotate-half) by the angles of row `Pos[n]` of `Table`
    [T, dim]: cos in its first half, sin in its second, already scaled
    (`models.transformer.rotary_table`). Float32 inside, X's dtype out. A
    `Pos` shorter than X's rows repeats over them (a training graph's one
    row of positions under [B, T] rows)."""
    x, table = ins["X"][0], ins["Table"][0]
    dim = table.shape[-1]
    half = dim // 2
    pos = ins["Pos"][0].reshape(-1).astype(jnp.int32)
    n_rows = x.size // x.shape[-1]
    if pos.shape[0] < n_rows:
        pos = jnp.tile(pos, n_rows // pos.shape[0])
    row = table[pos].astype(jnp.float32)                       # [N, dim]
    xf = x.reshape(pos.shape[0], -1, dim).astype(jnp.float32)  # [N, h, dim]
    cos, sin = row[:, None, :half], row[:, None, half:]
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}


@register_op("softmax")
def _softmax(ctx, ins, attrs):
    return {"Out": [jax.nn.softmax(ins["X"][0], axis=-1)]}


@register_op("log_softmax")
def _log_softmax(ctx, ins, attrs):
    return {"Out": [jax.nn.log_softmax(ins["X"][0],
                                       axis=attrs.get("axis", -1))]}


def _float0_zero(x):
    import numpy as _np
    return _np.zeros(x.shape, dtype=jax.dtypes.float0)


@jax.custom_vjp
def _ce_hard(logits, lbl, valid):
    """Hard-label softmax cross entropy with a closed-form backward.

    Plain autodiff stores the fp32 [rows, vocab] log-softmax as a residual
    — on the transformer-LM bench config that is a 1 GB buffer (round-4
    profile: the CE chain is ~20% of the step's HBM traffic). This VJP
    saves only the bf16 logits (already live) + a [rows]-sized fp32 lse
    and recomputes p = exp(logit - lse) inside the fused backward, so the
    vocab-sized work stays at activation width in both directions."""
    loss, _ = _ce_hard_fwd_math(logits, lbl, valid)
    return loss


def _ce_hard_fwd_math(logits, lbl, valid):
    l32 = logits.astype(jnp.float32)
    m = jnp.max(l32, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(l32 - m[..., None]), axis=-1))
    logit_at = jnp.take_along_axis(l32, lbl[..., None], axis=-1)[..., 0]
    nll = lse - logit_at
    loss = jnp.where(valid, nll, 0.0)[..., None]
    return loss, lse


def _ce_hard_fwd(logits, lbl, valid):
    loss, lse = _ce_hard_fwd_math(logits, lbl, valid)
    return loss, (logits, lbl, valid, lse)


def _ce_hard_bwd(res, dl):
    logits, lbl, valid, lse = res
    g = dl[..., 0] * valid
    # p - onehot via an iota compare: fused elementwise, nothing
    # vocab-sized materializes in fp32
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    oh = (jax.lax.broadcasted_iota(lbl.dtype, logits.shape,
                                   logits.ndim - 1) == lbl[..., None])
    dlogits = ((p - oh.astype(jnp.float32))
               * g[..., None]).astype(logits.dtype)
    return dlogits, _float0_zero(lbl), _float0_zero(valid)


_ce_hard.defvjp(_ce_hard_fwd, _ce_hard_bwd)


@register_op("softmax_with_cross_entropy")
def _softmax_with_cross_entropy(ctx, ins, attrs):
    """≙ softmax_with_cross_entropy_op.cc (fused, numerically stable)."""
    logits = ins["Logits"][0]
    label = ins["Label"][0]
    if attrs.get("soft_label", False):
        l32 = logits.astype(jnp.float32) \
            if logits.dtype != jnp.float32 else logits
        logp = jax.nn.log_softmax(l32, axis=-1)
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
        return {"Loss": [loss], "Softmax": [jnp.exp(logp)]}
    lbl = label
    if lbl.ndim == logits.ndim and lbl.shape[-1] == 1:
        lbl = jnp.squeeze(lbl, axis=-1)
    # labels equal to ignore_index (default -100, commonly -1 for
    # padding) contribute zero loss and zero gradient
    ignore = attrs.get("ignore_index", -100)
    valid = (lbl != ignore)
    safe = jnp.where(valid, lbl, 0)
    loss = _ce_hard(logits, safe, valid)
    # Softmax output: a separate differentiable branch (distillation /
    # entropy terms differentiate through it). Unused -> the whole branch
    # is DCE'd, so the custom-vjp loss path stays residual-lean in the
    # common loss-only programs.
    sm = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return {"Loss": [loss], "Softmax": [sm]}


@register_op("cross_entropy")
def _cross_entropy(ctx, ins, attrs):
    """≙ cross_entropy_op.cc over probabilities (not logits)."""
    x = ins["X"][0]
    label = ins["Label"][0]
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)), axis=-1,
                        keepdims=True)
    else:
        lbl = label
        if lbl.ndim == x.ndim and lbl.shape[-1] == 1:
            lbl = jnp.squeeze(lbl, axis=-1)
        ignore = attrs.get("ignore_index", -100)
        valid = (lbl != ignore)
        safe = jnp.where(valid, lbl, 0)
        p = jnp.take_along_axis(x, safe[..., None], axis=-1)
        loss = jnp.where(valid[..., None],
                         -jnp.log(jnp.maximum(p, 1e-20)), 0.0)
    return {"Y": [loss]}


@register_op("sigmoid_cross_entropy_with_logits")
def _sigmoid_ce(ctx, ins, attrs):
    x = ins["X"][0]
    label = ins["Label"][0]
    # max(x,0) - x*z + log(1+exp(-|x|)) — stable formulation
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {"Out": [loss]}


@register_op("lrn")
def _lrn(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": [x / jnp.power(mid, beta)], "MidOut": [mid]}


@register_op("l2_normalize")
def _l2_normalize(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


@register_op("huber_loss")
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= delta, 0.5 * jnp.square(r),
                     delta * (a - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register_op("smooth_l1_loss")
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if ins.get("InsideWeight"):
        diff = diff * ins["InsideWeight"][0]
    a = jnp.abs(diff)
    loss = jnp.where(a < 1.0 / s2, 0.5 * s2 * jnp.square(diff), a - 0.5 / s2)
    if ins.get("OutsideWeight"):
        loss = loss * ins["OutsideWeight"][0]
    return {"Out": [jnp.sum(loss, axis=tuple(range(1, loss.ndim)),
                            keepdims=False)[..., None]],
            "Diff": [diff]}


@register_op("log_loss")
def _log_loss(ctx, ins, attrs):
    p = ins["Predicted"][0]
    y = ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    loss = -y * jnp.log(p + eps) - (1 - y) * jnp.log(1 - p + eps)
    return {"Loss": [loss]}


@register_op("hinge_loss")
def _hinge_loss(ctx, ins, attrs):
    logits = ins["Logits"][0]
    labels = ins["Labels"][0]
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2 * labels - 1) * logits)]}


@register_op("rank_loss")
def _rank_loss(ctx, ins, attrs):
    label = ins["Label"][0]
    left, right = ins["Left"][0], ins["Right"][0]
    d = left - right
    return {"Out": [jnp.log1p(jnp.exp(d)) - label * d]}


@register_op("margin_rank_loss")
def _margin_rank_loss(ctx, ins, attrs):
    label = ins["Label"][0]
    x1, x2 = ins["X1"][0], ins["X2"][0]
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [out], "Activated": [(out > 0).astype(x1.dtype)]}


@register_op("mse_loss")
def _mse_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.square(x - y)]}


@register_op("bilinear_tensor_product")
def _bilinear_tensor_product(ctx, ins, attrs):
    x, y, w = ins["X"][0], ins["Y"][0], ins["Weight"][0]
    # w: [out, dx, dy]
    out = jnp.einsum("bi,oij,bj->bo", x, w, y)
    if ins.get("Bias"):
        out = out + ins["Bias"][0]
    return {"Out": [out]}


@register_op("bilinear_interp")
def _bilinear_interp(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    out_h = attrs["out_h"]
    out_w = attrs["out_w"]
    out = jax.image.resize(x, (x.shape[0], x.shape[1], out_h, out_w),
                           method="bilinear")
    return {"Out": [out]}


@register_op("im2sequence")
def _im2sequence(ctx, ins, attrs):
    # unfold image into patch sequence (≙ im2sequence_op)
    x = ins["X"][0]  # NCHW
    kh, kw = attrs["kernels"]
    sh, sw = attrs.get("strides", [1, 1])
    n, c, h, w = x.shape
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    # [N, C*kh*kw, OH, OW] -> [N*OH*OW, C*kh*kw]
    out = jnp.transpose(patches, (0, 2, 3, 1)).reshape(n * oh * ow, -1)
    return {"Out": [out]}


@register_op("grid_sampler")
def _grid_sampler(ctx, ins, attrs):
    x = ins["X"][0]          # [N, C, H, W]
    grid = ins["Grid"][0]    # [N, H', W', 2] in [-1, 1]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx, wy = gx - x0, gy - y0

    def sample(xi, yi):
        xi = jnp.clip(xi, 0, w - 1)
        yi = jnp.clip(yi, 0, h - 1)
        batch_idx = jnp.arange(n)[:, None, None]
        return x[batch_idx, :, yi, xi]  # [N, H', W', C]

    val = (sample(x0, y0) * ((1 - wx) * (1 - wy))[..., None] +
           sample(x1, y0) * (wx * (1 - wy))[..., None] +
           sample(x0, y1) * ((1 - wx) * wy)[..., None] +
           sample(x1, y1) * (wx * wy)[..., None])
    return {"Output": [jnp.transpose(val, (0, 3, 1, 2))]}


@register_op("spp")
def _spp(ctx, ins, attrs):
    """≙ spp_op.cc (spatial pyramid pooling): pool the [N,C,H,W] input at
    pyramid levels 1x1, 2x2, ... 2^(L-1) grids and concat the flattened
    bins -> [N, C * sum(4^l)]."""
    x = ins["X"][0]
    levels = attrs.get("pyramid_height", 3)
    pool_type = attrs.get("pooling_type", "max")
    n, c, h, w = x.shape
    outs = []
    def bounds(extent, bins):
        # nearly-even sections, never empty: when extent < bins the bins
        # overlap (each still >= 1 element) so the output bin count — and
        # the layer's declared shape — stays C * sum(4^l)
        out = []
        for i in range(bins):
            lo = min(extent - 1, extent * i // bins)
            hi = max(lo + 1, -(-extent * (i + 1) // bins))
            out.append((lo, min(hi, extent)))
        return out

    for lvl in range(levels):
        bins = 2 ** lvl
        hb = bounds(h, bins)
        wb = bounds(w, bins)
        for (h0, h1) in hb:
            for (w0, w1) in wb:
                sl = x[:, :, h0:h1, w0:w1]
                red = (jnp.max if pool_type == "max" else jnp.mean)(
                    sl, axis=(2, 3))
                outs.append(red)
    return {"Out": [jnp.concatenate(outs, axis=1)]}
