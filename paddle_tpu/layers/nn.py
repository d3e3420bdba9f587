"""Neural-network layers.

≙ reference python/paddle/fluid/layers/nn.py (79 layers: fc:114,
embedding:226, conv2d:1369, batch_norm:2004, layer_norm:2155, ...). Each layer
creates parameters via LayerHelper and appends ops; the TPU executor traces
and XLA-compiles the resulting program.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import numpy as np

from ..core.dtypes import dtype_name
from ..core.enforce import InvalidArgumentError, enforce
from ..framework.program import Variable
from ..initializer import ConstantInitializer, NormalInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


# ---------------------------------------------------------------- fc
def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None, use_bf16=False, out_dtype=None):
    """Fully connected layer (≙ reference layers/nn.py:114).

    use_bf16 routes the matmul through bfloat16 on the MXU with fp32
    accumulation (TPU-native analogue of fp16 kernels)."""
    helper = LayerHelper("fc", name=name, act=act, bias_attr=bias_attr)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = param_attr if isinstance(param_attr, (list, tuple)) \
        else [param_attr] * len(inputs)
    mul_results = []
    for inp, pattr in zip(inputs, param_attrs):
        in_dim = _prod(inp.shape[num_flatten_dims:])
        w = helper.create_parameter(pattr, shape=[in_dim, size],
                                    dtype=dtype_name(inp.dtype))
        out_shape = list(inp.shape[:num_flatten_dims]) + [size]
        tmp = helper.create_tmp_variable(
            dtype=out_dtype or dtype_name(inp.dtype), shape=out_shape)
        attrs = {"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1,
                 "use_bf16": use_bf16}
        if out_dtype:       # e.g. float32 logits over bfloat16 activations
            attrs["out_dtype"] = out_dtype
        helper.append_op(type="mul", inputs={"X": [inp], "Y": [w]},
                         outputs={"Out": [tmp]}, attrs=attrs)
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(
            dtype=dtype_name(inputs[0].dtype), shape=mul_results[0].shape)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims,
                                    use_bf16=use_bf16)
    return helper.append_activation(pre_act)


# ---------------------------------------------------------------- embedding
def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """≙ reference layers/nn.py:226 + lookup_table_op.cc:21. On TPU the table
    is a dense (shardable) array; is_sparse/is_distributed accepted for API
    parity — sharding is configured via the parallel strategy instead."""
    helper = LayerHelper("embedding", name=None)
    w = helper.create_parameter(param_attr, shape=list(size), dtype=dtype,
                                default_initializer=NormalInitializer(0., 0.02))
    in_shape = list(input.shape)
    if in_shape and in_shape[-1] == 1:
        in_shape = in_shape[:-1]
    out = helper.create_tmp_variable(dtype=dtype,
                                     shape=in_shape + [size[1]])
    helper.append_op(type="lookup_table",
                     inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [out]},
                     attrs={"is_sparse": is_sparse,
                            "is_distributed": is_distributed,
                            "padding_idx": padding_idx})
    return out


# ---------------------------------------------------------------- conv
def _pair(x):
    return list(x) if isinstance(x, (list, tuple)) else [x, x]


def _conv_out_dim(in_dim, k, pad, stride, dilation=1):
    if in_dim == -1:
        return -1
    return (in_dim + 2 * pad - (dilation * (k - 1) + 1)) // stride + 1


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           use_cudnn=True, name=None, data_format="NCHW", use_bf16=False):
    """≙ reference layers/nn.py:1369 (conv2d). use_cudnn accepted for API
    parity and ignored — XLA picks the conv implementation."""
    helper = LayerHelper("conv2d", name=name, act=act, bias_attr=bias_attr)
    filter_size = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    groups = groups or 1
    c_axis = 1 if data_format == "NCHW" else 3
    num_channels = input.shape[c_axis]
    w_shape = [num_filters, num_channels // groups] + filter_size
    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    std = (2.0 / fan_in) ** 0.5
    w = helper.create_parameter(param_attr, shape=w_shape,
                                dtype=dtype_name(input.dtype),
                                default_initializer=NormalInitializer(0., std))
    if data_format == "NCHW":
        n, c, h, wd = input.shape
        out_shape = [n, num_filters,
                     _conv_out_dim(h, filter_size[0], padding[0], stride[0],
                                   dilation[0]),
                     _conv_out_dim(wd, filter_size[1], padding[1], stride[1],
                                   dilation[1])]
    else:
        n, h, wd, c = input.shape
        out_shape = [n,
                     _conv_out_dim(h, filter_size[0], padding[0], stride[0],
                                   dilation[0]),
                     _conv_out_dim(wd, filter_size[1], padding[1], stride[1],
                                   dilation[1]),
                     num_filters]
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=out_shape)
    helper.append_op(type="conv2d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation, "groups": groups,
                            "data_format": data_format, "use_bf16": use_bf16})
    pre_act = helper.append_bias_op(out, dim_start=c_axis,
                                    dim_end=c_axis + 1, use_bf16=use_bf16)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, filter_size=None, output_size=None,
                     stride=1, padding=0, dilation=1, param_attr=None,
                     bias_attr=None, act=None, name=None):
    """≙ reference layers/nn.py conv2d_transpose."""
    helper = LayerHelper("conv2d_transpose", name=name, act=act,
                         bias_attr=bias_attr)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    n, c, h, wd = input.shape
    if filter_size is None:
        enforce(output_size is not None,
                "need filter_size or output_size", exc=InvalidArgumentError)
        output_size = _pair(output_size)
        filter_size = [output_size[0] - (h - 1) * stride[0] + 2 * padding[0],
                       output_size[1] - (wd - 1) * stride[1] + 2 * padding[1]]
    else:
        filter_size = _pair(filter_size)
    w = helper.create_parameter(param_attr,
                                shape=[c, num_filters] + filter_size,
                                dtype=dtype_name(input.dtype))

    def _out(in_dim, k, pad, s):
        return -1 if in_dim == -1 else (in_dim - 1) * s - 2 * pad + k

    out_shape = [n, num_filters,
                 _out(h, filter_size[0], padding[0], stride[0]),
                 _out(wd, filter_size[1], padding[1], stride[1])]
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=out_shape)
    helper.append_op(type="conv2d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation})
    pre_act = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


# ---------------------------------------------------------------- pool
def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False,
           exclusive=True, use_cudnn=True, name=None, data_format="NCHW"):
    """≙ reference layers/nn.py pool2d."""
    helper = LayerHelper("pool2d", name=name)
    pool_size = _pair(pool_size)
    pool_stride = _pair(pool_stride)
    pool_padding = _pair(pool_padding)
    spatial = (2, 3) if data_format == "NCHW" else (1, 2)
    out_shape = list(input.shape)
    for i, d in enumerate(spatial):
        if global_pooling:
            out_shape[d] = 1
        elif out_shape[d] != -1:
            span = out_shape[d] + 2 * pool_padding[i] - pool_size[i]
            if ceil_mode:
                out_shape[d] = -(-span // pool_stride[i]) + 1
            else:
                out_shape[d] = span // pool_stride[i] + 1
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=out_shape)
    helper.append_op(type="pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type, "ksize": pool_size,
                            "strides": pool_stride, "paddings": pool_padding,
                            "global_pooling": global_pooling,
                            "exclusive": exclusive, "ceil_mode": ceil_mode,
                            "data_format": data_format})
    return out


def _triple(x):
    return list(x) if isinstance(x, (list, tuple)) else [x, x, x]


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           use_cudnn=True, name=None, data_format="NCDHW", use_bf16=False):
    """≙ reference layers/nn.py conv3d (conv_op.cc vol2col path). Input
    [N, C, D, H, W] (or NDHWC); filter [M, C/g, kd, kh, kw]."""
    helper = LayerHelper("conv3d", name=name, act=act, bias_attr=bias_attr)
    filter_size = _triple(filter_size)
    stride = _triple(stride)
    padding = _triple(padding)
    dilation = _triple(dilation)
    groups = groups or 1
    c_axis = 1 if data_format == "NCDHW" else 4
    num_channels = input.shape[c_axis]
    w_shape = [num_filters, num_channels // groups] + filter_size
    fan_in = (num_channels // groups) * int(
        filter_size[0] * filter_size[1] * filter_size[2])
    std = (2.0 / fan_in) ** 0.5
    w = helper.create_parameter(param_attr, shape=w_shape,
                                dtype=dtype_name(input.dtype),
                                default_initializer=NormalInitializer(0., std))
    spatial_in = (input.shape[2:5] if data_format == "NCDHW"
                  else input.shape[1:4])
    spatial_out = [_conv_out_dim(s, filter_size[i], padding[i], stride[i],
                                 dilation[i])
                   for i, s in enumerate(spatial_in)]
    if data_format == "NCDHW":
        out_shape = [input.shape[0], num_filters] + spatial_out
    else:
        out_shape = [input.shape[0]] + spatial_out + [num_filters]
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=out_shape)
    helper.append_op(type="conv3d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation, "groups": groups,
                            "data_format": data_format, "use_bf16": use_bf16})
    pre_act = helper.append_bias_op(out, dim_start=c_axis,
                                    dim_end=c_axis + 1, use_bf16=use_bf16)
    return helper.append_activation(pre_act)


def conv3d_transpose(input, num_filters, filter_size=None, output_size=None,
                     stride=1, padding=0, dilation=1, param_attr=None,
                     bias_attr=None, act=None, use_cudnn=True, name=None):
    """≙ reference layers/nn.py conv3d_transpose (conv_transpose_op.cc 3-D
    path). Input [N, C, D, H, W]; filter stored [C, M, kd, kh, kw]."""
    helper = LayerHelper("conv3d_transpose", name=name, act=act,
                         bias_attr=bias_attr)
    stride = _triple(stride)
    padding = _triple(padding)
    dilation = _triple(dilation)
    n, c = input.shape[0], input.shape[1]
    spatial_in = list(input.shape[2:5])
    if filter_size is None:
        enforce(output_size is not None,
                "conv3d_transpose needs filter_size or output_size",
                exc=InvalidArgumentError)
        output_size = _triple(output_size)
        # invert out = (in-1)*s - 2p + d*(k-1) + 1 for k
        filter_size = []
        for i in range(3):
            if spatial_in[i] == -1:
                filter_size.append(1)
                continue
            span = (output_size[i] - (spatial_in[i] - 1) * stride[i]
                    + 2 * padding[i] - 1)
            enforce(span % dilation[i] == 0,
                    f"output_size[{i}]={output_size[i]} unreachable with "
                    f"stride={stride[i]} padding={padding[i]} "
                    f"dilation={dilation[i]}", exc=InvalidArgumentError)
            filter_size.append(span // dilation[i] + 1)
    else:
        filter_size = _triple(filter_size)
    w = helper.create_parameter(param_attr,
                                shape=[c, num_filters] + filter_size,
                                dtype=dtype_name(input.dtype))
    spatial_out = [
        (spatial_in[i] - 1) * stride[i] - 2 * padding[i]
        + dilation[i] * (filter_size[i] - 1) + 1
        if spatial_in[i] != -1 else -1 for i in range(3)]
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=[n, num_filters] + spatial_out)
    helper.append_op(type="conv3d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation})
    pre_act = helper.append_bias_op(out, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False,
           exclusive=True, use_cudnn=True, name=None, data_format="NCDHW"):
    """≙ reference layers/nn.py pool3d."""
    helper = LayerHelper("pool3d", name=name)
    pool_size = _triple(pool_size)
    pool_stride = _triple(pool_stride)
    pool_padding = _triple(pool_padding)
    spatial = (2, 3, 4) if data_format == "NCDHW" else (1, 2, 3)
    out_shape = list(input.shape)
    for i, d in enumerate(spatial):
        if global_pooling:
            out_shape[d] = 1
        elif out_shape[d] != -1:
            span = out_shape[d] + 2 * pool_padding[i] - pool_size[i]
            if ceil_mode:
                out_shape[d] = -(-span // pool_stride[i]) + 1
            else:
                out_shape[d] = span // pool_stride[i] + 1
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=out_shape)
    helper.append_op(type="pool3d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type, "ksize": pool_size,
                            "strides": pool_stride, "paddings": pool_padding,
                            "global_pooling": global_pooling,
                            "exclusive": exclusive, "ceil_mode": ceil_mode,
                            "data_format": data_format})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR"):
    """≙ reference layers/nn.py image_resize (bilinear_interp_op). Input
    [N, C, H, W]; out_shape [H', W'] or scale factor."""
    enforce(resample.upper() == "BILINEAR",
            "only BILINEAR resample is supported", exc=InvalidArgumentError)
    helper = LayerHelper("image_resize", name=name)
    h, w = input.shape[2], input.shape[3]
    if out_shape is None:
        enforce(scale is not None, "image_resize needs out_shape or scale",
                exc=InvalidArgumentError)
        out_h, out_w = int(h * scale), int(w * scale)
        enforce(out_h > 0 and out_w > 0,
                f"image_resize with scale= needs static spatial dims "
                f"(got H={h}, W={w}); pass out_shape for dynamic inputs",
                exc=InvalidArgumentError)
    else:
        out_h, out_w = int(out_shape[0]), int(out_shape[1])
    out = helper.create_tmp_variable(
        dtype=dtype_name(input.dtype),
        shape=[input.shape[0], input.shape[1], out_h, out_w])
    helper.append_op(type="bilinear_interp", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"out_h": out_h, "out_w": out_w})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    """≙ reference layers/nn.py resize_bilinear."""
    return image_resize(input, out_shape=out_shape, scale=scale, name=name)


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """≙ reference layers/nn.py image_resize_short: resize so the SHORT side
    equals out_short_len, keeping aspect ratio."""
    h, w = input.shape[2], input.shape[3]
    short = min(h, w)
    out_h = int(h * out_short_len / short)
    out_w = int(w * out_short_len / short)
    return image_resize(input, out_shape=[out_h, out_w], resample=resample)


def dice_loss(input, label, epsilon=1e-5):
    """≙ reference layers/nn.py dice_loss: 1 - 2|X∩Y| / (|X|+|Y|).
    input [N, D] probabilities, label [N, 1] int class indices."""

    label = one_hot(label, depth=input.shape[-1])
    reduce_dims = list(range(1, len(input.shape)))
    inse = reduce_sum(input * label, dim=reduce_dims)
    dice_denominator = reduce_sum(input, dim=reduce_dims) + \
        reduce_sum(label, dim=reduce_dims) + epsilon
    dice_score = 1 - inse * 2 / dice_denominator
    return reduce_mean(dice_score)


def positive_negative_pair(score, label, query_id, name=None):
    """≙ reference positive_negative_pair_op.cc: counts of correctly /
    incorrectly / neutrally ranked pairs per query group. Returns
    (positive, negative, neutral) float scalars."""
    helper = LayerHelper("positive_negative_pair", name=name)
    pos = helper.create_tmp_variable(dtype="float32", shape=[1])
    neg = helper.create_tmp_variable(dtype="float32", shape=[1])
    neu = helper.create_tmp_variable(dtype="float32", shape=[1])
    helper.append_op(type="positive_negative_pair",
                     inputs={"Score": [score], "Label": [label],
                             "QueryID": [query_id]},
                     outputs={"PositivePair": [pos], "NegativePair": [neg],
                              "NeutralPair": [neu]})
    return pos, neg, neu


# ---------------------------------------------------------------- norms
def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None):
    """≙ reference layers/nn.py:2004. Moving stats are persistable vars
    updated functionally each step."""
    helper = LayerHelper("batch_norm", name=name, act=act)
    c_axis = 1 if data_layout == "NCHW" else input.ndim - 1
    c = input.shape[c_axis]
    dtype = dtype_name(input.dtype)
    scale = helper.create_parameter(param_attr, shape=[c], dtype=dtype,
                                    default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, shape=[c], dtype=dtype,
                                   is_bias=True)
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, trainable=False), shape=[c],
        dtype=dtype, default_initializer=ConstantInitializer(0.0))
    variance = helper.create_parameter(
        ParamAttr(name=moving_variance_name, trainable=False), shape=[c],
        dtype=dtype, default_initializer=ConstantInitializer(1.0))
    mean.stop_gradient = True
    variance.stop_gradient = True
    y = helper.create_tmp_variable(dtype=dtype, shape=input.shape)
    saved_mean = helper.create_tmp_variable(dtype=dtype, shape=[c],
                                            stop_gradient=True)
    saved_var = helper.create_tmp_variable(dtype=dtype, shape=[c],
                                           stop_gradient=True)
    helper.append_op(type="batch_norm",
                     inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                             "Mean": [mean], "Variance": [variance]},
                     outputs={"Y": [y], "MeanOut": [mean],
                              "VarianceOut": [variance],
                              "SavedMean": [saved_mean],
                              "SavedVariance": [saved_var]},
                     attrs={"momentum": momentum, "epsilon": epsilon,
                            "data_layout": data_layout, "is_test": is_test})
    return helper.append_activation(y)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    """≙ reference layers/nn.py:2155."""
    helper = LayerHelper("layer_norm", name=name, act=act)
    dtype = dtype_name(input.dtype)
    norm_shape = [_prod(input.shape[begin_norm_axis:])]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, shape=norm_shape, dtype=dtype,
                                    default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, shape=norm_shape, dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    y = helper.create_tmp_variable(dtype=dtype, shape=input.shape)
    mean = helper.create_tmp_variable(dtype=dtype,
                                      shape=input.shape[:begin_norm_axis],
                                      stop_gradient=True)
    var = helper.create_tmp_variable(dtype=dtype,
                                     shape=input.shape[:begin_norm_axis],
                                     stop_gradient=True)
    helper.append_op(type="layer_norm", inputs=inputs,
                     outputs={"Y": [y], "Mean": [mean], "Variance": [var]},
                     attrs={"begin_norm_axis": begin_norm_axis,
                            "epsilon": epsilon})
    return helper.append_activation(y)


# ---------------------------------------------------------------- dropout
def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape)
    mask = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                      shape=x.shape, stop_gradient=True)
    helper.append_op(type="dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "seed": seed or 0,
                            "dropout_implementation": dropout_implementation})
    return out


# ---------------------------------------------------------------- losses
def softmax(input, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=input.shape)
    helper.append_op(type="softmax", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, return_softmax=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    loss_shape = list(logits.shape[:-1]) + [1]
    loss = helper.create_tmp_variable(dtype=dtype_name(logits.dtype),
                                      shape=loss_shape)
    sm = helper.create_tmp_variable(dtype=dtype_name(logits.dtype),
                                    shape=logits.shape)
    helper.append_op(type="softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Loss": [loss], "Softmax": [sm]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    if return_softmax:
        return loss, sm
    return loss


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    loss_shape = list(input.shape[:-1]) + [1]
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=loss_shape)
    helper.append_op(type="cross_entropy",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    return out


def sigmoid_cross_entropy_with_logits(x, label, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape)
    helper.append_op(type="sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x], "Label": [label]},
                     outputs={"Out": [out]})
    return out


def square_error_cost(input, label):
    """≙ reference layers/nn.py square_error_cost (fit-a-line loss)."""
    helper = LayerHelper("square_error_cost")
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=input.shape)
    helper.append_op(type="mse_loss", inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out]})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    loss = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                      shape=[x.shape[0], 1])
    diff = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                      shape=x.shape, stop_gradient=True)
    helper.append_op(type="smooth_l1_loss", inputs=inputs,
                     outputs={"Out": [loss], "Diff": [diff]},
                     attrs={"sigma": sigma or 1.0})
    return loss


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=input.shape)
    resid = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                       shape=input.shape, stop_gradient=True)
    helper.append_op(type="huber_loss",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out], "Residual": [resid]},
                     attrs={"delta": delta})
    return out


# ---------------------------------------------------------------- reductions
def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=[])
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def _reduce_layer(op_type, input, dim, keep_dim, name):
    helper = LayerHelper(op_type, name=name)
    shape = list(input.shape)
    if dim is None:
        out_shape = [] if not keep_dim else [1] * len(shape)
    else:
        dims = [dim] if isinstance(dim, int) else list(dim)
        dims = [d if d >= 0 else len(shape) + d for d in dims]
        out_shape = [1 if i in dims else d for i, d in enumerate(shape)] \
            if keep_dim else [d for i, d in enumerate(shape)
                              if i not in dims]
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=out_shape)
    helper.append_op(type=op_type, inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"dim": dim, "keep_dim": keep_dim,
                            "reduce_all": dim is None})
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_prod", input, dim, keep_dim, name)


# ---------------------------------------------------------------- manip
def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape", name=name, act=act)
    out_shape = list(shape)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                     shape=[d if d != 0 else x.shape[i]
                                            for i, d in enumerate(out_shape)])
    helper.append_op(type="reshape", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"shape": list(shape)})
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", name=name)
    out = helper.create_tmp_variable(
        dtype=dtype_name(x.dtype),
        shape=[x.shape[p] for p in perm] if x.shape else None)
    helper.append_op(type="transpose", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"axis": list(perm)})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    shape = list(input.shape)
    axis = dim if dim >= 0 else len(shape) + dim
    if isinstance(num_or_sections, int):
        n = num_or_sections
        sections = [shape[axis] // n] * n
        attrs = {"num": n, "axis": axis, "sections": []}
    else:
        sections = list(num_or_sections)
        attrs = {"num": 0, "axis": axis, "sections": sections}
    outs = []
    for s in sections:
        os = list(shape)
        os[axis] = s
        outs.append(helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                               shape=os))
    helper.append_op(type="split", inputs={"X": [input]},
                     outputs={"Out": outs}, attrs=attrs)
    return outs


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze", name=name)
    shape = [d for i, d in enumerate(input.shape) if i not in axes] \
        if input.shape else None
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=shape)
    helper.append_op(type="squeeze", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    shape = list(input.shape)
    for ax in sorted(axes):
        shape.insert(ax, 1)
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=shape)
    helper.append_op(type="unsqueeze", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axes": list(axes)})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", name=name)
    lead = _prod(x.shape[:axis]) if axis > 0 else 1
    trail = _prod(x.shape[axis:])
    if any(d == -1 for d in x.shape[:axis]):
        lead = -1
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                     shape=[lead, trail])
    helper.append_op(type="flatten", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    xs = x if isinstance(x, (list, tuple)) else [x]
    shape = list(xs[0].shape)
    shape.insert(axis if axis >= 0 else len(shape) + 1 + axis, len(xs))
    out = helper.create_tmp_variable(dtype=dtype_name(xs[0].dtype),
                                     shape=shape)
    helper.append_op(type="stack", inputs={"X": list(xs)},
                     outputs={"Y": [out]}, attrs={"axis": axis})
    return out


def gather(input, index):
    helper = LayerHelper("gather")
    out_shape = list(index.shape) + list(input.shape[1:])
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=out_shape)
    helper.append_op(type="gather",
                     inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def scatter(input, index, updates, overwrite=True):
    helper = LayerHelper("scatter")
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=input.shape)
    helper.append_op(type="scatter",
                     inputs={"X": [input], "Ids": [index],
                             "Updates": [updates]},
                     outputs={"Out": [out]}, attrs={"overwrite": overwrite})
    return out


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    shape = [(-1 if d == -1 else d * t)
             for d, t in zip(x.shape, expand_times)]
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=shape)
    helper.append_op(type="expand", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"expand_times": list(expand_times)})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    shape = [(-1 if d == -1 else d + paddings[2 * i] + paddings[2 * i + 1])
             for i, d in enumerate(x.shape)]
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=shape)
    helper.append_op(type="pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": list(paddings),
                            "pad_value": pad_value})
    return out


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    shape = list(input.shape)
    if shape and shape[-1] == 1:
        shape = shape[:-1]
    out = helper.create_tmp_variable(dtype="float32", shape=shape + [depth],
                                     stop_gradient=True)
    helper.append_op(type="one_hot", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"depth": depth})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape)
    helper.append_op(type="clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": min, "max": max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape)
    helper.append_op(type="clip_by_norm", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"max_norm": max_norm})
    return out


# ---------------------------------------------------------------- metrics
def accuracy(input, label, k=1, correct=None, total=None):
    """≙ reference layers/metric_op.py accuracy: top-k then accuracy op."""
    helper = LayerHelper("accuracy")
    topk_out, topk_indices = topk(input, k=k)
    acc = helper.create_tmp_variable(dtype="float32", shape=[],
                                     stop_gradient=True)
    correct = correct or helper.create_tmp_variable(dtype="int32", shape=[],
                                                    stop_gradient=True)
    total = total or helper.create_tmp_variable(dtype="int32", shape=[],
                                                stop_gradient=True)
    helper.append_op(type="accuracy",
                     inputs={"Out": [topk_out], "Indices": [topk_indices],
                             "Label": [label]},
                     outputs={"Accuracy": [acc], "Correct": [correct],
                              "Total": [total]})
    return acc


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    shape = list(input.shape[:-1]) + [k]
    values = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                        shape=shape, stop_gradient=True)
    indices = helper.create_tmp_variable(dtype="int64", shape=shape,
                                         stop_gradient=True)
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    return values, indices


def auc(input, label, curve="ROC", num_thresholds=200, topk=1):
    """≙ reference layers/metric_op.py auc — streaming AUC with persistable
    bucket state."""
    helper = LayerHelper("auc")
    stat_pos = helper.create_global_variable(
        name=helper.name + ".stat_pos", shape=[num_thresholds + 1],
        dtype="float32")
    stat_neg = helper.create_global_variable(
        name=helper.name + ".stat_neg", shape=[num_thresholds + 1],
        dtype="float32")
    for var in (stat_pos, stat_neg):
        sb = helper.startup_program.global_block()
        if var.name not in sb.vars:
            sv = sb.create_var(name=var.name, shape=var.shape,
                               dtype=var.dtype, persistable=True)
            sb.append_op("fill_constant", outputs={"Out": [sv.name]},
                         attrs={"shape": list(var.shape), "value": 0.0,
                                "dtype": "float32"})
    auc_out = helper.create_tmp_variable(dtype="float32", shape=[],
                                         stop_gradient=True)
    helper.append_op(type="auc",
                     inputs={"Predict": [input], "Label": [label],
                             "StatPos": [stat_pos], "StatNeg": [stat_neg]},
                     outputs={"AUC": [auc_out], "StatPosOut": [stat_pos],
                              "StatNegOut": [stat_neg]},
                     attrs={"num_thresholds": num_thresholds})
    return auc_out, [stat_pos, stat_neg]


# ---------------------------------------------------------------- misc
def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None,
           use_bf16=False, out_dtype=None):
    helper = LayerHelper("matmul", name=name)
    xs, ys = list(x.shape), list(y.shape)
    if transpose_x:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    batch = xs[:-2] if len(xs) >= len(ys) else ys[:-2]
    out_shape = batch + [xs[-2] if len(xs) > 1 else 1, ys[-1]]
    out = helper.create_tmp_variable(dtype=out_dtype or dtype_name(x.dtype),
                                     shape=out_shape)
    attrs = {"transpose_X": transpose_x, "transpose_Y": transpose_y,
             "alpha": alpha, "use_bf16": use_bf16}
    if out_dtype:           # as `fc`: float32 logits over bfloat16 rows
        attrs["out_dtype"] = out_dtype
    helper.append_op(type="matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def elementwise_op_layer(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name, act=act)
    xs, ys = x.shape or (), y.shape or ()
    if len(xs) == len(ys) and all(
            d is not None and d != -1 for d in (*xs, *ys)):
        # equal-rank operands: declare the true numpy broadcast shape
        # (size-1 dims stretch), so e.g. [S,1,1] + [1,G,1] declares
        # [S,G,1] — what the analyzer's inference derives
        shape = [max(a, b) for a, b in zip(xs, ys)]
    else:
        shape = xs if len(xs) >= len(ys) else ys
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=shape)
    helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return elementwise_op_layer("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return elementwise_op_layer("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return elementwise_op_layer("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return elementwise_op_layer("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return elementwise_op_layer("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return elementwise_op_layer("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return elementwise_op_layer("elementwise_pow", x, y, axis, act, name)


def cache_write(cache, new, pos, axis, batch_axis=None, out=None, name=None):
    """Write `new` (size-1 along `axis`) into `cache` at position `pos` —
    the KV-cache decode primitive (lowers to an in-place
    dynamic_update_slice inside scan carries).

    Default mode: `pos` is one scalar position for the whole batch (any
    tensor; its first element is the position — the contract is enforced).
    With `batch_axis` set, `pos` holds one position PER ROW of `cache`
    along that axis and each row is written at its own position — the
    slot-indexed cache the continuous-batching serving engine runs on.
    `out` (optional Variable) receives the result in place of a fresh
    temporary — pass the cache variable itself to round-trip a persistable
    serving cache through the executor's donated state path."""
    helper = LayerHelper("cache_write", name=name)
    if out is None:
        out = helper.create_tmp_variable(dtype=dtype_name(cache.dtype),
                                         shape=cache.shape,
                                         stop_gradient=True)
    attrs = {"axis": axis}
    if batch_axis is not None:
        attrs["batch_axis"] = batch_axis
    helper.append_op(type="cache_write",
                     inputs={"Cache": [cache], "New": [new], "Pos": [pos]},
                     outputs={"Out": [out]},
                     attrs=attrs)
    return out


def paged_cache_write(pool, new, block_ids, offsets, out=None, name=None,
                      chunk=None, chunk_block_ids=None):
    """Scatter one new KV row per tick slot into the paged block pool —
    the block-granular counterpart of `cache_write` (serving/kv_pager.py).
    `pool` is [n_blocks, nh, block_size, dh]; `new` is [S, nh, dh];
    `block_ids`/`offsets` give each slot's physical target
    (pool[block_ids[s], :, offsets[s], :]). Pass the pool variable as
    `out` to round-trip the persistable pool through the executor's
    donated-state path, same as `cache_write(out=...)`. The mixed tick's
    prefill lanes add `chunk` [L, C, H] (C block-aligned token rows a
    lane) and `chunk_block_ids` [L * C/block_size]: whole blocks, written
    by the same op so that a pool keeps one writer."""
    helper = LayerHelper("paged_cache_write", name=name)
    if out is None:
        out = helper.create_tmp_variable(dtype=dtype_name(pool.dtype),
                                         shape=pool.shape,
                                         stop_gradient=True)
    inputs = {"Cache": [pool], "New": [new], "BlockIds": [block_ids],
              "Offsets": [offsets]}
    if chunk is not None:
        inputs["Chunk"], inputs["ChunkBlockIds"] = [chunk], [chunk_block_ids]
    helper.append_op(type="paged_cache_write", inputs=inputs,
                     outputs={"Out": [out]})
    return out


def paged_cache_write_quant(pool, scales, new, block_ids, offsets,
                            out=None, scales_out=None, name=None):
    """int8 paged KV write: quantize each f32 row of `new` over its dh
    vector (symmetric amax/127) and scatter payload + per-row scale into
    `pool` (int8, [n_blocks, nh, block_size, dh]) and `scales` (f32,
    [n_blocks, nh, block_size, 1]). Returns (pool_out, scales_out); pass
    the pool variables themselves as `out`/`scales_out` to round-trip both
    through the executor's donated-state path, as `paged_cache_write`
    does. The read side dequantizes with one cast+multiply against the
    gathered scale view — XLA fuses it into the cache read, so the HBM
    resident AND streamed bytes are the int8 payload."""
    helper = LayerHelper("paged_cache_write_quant", name=name)
    if out is None:
        out = helper.create_tmp_variable(dtype=dtype_name(pool.dtype),
                                         shape=pool.shape,
                                         stop_gradient=True)
    if scales_out is None:
        scales_out = helper.create_tmp_variable(
            dtype=dtype_name(scales.dtype), shape=scales.shape,
            stop_gradient=True)
    helper.append_op(type="paged_cache_write_quant",
                     inputs={"Cache": [pool], "Scales": [scales],
                             "New": [new], "BlockIds": [block_ids],
                             "Offsets": [offsets]},
                     outputs={"Out": [out], "ScalesOut": [scales_out]})
    return out, scales_out


def paged_decode_attention(q, k_pool, v_pool, block_table, pos, num_heads,
                           scale=1.0, k_scale=None, v_scale=None,
                           name=None, n_rows=None, window=0):
    """Attention of each tick slot's query rows over its PAGED cache, read
    through the block table (fusion/paged_attention.py). `q` is [S, G, H]
    (G = 1 for the decode tick, γ+1 for a verify window, the chunk length
    for a prefill lane), `k_pool`/`v_pool`
    the written pools [n_blocks, nh, block_size, dh] (with `k_scale`/
    `v_scale` [n_blocks, nh, block_size, 1] when they are int8),
    `block_table` [S, NLB], `pos` the position of each slot's first query
    row (S elements; row g attends cache positions 0..pos+g). `n_rows`
    (S elements, optional) says how many of a slot's G rows are real: the
    rest, and a slot with none, return values nobody reads, and the blocks
    only they would attend are not fetched. `window` > 0: a row attends its
    last `window` positions only (a sliding-window layer, read through its
    own table). Returns [S, G, H]."""
    helper = LayerHelper("paged_decode_attention", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(q.dtype),
                                     shape=q.shape, stop_gradient=True)
    inputs = {"Q": [q], "KPool": [k_pool], "VPool": [v_pool],
              "BlockTable": [block_table], "Pos": [pos]}
    if k_scale is not None:
        inputs["KScale"], inputs["VScale"] = [k_scale], [v_scale]
    if n_rows is not None:
        inputs["Rows"] = [n_rows]
    attrs = {"num_heads": num_heads, "scale": float(scale)}
    if window:
        attrs["window"] = int(window)
    helper.append_op(type="paged_decode_attention", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """RMSNorm over the last axis with a learned scale (no bias, no mean):
    statistics in float32, the result in the input's dtype."""
    helper = LayerHelper("rms_norm", name=name)
    dtype = dtype_name(input.dtype)
    scale = helper.create_parameter(
        param_attr, shape=[input.shape[-1]], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    y = helper.create_tmp_variable(dtype=dtype, shape=input.shape)
    helper.append_op(type="rms_norm", inputs={"X": [input], "Scale": [scale]},
                     outputs={"Y": [y]}, attrs={"epsilon": float(epsilon)})
    return y


def rotary(x, pos, table, name=None, stop_gradient=True):
    """Rotary positions: rotate each head's values of `x` [N, .., heads*dim]
    by the angles of row `pos[n]` of `table` [T, dim] (cos | sin). A
    training graph passes `stop_gradient=False`: the rotation is linear in
    x and its gradient flows."""
    helper = LayerHelper("rotary", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape,
                                     stop_gradient=stop_gradient)
    helper.append_op(type="rotary",
                     inputs={"X": [x], "Pos": [pos], "Table": [table]},
                     outputs={"Out": [out]})
    return out


def latent_head_proj(x, w, mode, num_heads, k_dim, v_dim, name=None):
    """One half of latent attention's `kv_b_proj` `w` [c, nh*(k_dim+v_dim)],
    a head at a time: mode "absorb_q" takes q_nope [.., nh*k_dim] to the
    latent [.., nh*c]; "expand_v" takes a latent context [.., nh*c] to
    values [.., nh*v_dim] (fusion/latent_attention.py)."""
    helper = LayerHelper("latent_head_proj", name=name)
    width = num_heads * (w.shape[0] if mode == "absorb_q" else v_dim)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                     shape=list(x.shape[:-1]) + [width],
                                     stop_gradient=True)
    helper.append_op(type="latent_head_proj", inputs={"X": [x], "W": [w]},
                     outputs={"Out": [out]},
                     attrs={"mode": mode, "num_heads": num_heads,
                            "k_dim": k_dim, "v_dim": v_dim})
    return out


def latent_paged_attention(q, pool, block_table, pos, num_heads, v_width,
                           scale, n_rows=None, name=None):
    """Attention of padded per-head query rows `q` [S, G, nh*W] over the
    latent rows of `pool` [n_blocks, 1, block_size, W], read through the
    block table; returns per head the weighted sum of the rows' first
    `v_width` values, [S, G, nh*v_width] (fusion/latent_attention.py)."""
    helper = LayerHelper("latent_paged_attention", name=name)
    out = helper.create_tmp_variable(
        dtype=dtype_name(q.dtype),
        shape=list(q.shape[:-1]) + [num_heads * v_width], stop_gradient=True)
    inputs = {"Q": [q], "Pool": [pool], "BlockTable": [block_table],
              "Pos": [pos]}
    if n_rows is not None:
        inputs["Rows"] = [n_rows]
    helper.append_op(type="latent_paged_attention", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"num_heads": num_heads, "v_width": v_width,
                            "scale": float(scale)})
    return out


def sparse_latent_attention(q, pool, index_pool, qi, ki, wi, positions,
                            table, block_table, wblock, woff, num_heads,
                            v_width, scale, indexer, lanes=None, name=None):
    """The latent read over the rows an indexer SELECTS
    (fusion/sparse_latent_attention.py): `q` [N, 1, nh*W] the tick's padded
    query rows, `pool` the written latent pool, `index_pool` the pooled index
    keys' pool (written here, in place), `qi` / `ki` / `wi` the rows' index
    queries [N, 1, heads*dim], key [N, 1, dim] and head weights [N, 1,
    heads], `positions` [N, 1, 1], `table` the indexer's rotary table, the
    decode rows' `block_table`, `wblock`, `woff`; `lanes` (dict: lbtab,
    lwblocks, lrows, chunk) where the tick has them; `indexer` the
    `IndexerSpec`. Returns [N, 1, nh*v_width]."""
    helper = LayerHelper("sparse_latent_attention", name=name)
    out = helper.create_tmp_variable(
        dtype=dtype_name(q.dtype),
        shape=list(q.shape[:-1]) + [num_heads * v_width], stop_gradient=True)
    inputs = {"Q": [q], "Pool": [pool], "IndexPool": [index_pool],
              "QI": [qi], "KI": [ki], "WI": [wi], "Positions": [positions],
              "Table": [table], "BlockTable": [block_table],
              "WBlock": [wblock], "WOff": [woff]}
    attrs = {"num_heads": num_heads, "v_width": v_width,
             "scale": float(scale), "index_heads": indexer.heads,
             "top_groups": indexer.top_groups, "kpool": indexer.kpool}
    if lanes is not None:
        inputs.update(LaneBlockTable=[lanes["lbtab"]],
                      LaneWBlocks=[lanes["lwblocks"]],
                      LaneRows=[lanes["lrows"]])
        attrs["chunk"] = int(lanes["chunk"])
    helper.append_op(type="sparse_latent_attention", inputs=inputs,
                     outputs={"Out": [out], "IndexPoolOut": [index_pool]},
                     attrs=attrs)
    return out


def hyper_connection_pre(x, p, a, b, hyper, norm_eps, name=None):
    """The streams `x` [N, 1, n*d] mixed into ONE row for a sub-layer
    (fusion/hyper_connection.py): returns (the row [N, 1, d], H_post [N, n],
    H_res [N, n*n]), the maps float32."""
    helper = LayerHelper("hyper_connection_pre", name=name)
    n, rows = hyper.mult, _prod(x.shape[:-1])
    out = helper.create_tmp_variable(
        dtype=dtype_name(x.dtype),
        shape=list(x.shape[:-1]) + [x.shape[-1] // n], stop_gradient=True)
    h_post = helper.create_tmp_variable(dtype="float32", shape=[rows, n],
                                        stop_gradient=True)
    h_res = helper.create_tmp_variable(dtype="float32", shape=[rows, n * n],
                                       stop_gradient=True)
    helper.append_op(type="hyper_connection_pre",
                     inputs={"X": [x], "P": [p], "A": [a], "B": [b]},
                     outputs={"Out": [out], "HPost": [h_post],
                              "HRes": [h_res]},
                     attrs={"mult": n,
                            "sinkhorn_iters": int(hyper.sinkhorn_iters),
                            "eps": float(hyper.eps),
                            "norm_eps": float(norm_eps)})
    return out, h_post, h_res


def hyper_connection_post(x, y, h_post, h_res, hyper, name=None):
    """The streams after a sub-layer: `H_res x + H_post^T y`, as `x`."""
    helper = LayerHelper("hyper_connection_post", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape,
                                     stop_gradient=True)
    helper.append_op(type="hyper_connection_post",
                     inputs={"X": [x], "Y": [y], "HPost": [h_post],
                             "HRes": [h_res]},
                     outputs={"Out": [out]}, attrs={"mult": hyper.mult})
    return out


def hyper_connection_exit(x, hyper, name=None):
    """The streams' sum: [N, 1, n*d] -> [N, 1, d]."""
    helper = LayerHelper("hyper_connection_exit", name=name)
    out = helper.create_tmp_variable(
        dtype=dtype_name(x.dtype),
        shape=list(x.shape[:-1]) + [x.shape[-1] // hyper.mult],
        stop_gradient=True)
    helper.append_op(type="hyper_connection_exit", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"mult": hyper.mult})
    return out


def moe_route(x, w_router, held, top_k, scaling, norm_topk_prob=True,
              live=None, name=None, bias=None, norm_eps=0.0, groups=None):
    """Sigmoid top-k routing over every column of `w_router`; returns the
    dense weights of the `held` experts [n_held, N, 1] (float32) and the
    rows each got [n_held] (int32) (fusion/moe.py). `live` [N]: rows that
    are 0 there select nothing. `bias` [E]: the selection is the top-k of
    score + bias, the weights the unbiased scores; `norm_eps` joins the sum
    they are divided by. `groups` (n_group, topk_group): only the experts of
    the `topk_group` best of `n_group` groups are eligible."""
    helper = LayerHelper("moe_route", name=name)
    n = _prod(x.shape[:-1])
    weights = helper.create_tmp_variable(dtype="float32",
                                         shape=[len(held), n, 1],
                                         stop_gradient=True)
    rows = helper.create_tmp_variable(dtype="int32", shape=[len(held)],
                                      stop_gradient=True)
    inputs = {"X": [x], "W": [w_router]}
    if live is not None:
        inputs["Live"] = [live]
    attrs = {"held": [int(e) for e in held], "top_k": int(top_k),
             "scaling": float(scaling),
             "norm_topk_prob": bool(norm_topk_prob)}
    if bias is not None:
        inputs["Bias"] = [bias]
    if norm_eps:
        attrs["norm_eps"] = float(norm_eps)
    if groups is not None:
        attrs["n_group"], attrs["topk_group"] = map(int, groups)
    helper.append_op(type="moe_route", inputs=inputs,
                     outputs={"Weights": [weights], "Rows": [rows]},
                     attrs=attrs)
    return weights, rows


def moe_train(x, w_router, held, top_k, gate, up, down, counters,
              scaling=1.0, norm_topk_prob=True, name=None):
    """The routed layer of a TRAINING graph, differentiable in x, the
    router and the three stacks (fusion/moe.py `moe_train`): softmax scores
    over every column of `w_router`, the top-k, weights normalised over the
    selected, the `held` experts' part of the sum by a grouped product over
    the (row, expert) pairs sorted by expert. Returns (out as x, aux: the
    layer's balance term, a scalar). `counters`: a name prefix; the step
    keeps, as persistable variables it adds to in the graph,
    `<prefix>.rows` [n_held] (pairs each held expert got), `<prefix>.pairs`
    [3] (routed, held, dropped) and `<prefix>.aux` [1] (the balance term's
    last value)."""
    helper = LayerHelper("moe_train", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape)
    aux = helper.create_tmp_variable(dtype="float32", shape=[])
    inputs = {"X": [x], "W": [w_router], "Gate": [gate], "Up": [up],
              "Down": [down]}
    outputs = {"Out": [out], "Aux": [aux]}
    for slot, suffix, shape, dtype in (
            ("RowsTotal", "rows", [len(held)], "int32"),
            ("PairsTotal", "pairs", [3], "int32"),
            ("AuxLast", "aux", [1], "float32")):
        var = helper.create_parameter(
            ParamAttr(name=f"{counters}.{suffix}", trainable=False),
            shape=shape, dtype=dtype,
            default_initializer=ConstantInitializer(0.0))
        var.stop_gradient = True
        inputs[slot], outputs[slot + "Out"] = [var], [var]
    helper.append_op(type="moe_train", inputs=inputs, outputs=outputs,
                     attrs={"held": [int(e) for e in held],
                            "n_routed": int(w_router.shape[-1]),
                            "top_k": int(top_k), "scaling": float(scaling),
                            "norm_topk_prob": bool(norm_topk_prob)})
    return out, aux


def moe_experts(x, weights, rows, gate, up, down, name=None, limit=0.0):
    """The held experts' part of a routed layer: sum over them of
    `weights[e] * down_e(silu(gate_e x) * up_e x)`, skipping every expert
    whose `rows[e]` is 0 (fusion/moe.py). `gate` None: an expert is
    `down_e(relu(up_e x)^2)`. `limit` > 0: the pair is clamped,
    `silu(min(gate, limit)) * clip(up, -limit, limit)`."""
    helper = LayerHelper("moe_experts", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape,
                                     stop_gradient=True)
    inputs = {"X": [x], "Weights": [weights], "Rows": [rows], "Up": [up],
              "Down": [down]}
    if gate is not None:
        inputs["Gate"] = [gate]
    helper.append_op(type="moe_experts", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"limit": float(limit)} if limit else {})
    return out


def short_conv(u, taps, slot_state, layer, n_slots, lanes=None, name=None):
    """The causal part of a gated short convolution over a tick's rows `u`
    [S + L*C, 1, D] (S decode rows, then the lanes'), `taps` [D, K], from
    the state in `slot_state` and, with `lanes` (dict: block_state, lbtab,
    lpos, lrows, chunk, block_size), the block snapshots a chunk starts
    from (fusion/short_conv.py). Returns (c, the decode rows' new state,
    and with lanes the chunks' block snapshots and last states)."""
    helper = LayerHelper("short_conv", name=name)
    dtype, d = dtype_name(u.dtype), u.shape[-1]
    r = taps.shape[1] - 1
    tmp = lambda shape: helper.create_tmp_variable(  # noqa: E731
        dtype=dtype, shape=shape, stop_gradient=True)
    out, new_d = tmp(u.shape), tmp([n_slots, r, d])
    inputs = {"U": [u], "Taps": [taps], "SlotState": [slot_state]}
    outputs = {"Out": [out], "DecodeState": [new_d]}
    attrs = {"layer": int(layer), "n_slots": int(n_slots)}
    snaps = last = None
    if lanes is not None:
        n_lanes = lanes["lbtab"].shape[0]
        per_lane = lanes["chunk"] // lanes["block_size"]
        snaps, last = tmp([n_lanes * per_lane, r, d]), tmp([n_lanes, r, d])
        inputs.update(BlockState=[lanes["block_state"]],
                      LaneBlockTable=[lanes["lbtab"]],
                      LanePos=[lanes["lpos"]], LaneRows=[lanes["lrows"]])
        outputs.update(LaneSnaps=[snaps], LaneState=[last])
        attrs.update(chunk=int(lanes["chunk"]),
                     block_size=int(lanes["block_size"]))
    helper.append_op(type="short_conv", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return out, new_d, snaps, last


def conv_state_commit(slot_state, new_states, live, lanes=None, name=None):
    """Write a tick's conv states (`new_states`: a list a conv layer of
    `short_conv`'s decode states) into `slot_state`, and with `lanes`
    (dict: block_state, snaps, last (a list a layer each), lwblocks, lrows,
    lslot, block_size) the lanes' into `block_state` and their slots, in
    place (fusion/short_conv.py)."""
    helper = LayerHelper("conv_state_commit", name=name)
    inputs = {"SlotState": [slot_state], "DecodeState": list(new_states),
              "Live": [live]}
    outputs = {"SlotStateOut": [slot_state]}
    attrs = {}
    if lanes is not None:
        inputs.update(BlockState=[lanes["block_state"]],
                      LaneSnaps=list(lanes["snaps"]),
                      LaneState=list(lanes["last"]),
                      LaneWriteBlocks=[lanes["lwblocks"]],
                      LaneRows=[lanes["lrows"]], LaneSlot=[lanes["lslot"]])
        outputs["BlockStateOut"] = [lanes["block_state"]]
        attrs["block_size"] = int(lanes["block_size"])
    helper.append_op(type="conv_state_commit", inputs=inputs,
                     outputs=outputs, attrs=attrs)
    return slot_state


def ssm_scan(xbc, dt, params, state, live, ssm, lanes=None, name=None):
    """One state-space layer's convolution and scan over a tick's rows
    (fusion/ssm.py): `xbc` [N, 1, conv_dim] and `dt` [N, 1, heads] from the
    input projection, `params` (dict: taps, conv_bias, a_log, dt_bias, d),
    `state` (dict: slot_h, slot_conv, and with `lanes` snap_h, snap_conv), all
    updated in place, `lanes` (dict: lpos, lrows, lslot, snap_src, snap_dst,
    snap_rows, chunk), `ssm` the `SsmSpec`. Returns y [N, 1, d_inner]."""
    helper = LayerHelper("ssm_scan", name=name)
    out = helper.create_tmp_variable(
        dtype=dtype_name(xbc.dtype),
        shape=list(xbc.shape[:-1]) + [ssm.d_inner], stop_gradient=True)
    inputs = {"XBC": [xbc], "Dt": [dt], "Taps": [params["taps"]],
              "ConvBias": [params["conv_bias"]], "ALog": [params["a_log"]],
              "DtBias": [params["dt_bias"]], "D": [params["d"]],
              "SlotH": [state["slot_h"]], "SlotConv": [state["slot_conv"]],
              "Live": [live]}
    outputs = {"Out": [out], "SlotHOut": [state["slot_h"]],
               "SlotConvOut": [state["slot_conv"]]}
    attrs = {"heads": ssm.heads, "head_dim": ssm.head_dim,
             "groups": ssm.groups, "state": ssm.state}
    if lanes is not None:
        inputs.update(SnapH=[state["snap_h"]], SnapConv=[state["snap_conv"]],
                      LanePos=[lanes["lpos"]], LaneRows=[lanes["lrows"]],
                      LaneSlot=[lanes["lslot"]], SnapSrc=[lanes["snap_src"]],
                      SnapDst=[lanes["snap_dst"]],
                      SnapRows=[lanes["snap_rows"]])
        outputs.update(SnapHOut=[state["snap_h"]],
                       SnapConvOut=[state["snap_conv"]])
        attrs["chunk"] = int(lanes["chunk"])
    helper.append_op(type="ssm_scan", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return out


def gated_rms_norm(x, z, groups, epsilon=1e-5, param_attr=None, name=None):
    """RMSNorm over each of `groups` groups of the last dimension of
    `x * silu(z)` (the gate first), with a learned scale a value
    (fusion/ssm.py)."""
    helper = LayerHelper("gated_rms_norm", name=name)
    scale = helper.create_parameter(
        param_attr, shape=[x.shape[-1]], dtype=dtype_name(x.dtype),
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape,
                                     stop_gradient=True)
    helper.append_op(type="gated_rms_norm",
                     inputs={"X": [x], "Z": [z], "Scale": [scale]},
                     outputs={"Out": [out]},
                     attrs={"groups": int(groups), "epsilon": float(epsilon)})
    return out


def kda_scan(qkv, f, b, params, state, live, kda, lanes=None, name=None):
    """One kda layer's convolution and delta-rule scan over a tick's rows
    (fusion/kda.py): `qkv` [N, 1, conv_dim], the gate's `f` [N, 1, d_inner]
    and `b` [N, 1, heads] from the input projections, `params` (dict: taps,
    a_log, dt_bias), `state` and `lanes` as `ssm_scan` takes them, `kda` the
    `KdaSpec`. Returns o [N, 1, d_inner]."""
    helper = LayerHelper("kda_scan", name=name)
    out = helper.create_tmp_variable(
        dtype=dtype_name(qkv.dtype),
        shape=list(qkv.shape[:-1]) + [kda.d_inner], stop_gradient=True)
    inputs = {"QKV": [qkv], "F": [f], "B": [b], "Taps": [params["taps"]],
              "ALog": [params["a_log"]], "DtBias": [params["dt_bias"]],
              "SlotH": [state["slot_h"]], "SlotConv": [state["slot_conv"]],
              "Live": [live]}
    outputs = {"Out": [out], "SlotHOut": [state["slot_h"]],
               "SlotConvOut": [state["slot_conv"]]}
    attrs = {"heads": kda.heads, "head_dim": kda.head_dim,
             "gate_lower_bound": float(kda.gate_lower_bound)}
    if lanes is not None:
        inputs.update(SnapH=[state["snap_h"]], SnapConv=[state["snap_conv"]],
                      LanePos=[lanes["lpos"]], LaneRows=[lanes["lrows"]],
                      LaneSlot=[lanes["lslot"]], SnapSrc=[lanes["snap_src"]],
                      SnapDst=[lanes["snap_dst"]],
                      SnapRows=[lanes["snap_rows"]])
        outputs.update(SnapHOut=[state["snap_h"]],
                       SnapConvOut=[state["snap_conv"]])
        attrs["chunk"] = int(lanes["chunk"])
    helper.append_op(type="kda_scan", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return out


def kda_gate_norm(x, gate, heads, epsilon=1e-6, param_attr=None, name=None):
    """RMSNorm over each of `heads` heads of the last dimension of `x` with
    ONE learned scale a head value (shared by the heads), times
    sigmoid(`gate`) (fusion/kda.py)."""
    helper = LayerHelper("kda_gate_norm", name=name)
    scale = helper.create_parameter(
        param_attr, shape=[x.shape[-1] // heads], dtype=dtype_name(x.dtype),
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape,
                                     stop_gradient=True)
    helper.append_op(type="kda_gate_norm",
                     inputs={"X": [x], "Gate": [gate], "Scale": [scale]},
                     outputs={"Out": [out]},
                     attrs={"heads": int(heads), "epsilon": float(epsilon)})
    return out


def head_gate(x, gate, heads, name=None):
    """`x` [.., heads * d] times sigmoid(`gate`) [.., heads], a value a head
    (fusion/kda.py)."""
    helper = LayerHelper("head_gate", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape,
                                     stop_gradient=True)
    helper.append_op(type="head_gate", inputs={"X": [x], "Gate": [gate]},
                     outputs={"Out": [out]}, attrs={"heads": int(heads)})
    return out


def lrn(input, n=5, k=2.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=input.shape)
    mid = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=input.shape, stop_gradient=True)
    helper.append_op(type="lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape)
    norm = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                      shape=x.shape, stop_gradient=True)
    helper.append_op(type="l2_normalize", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    out = helper.create_tmp_variable(dtype=dtype, shape=label.shape)
    helper.append_op(type="label_smooth", inputs=inputs,
                     outputs={"Out": [out]}, attrs={"epsilon": epsilon})
    return out


def slice(input, axes, starts, ends, name=None):
    """≙ reference slice_op.cc — static slice."""
    helper = LayerHelper("slice", name=name)
    out_shape = list(input.shape)
    for ax, s, e in zip(axes, starts, ends):
        if out_shape[ax] is not None and out_shape[ax] >= 0:
            dim = out_shape[ax]
            # python slice clamping semantics, matching the runtime x[s:e]
            s2 = min(max(s if s >= 0 else dim + s, 0), dim)
            e2 = min(max(e if e >= 0 else dim + e, 0), dim)
            out_shape[ax] = max(e2 - s2, 0)
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=out_shape)
    helper.append_op(type="slice", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return out


# ---------------------------------------------------------------- losses
# ≙ reference nn.py / operators "Losses" family (SURVEY §2.2)


def rank_loss(label, left, right, name=None):
    """Pairwise RankNet loss (≙ rank_loss_op.cc)."""
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(left.dtype),
                                     shape=left.shape)
    helper.append_op(type="rank_loss",
                     inputs={"Label": [label], "Left": [left],
                             "Right": [right]},
                     outputs={"Out": [out]})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    """≙ margin_rank_loss_op.cc: max(0, -label*(left-right) + margin)."""
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(left.dtype),
                                     shape=left.shape)
    act = helper.create_tmp_variable(dtype=dtype_name(left.dtype),
                                     shape=left.shape, stop_gradient=True)
    helper.append_op(type="margin_rank_loss",
                     inputs={"Label": [label], "X1": [left], "X2": [right]},
                     outputs={"Out": [out], "Activated": [act]},
                     attrs={"margin": float(margin)})
    return out


def hinge_loss(input, label, name=None):
    """≙ hinge_loss_op.cc: max(0, 1 - input*(2*label-1))."""
    helper = LayerHelper("hinge_loss", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=input.shape)
    helper.append_op(type="hinge_loss",
                     inputs={"Logits": [input], "Labels": [label]},
                     outputs={"Loss": [out]})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    """≙ log_loss_op.cc: binary CE on probabilities."""
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=input.shape)
    helper.append_op(type="log_loss",
                     inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [out]},
                     attrs={"epsilon": float(epsilon)})
    return out


def cos_sim(X, Y, name=None):
    """Row-wise cosine similarity; Y may be one row (≙ cos_sim_op.cc)."""
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(X.dtype),
                                     shape=[X.shape[0], 1])
    xn = helper.create_tmp_variable(dtype=dtype_name(X.dtype),
                                    shape=[X.shape[0], 1],
                                    stop_gradient=True)
    yn = helper.create_tmp_variable(dtype=dtype_name(X.dtype),
                                    shape=[Y.shape[0], 1],
                                    stop_gradient=True)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]})
    return out


def squared_l2_norm(x, name=None):
    """sum(x**2) (≙ squared_l2_norm_op.cc)."""
    helper = LayerHelper("squared_l2_norm", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=[1])
    helper.append_op(type="squared_l2_norm", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def squared_l2_distance(x, y, name=None):
    """Row-wise ||x-y||^2 (≙ squared_l2_distance_op.cc)."""
    helper = LayerHelper("squared_l2_distance", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                     shape=[x.shape[0], 1])
    sub = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                     shape=x.shape, stop_gradient=True)
    helper.append_op(type="squared_l2_distance",
                     inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out], "sub_result": [sub]})
    return out


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    """out[n,k] = x[n] @ W_k @ y[n]^T (≙ bilinear_tensor_product_op.cc)."""
    helper = LayerHelper("bilinear_tensor_product", name=name, act=act,
                         param_attr=param_attr, bias_attr=bias_attr)
    dx, dy = x.shape[1], y.shape[1]
    w = helper.create_parameter(attr=param_attr, shape=[size, dx, dy],
                                dtype=dtype_name(x.dtype))
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if bias_attr is not False:
        bias = helper.create_parameter(attr=bias_attr, shape=[1, size],
                                       dtype=dtype_name(x.dtype),
                                       is_bias=True)
        inputs["Bias"] = [bias]
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype),
                                     shape=[x.shape[0], size])
    helper.append_op(type="bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=10, name=None):
    """NCE loss with a uniform negative sampler (≙ nce_op.cc + layers/nn.py
    nce). Returns per-example cost [N, 1]."""
    helper = LayerHelper("nce", name=name, param_attr=param_attr,
                         bias_attr=bias_attr)
    dim = input.shape[1]
    w = helper.create_parameter(attr=param_attr,
                                shape=[num_total_classes, dim],
                                dtype=dtype_name(input.dtype))
    inputs = {"Input": [input], "Label": [label], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr,
                                    shape=[num_total_classes],
                                    dtype=dtype_name(input.dtype),
                                    is_bias=True)
        inputs["Bias"] = [b]
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight]
    n = input.shape[0]
    cost = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                      shape=[n, 1])
    slog = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                      shape=[n, num_neg_samples + 1],
                                      stop_gradient=True)
    slab = helper.create_tmp_variable(dtype="int64",
                                      shape=[n, num_neg_samples + 1],
                                      stop_gradient=True)
    helper.append_op(type="nce", inputs=inputs,
                     outputs={"Cost": [cost], "SampleLogits": [slog],
                              "SampleLabels": [slab]},
                     attrs={"num_total_classes": int(num_total_classes),
                            "num_neg_samples": int(num_neg_samples)})
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """Hierarchical sigmoid over a complete binary tree
    (≙ hsigmoid_op.cc + math/matrix_bit_code.h). Returns cost [N, 1]."""
    helper = LayerHelper("hierarchical_sigmoid", name=name,
                         param_attr=param_attr, bias_attr=bias_attr)
    dim = input.shape[1]
    from ..ops.loss_ops import hsigmoid_code_length
    max_len = hsigmoid_code_length(num_classes)
    w = helper.create_parameter(attr=param_attr,
                                shape=[num_classes - 1, dim],
                                dtype=dtype_name(input.dtype))
    inputs = {"X": [input], "Label": [label], "W": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr,
                                    shape=[num_classes - 1, 1],
                                    dtype=dtype_name(input.dtype),
                                    is_bias=True)
        inputs["Bias"] = [b]
    n = input.shape[0]
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=[n, 1])
    pre = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=[n, max_len], stop_gradient=True)
    helper.append_op(type="hierarchical_sigmoid", inputs=inputs,
                     outputs={"Out": [out], "PreOut": [pre]},
                     attrs={"num_classes": int(num_classes)})
    return out


def beam_search(pre_ids, pre_scores, scores, beam_size, end_id, name=None):
    """One beam-search growth step (≙ reference layers/nn.py beam_search:2706
    / beam_search_op.cc). Static-beam TPU translation: all tensors carry a
    fixed beam dim K = beam_size.

    pre_ids/pre_scores: [B, K]; scores: [B, K, V] per-step log-probs.
    Initialize pre_scores to 0 for beam 0 and a large negative (e.g. -1e9)
    for beams 1..K-1 so the first step expands a single hypothesis.
    Returns (selected_ids [B, K], selected_scores [B, K], parent_idx [B, K]).
    """
    helper = LayerHelper("beam_search", name=name)
    B = pre_ids.shape[0]
    sel_ids = helper.create_tmp_variable(dtype="int64", shape=[B, beam_size])
    sel_scores = helper.create_tmp_variable(dtype=dtype_name(scores.dtype),
                                            shape=[B, beam_size])
    parent = helper.create_tmp_variable(dtype="int64", shape=[B, beam_size])
    helper.append_op(type="beam_search",
                     inputs={"PreIds": [pre_ids], "PreScores": [pre_scores],
                             "Scores": [scores]},
                     outputs={"SelectedIds": [sel_ids],
                              "SelectedScores": [sel_scores],
                              "ParentIdx": [parent]},
                     attrs={"beam_size": int(beam_size),
                            "end_id": int(end_id)})
    return sel_ids, sel_scores, parent


def beam_search_decode(ids, parents, name=None):
    """Backtrack per-step beam selections into full sequences
    (≙ reference beam_search_decode / beam_search_decode_op.cc).
    ids/parents: [B, T, K] as collected by a decode loop emitting
    beam_search outputs. Returns sequences [B, T, K]."""
    helper = LayerHelper("beam_search_decode", name=name)
    out = helper.create_tmp_variable(dtype="int64", shape=list(ids.shape))
    helper.append_op(type="gather_tree",
                     inputs={"Ids": [ids], "Parents": [parents]},
                     outputs={"Out": [out]})
    return out


gather_tree = beam_search_decode


def log_softmax(x, axis=-1, name=None):
    """≙ log_softmax op (numerically stable log(softmax(x)))."""
    helper = LayerHelper("log_softmax", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(x.dtype), shape=x.shape)
    helper.append_op(type="log_softmax", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def fused_attention(q, k, v, scale=None, causal=False, segment_ids=None,
                    kv_segment_ids=None, num_heads=None, name=None,
                    window=0):
    """Fused scaled-dot-product attention over [B, H, T, D] tensors —
    flash kernel (Pallas) on TPU, XLA composite elsewhere
    (≙ nets.py scaled_dot_product_attention, kernelized).

    num_heads: q [B, T, H*D] and k, v [B, Tk, H*D] come as the projections
    leave them, H heads side by side in the last axis, and so does the
    context: the kernels then read and write that layout where the shape
    allows (docs/fusion.md), with no head-major copy around the call.

    segment_ids ([B, T] int var) enables packed-batch masking — multiple
    sequences share one row and attend only within their own segment (the
    static-shape LoD translation, SURVEY §5); kv_segment_ids defaults to
    segment_ids (self-attention). Composes with `causal`.

    k and v may have fewer heads than q (grouped heads: query head i reads
    key/value head i // group); `window` > 0 (causal only): a query sees
    its last `window` keys, itself among them."""
    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_tmp_variable(dtype=dtype_name(q.dtype),
                                     shape=list(q.shape))
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if kv_segment_ids is not None and segment_ids is None:
        raise ValueError(
            "fused_attention: kv_segment_ids requires segment_ids (the "
            "query-side ids); pass both for cross-attention masking")
    if segment_ids is not None:
        inputs["QSeg"] = [segment_ids]
        inputs["KVSeg"] = [kv_segment_ids if kv_segment_ids is not None
                           else segment_ids]
    attrs = {"scale": scale, "causal": causal}
    if num_heads:
        attrs["num_heads"] = num_heads
    if window:
        attrs["window"] = int(window)
    helper.append_op(type="fused_attention",
                     inputs=inputs,
                     outputs={"Out": [out]},
                     attrs=attrs)
    return out


def row_conv(input, future_context_size, param_attr=None, act=None,
             name=None):
    """≙ reference layers/nn.py row_conv (lookahead convolution).
    input [B, T, D]; future_context_size = lookahead window - 1."""
    helper = LayerHelper("row_conv", name=name, param_attr=param_attr,
                         act=act)
    d = input.shape[-1]
    w = helper.create_parameter(param_attr,
                                shape=[future_context_size + 1, d],
                                dtype=dtype_name(input.dtype))
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=list(input.shape))
    helper.append_op(type="row_conv",
                     inputs={"X": [input], "Filter": [w]},
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def lstm_unit(x_t, cell_t_prev, forget_bias=0.0, name=None):
    """≙ reference layers lstm_unit: x_t [B, 4H] pre-projected gates.
    Returns (hidden, cell)."""
    helper = LayerHelper("lstm_unit", name=name)
    h = cell_t_prev.shape[-1]
    dtype = dtype_name(x_t.dtype)
    c = helper.create_tmp_variable(dtype=dtype, shape=list(cell_t_prev.shape))
    hid = helper.create_tmp_variable(dtype=dtype,
                                     shape=list(cell_t_prev.shape))
    helper.append_op(type="lstm_unit",
                     inputs={"X": [x_t], "C_prev": [cell_t_prev]},
                     outputs={"C": [c], "H": [hid]},
                     attrs={"forget_bias": float(forget_bias)})
    return hid, c


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             name=None):
    """≙ reference layers gru_unit: input [B, 3H] pre-projected; hidden
    [B, H]. Returns (new_hidden, reset_hidden_prev, gate)."""
    helper = LayerHelper("gru_unit", name=name, param_attr=param_attr)
    h = size // 3
    dtype = dtype_name(input.dtype)
    w = helper.create_parameter(param_attr, shape=[h, 3 * h], dtype=dtype)
    inputs = {"Input": [input], "HiddenPrev": [hidden], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, shape=[3 * h], dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    new_h = helper.create_tmp_variable(dtype=dtype, shape=list(hidden.shape))
    gate = helper.create_tmp_variable(dtype=dtype,
                                      shape=[hidden.shape[0], 3 * h])
    reset = helper.create_tmp_variable(dtype=dtype,
                                       shape=list(hidden.shape))
    helper.append_op(type="gru_unit", inputs=inputs,
                     outputs={"Hidden": [new_h], "Gate": [gate],
                              "ResetHiddenPrev": [reset]})
    return new_h, reset, gate


def spp(input, pyramid_height=3, pool_type="max", name=None):
    """≙ reference layers spp (spatial pyramid pooling) — [N,C,H,W] ->
    [N, C * sum(4^l for l < pyramid_height)]."""
    helper = LayerHelper("spp", name=name)
    c = input.shape[1]
    total_bins = sum(4 ** l for l in range(pyramid_height))
    out = helper.create_tmp_variable(dtype=dtype_name(input.dtype),
                                     shape=[input.shape[0], c * total_bins])
    helper.append_op(type="spp", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pyramid_height": pyramid_height,
                            "pooling_type": pool_type})
    return out
