"""NN layer functions (ref: python/paddle/fluid/layers/nn.py — fc:~190,
conv2d, pool2d, batch_norm, layer_norm, embedding, dropout, ...)."""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..framework.core import Variable, default_main_program
from ..framework.layer_helper import LayerHelper, ParamAttr
from ..framework.initializer import (ConstantInitializer, NormalInitializer,
                                     XavierInitializer, MSRAInitializer)
from . import math_ops


def data(name, shape, dtype="float32", lod_level=0, append_batch_size=True):
    """Declare an input (ref: layers/io.py data / data_feeder).  With
    ``append_batch_size`` a leading -1 batch dim is added, matching the
    reference's convention."""
    block = default_main_program().global_block()
    if append_batch_size and (not shape or shape[0] != -1):
        shape = [-1] + list(shape)
    return block.create_var(name=name, shape=shape, dtype=dtype,
                            is_data=True, stop_gradient=True)


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully connected (ref: layers/nn.py fc) — mul + elementwise_add + act,
    one XLA dot on the MXU."""
    helper = LayerHelper("fc", name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_results = []
    for inp in inputs:
        in_features = int(np.prod(inp.shape[num_flatten_dims:]))
        w = helper.create_parameter(param_attr, [in_features, size],
                                    inp.dtype)
        out_shape = tuple(inp.shape[:num_flatten_dims]) + (size,)
        tmp = helper.create_variable_for_type_inference(inp.dtype, out_shape)
        helper.append_op(type="mul", inputs={"X": [inp], "Y": [w]},
                         outputs={"Out": [tmp]},
                         attrs={"x_num_col_dims": num_flatten_dims,
                                "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(
            mul_results[0].dtype, mul_results[0].shape)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [size], pre_bias.dtype,
                                    is_bias=True)
        pre_act = helper.create_variable_for_type_inference(
            pre_bias.dtype, pre_bias.shape)
        helper.append_op(type="elementwise_add",
                         inputs={"X": [pre_bias], "Y": [b]},
                         outputs={"Out": [pre_act]},
                         attrs={"axis": num_flatten_dims})
        # axis aligns bias to the feature dim
    else:
        pre_act = pre_bias
    return helper.append_activation(pre_act, act)


def _conv_out(size, k, pad, stride, dilation=1):
    if size == -1:
        return -1
    k_eff = dilation * (k - 1) + 1
    return (size + 2 * pad - k_eff) // stride + 1


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           data_format="NCHW", name=None, use_cudnn=True):
    """ref: layers/nn.py conv2d — filters stored OIHW."""
    helper = LayerHelper("conv2d", name=name)
    groups = groups or 1
    fs = [filter_size] * 2 if isinstance(filter_size, int) else list(filter_size)
    st = [stride] * 2 if isinstance(stride, int) else list(stride)
    pd = [padding] * 2 if isinstance(padding, int) else list(padding)
    dl = [dilation] * 2 if isinstance(dilation, int) else list(dilation)
    ch_axis = 1 if data_format == "NCHW" else 3
    in_ch = input.shape[ch_axis]
    filter_shape = [num_filters, in_ch // groups] + fs
    fan_in = (in_ch // groups) * fs[0] * fs[1]
    w = helper.create_parameter(
        param_attr, filter_shape, input.dtype,
        default_initializer=NormalInitializer(0.0, np.sqrt(2.0 / fan_in)))
    if data_format == "NCHW":
        n, _, h, wd = input.shape
        out_shape = (n, num_filters, _conv_out(h, fs[0], pd[0], st[0], dl[0]),
                     _conv_out(wd, fs[1], pd[1], st[1], dl[1]))
    else:
        n, h, wd, _ = input.shape
        out_shape = (n, _conv_out(h, fs[0], pd[0], st[0], dl[0]),
                     _conv_out(wd, fs[1], pd[1], st[1], dl[1]), num_filters)
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op(type="conv2d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": st, "paddings": pd, "dilations": dl,
                            "groups": groups, "data_format": data_format})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        pre_act = helper.create_variable_for_type_inference(input.dtype,
                                                            out_shape)
        helper.append_op(type="elementwise_add",
                         inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [pre_act]}, attrs={"axis": ch_axis})
    else:
        pre_act = out
    return helper.append_activation(pre_act, act)


def pool2d(input, pool_size=2, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, ceil_mode=False, exclusive=True, name=None,
           use_cudnn=True):
    helper = LayerHelper("pool2d", name=name)
    ks = [pool_size] * 2 if isinstance(pool_size, int) else list(pool_size)
    st = [pool_stride] * 2 if isinstance(pool_stride, int) else list(pool_stride)
    pd = [pool_padding] * 2 if isinstance(pool_padding, int) else list(pool_padding)
    n, c, h, w = input.shape

    def out_sz(size, k, p, s):
        if size == -1:
            return -1
        if ceil_mode:
            return -(-(size + 2 * p - k) // s) + 1
        return (size + 2 * p - k) // s + 1

    if global_pooling:
        out_shape = (n, c, 1, 1)
    else:
        out_shape = (n, c, out_sz(h, ks[0], pd[0], st[0]),
                     out_sz(w, ks[1], pd[1], st[1]))
    out = helper.create_variable_for_type_inference(input.dtype, out_shape)
    helper.append_op(type="pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type, "ksize": ks,
                            "strides": st, "paddings": pd,
                            "global_pooling": global_pooling,
                            "ceil_mode": ceil_mode, "exclusive": exclusive})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    assert tuple(pool_size) == (1, 1) or pool_size == 1, \
        "only global adaptive pooling supported"
    return pool2d(input, pool_type=pool_type, global_pooling=True, name=name)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               moving_mean_name=None, moving_variance_name=None,
               use_global_stats=False, name=None):
    """ref: layers/nn.py batch_norm — scale/bias trainable params plus
    moving mean/variance persistables updated in the forward pass."""
    helper = LayerHelper("batch_norm", name=name)
    ch_axis = 1 if data_layout == "NCHW" else len(input.shape) - 1
    c = input.shape[ch_axis]
    scale = helper.create_parameter(
        param_attr, [c], input.dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, [c], input.dtype, is_bias=True)

    block = helper.block
    sb = helper.startup_program.global_block()
    mean_name = moving_mean_name or f"{helper.name}.mean"
    var_name = moving_variance_name or f"{helper.name}.variance"
    mean = block.create_var(name=mean_name, shape=(c,), dtype=input.dtype,
                            persistable=True)
    variance = block.create_var(name=var_name, shape=(c,), dtype=input.dtype,
                                persistable=True)
    smean = sb.create_var(name=mean_name, shape=(c,), dtype=input.dtype,
                          persistable=True)
    svar = sb.create_var(name=var_name, shape=(c,), dtype=input.dtype,
                         persistable=True)
    ConstantInitializer(0.0)(smean, sb)
    ConstantInitializer(1.0)(svar, sb)

    saved_mean = helper.create_variable_for_type_inference(input.dtype, (c,))
    saved_var = helper.create_variable_for_type_inference(input.dtype, (c,))
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout,
               "use_global_stats": use_global_stats})
    return helper.append_activation(out, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", name=name)
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            param_attr, norm_shape, input.dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, norm_shape, input.dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    mean = helper.create_variable_for_type_inference(
        input.dtype, input.shape[:begin_norm_axis])
    var = helper.create_variable_for_type_inference(
        input.dtype, input.shape[:begin_norm_axis])
    helper.append_op(type="layer_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out, act)


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """``x / sqrt(mean(x^2) + epsilon) * g`` over the last axis (no
    reference analog: the pre-norm decoders' RMSNorm; statistics in
    float32)."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        param_attr, [int(input.shape[-1])], input.dtype,
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(type="rms_norm",
                     inputs={"X": [input], "Scale": [scale]},
                     outputs={"Y": [out]}, attrs={"epsilon": epsilon})
    return out


def rotary_embedding(input, head_dim, rope_theta=10000.0,
                     rope_type="default", name=None, pos=None,
                     interleaved=False, rotary_dim=None, leading=False,
                     **yarn):
    """Rotary position embedding (rotate-half form) of every head of a
    ``[B, S, heads * head_dim]`` projection at positions ``0..S-1``, or
    at ``pos`` ``[B, S]`` when given (a served decoder's positions come
    from its feeds).  ``interleaved`` rotates ADJACENT pairs together;
    ``rotary_dim`` rotates only the last so many columns of every head,
    or under ``leading`` the first so many (a ``partial_rotary_factor``).
    ``rope_type="yarn"`` takes ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``
    and ``attention_factor`` as the published configs name them."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    inputs = {"X": [input]}
    attrs = dict(yarn, head_dim=int(head_dim), rope_theta=float(rope_theta),
                 rope_type=rope_type)
    if pos is not None:
        inputs["Pos"] = [pos]
    if interleaved:
        attrs["interleaved"] = True
    if rotary_dim and int(rotary_dim) != int(head_dim):
        attrs["rotary_dim"] = int(rotary_dim)
        if leading:
            attrs["rotary_leading"] = True
    helper.append_op(type="rotary_embedding", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32", name=None):
    """ref: layers/nn.py embedding (lookup_table_v2).  ``is_sparse`` is a
    no-op: on TPU the gather+scatter-add gradient XLA generates is already
    the sparse path (no dense one-hot matmul)."""
    helper = LayerHelper("embedding", name=name)
    w = helper.create_parameter(param_attr, list(size), dtype)
    w.is_distributed = is_distributed
    ids_shape = list(input.shape)
    if ids_shape and ids_shape[-1] == 1:
        ids_shape = ids_shape[:-1]
    out = helper.create_variable_for_type_inference(
        dtype, tuple(ids_shape) + (size[1],))
    helper.append_op(type="lookup_table_v2",
                     inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [out]},
                     attrs={"padding_idx": -1 if padding_idx is None
                            else padding_idx})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    mask = helper.create_variable_for_type_inference("uint8", x.shape,
                                                     stop_gradient=True)
    helper.append_op(type="dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "dropout_implementation": dropout_implementation})
    return out


def softmax(input, axis=-1, name=None, use_cudnn=False):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(type="softmax", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(type="log_softmax", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def one_hot(input, depth, name=None):
    helper = LayerHelper("one_hot", name=name)
    shape = list(input.shape)
    if shape and shape[-1] == 1:
        shape = shape[:-1]
    out = helper.create_variable_for_type_inference(
        "float32", tuple(shape) + (depth,))
    helper.append_op(type="one_hot", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"depth": depth})
    return out


def topk(input, k=1, name=None):
    helper = LayerHelper("top_k", name=name)
    shape = tuple(input.shape[:-1]) + (k,)
    out = helper.create_variable_for_type_inference(input.dtype, shape)
    idx = helper.create_variable_for_type_inference("int64", shape,
                                                    stop_gradient=True)
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [out], "Indices": [idx]},
                     attrs={"k": k})
    return out, idx


def argmax(x, axis=-1, keepdims=False, name=None):
    helper = LayerHelper("arg_max", name=name)
    nd = len(x.shape)
    ax = axis % nd
    if keepdims:
        shape = tuple(1 if i == ax else s for i, s in enumerate(x.shape))
    else:
        shape = tuple(s for i, s in enumerate(x.shape) if i != ax)
    out = helper.create_variable_for_type_inference("int64", shape,
                                                    stop_gradient=True)
    helper.append_op(type="arg_max", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"axis": axis, "keepdims": keepdims})
    return out


# -- extended activations / vision layer fns (ops in nn_ext_ops.py) ---------

def _simple(op_type, x, attrs=None, out_dtype=None, out_shape=None,
            in_slot="X", out_slot="Out", name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(
        out_dtype or x.dtype, out_shape if out_shape is not None else x.shape)
    helper.append_op(type=op_type, inputs={in_slot: [x]},
                     outputs={out_slot: [out]}, attrs=attrs or {})
    return out


def prelu(x, mode="all", param_attr=None, name=None):
    """ref: layers/nn.py prelu."""
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        ashape = [1]
    elif mode == "channel":
        ashape = [x.shape[1]]
    else:
        ashape = list(x.shape[1:])
    alpha = helper.create_parameter(
        param_attr, ashape, x.dtype,
        default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(type="prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return _simple("selu", x, {"scale": scale, "alpha": alpha}, name=name)


def hard_shrink(x, threshold=0.5, name=None):
    return _simple("hard_shrink", x, {"threshold": threshold}, name=name)


def softshrink(x, lambd=0.5, name=None):
    return _simple("softshrink", x, {"lambda": lambd}, name=name)


def tanh_shrink(x, name=None):
    return _simple("tanh_shrink", x, name=name)


def thresholded_relu(x, threshold=1.0, name=None):
    return _simple("thresholded_relu", x, {"threshold": threshold},
                   name=name)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _simple("stanh", x, {"scale_a": scale_a, "scale_b": scale_b},
                   name=name)


def maxout(x, groups, name=None, axis=1):
    ax = axis % len(x.shape)
    shape = tuple(s // groups if i == ax else s
                  for i, s in enumerate(x.shape))
    return _simple("maxout", x, {"groups": groups, "axis": ax},
                   out_shape=shape, name=name)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    """ref: layers/nn.py l2_normalize (norm op)."""
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    nrm = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(type="norm", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [nrm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def cos_sim(X, Y, name=None):
    helper = LayerHelper("cos_sim", name=name)
    shape = (X.shape[0], 1)
    out = helper.create_variable_for_type_inference(X.dtype, shape)
    xn = helper.create_variable_for_type_inference(X.dtype, shape)
    yn = helper.create_variable_for_type_inference(X.dtype, shape)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]})
    return out


def pixel_shuffle(x, upscale_factor, name=None):
    n, c, h, w = x.shape
    r = upscale_factor
    return _simple("pixel_shuffle", x, {"upscale_factor": r},
                   out_shape=(n, c // (r * r), h * r, w * r), name=name)


def shuffle_channel(x, group, name=None):
    return _simple("shuffle_channel", x, {"group": group}, name=name)


def space_to_depth(x, blocksize, name=None):
    n, c, h, w = x.shape
    bs = blocksize
    return _simple("space_to_depth", x, {"blocksize": bs},
                   out_shape=(n, c * bs * bs, h // bs, w // bs), name=name)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    return _simple("temporal_shift", x,
                   {"seg_num": seg_num, "shift_ratio": shift_ratio},
                   name=name)


def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None,
                   act=None):
    helper = LayerHelper("affine_channel", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    ins = {"X": [x]}
    if scale is not None:
        ins["Scale"] = [scale]
    if bias is not None:
        ins["Bias"] = [bias]
    helper.append_op(type="affine_channel", inputs=ins,
                     outputs={"Out": [out]},
                     attrs={"data_layout": data_layout})
    return helper.append_activation(out, act)


def grid_sampler(x, grid, name=None):
    n, c = x.shape[0], x.shape[1]
    ho, wo = grid.shape[1], grid.shape[2]
    helper = LayerHelper("grid_sampler", name=name)
    out = helper.create_variable_for_type_inference(x.dtype,
                                                    (n, c, ho, wo))
    helper.append_op(type="grid_sampler",
                     inputs={"X": [x], "Grid": [grid]},
                     outputs={"Output": [out]}, attrs={})
    return out


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    k = kernel_sizes if isinstance(kernel_sizes, (list, tuple)) \
        else [kernel_sizes] * 2
    s = strides if isinstance(strides, (list, tuple)) else [strides] * 2
    p = paddings if isinstance(paddings, (list, tuple)) \
        else [paddings] * 4
    d = dilations if isinstance(dilations, (list, tuple)) \
        else [dilations] * 2
    n, c, h, w = x.shape
    oh = (h + p[0] + (p[2] if len(p) > 2 else p[0])
          - (d[0] * (k[0] - 1) + 1)) // s[0] + 1
    ow = (w + p[1] + (p[3] if len(p) > 3 else p[1])
          - (d[1] * (k[1] - 1) + 1)) // s[1] + 1
    return _simple("unfold", x,
                   {"kernel_sizes": list(k), "strides": list(s),
                    "paddings": list(p), "dilations": list(d)},
                   out_shape=(n, c * k[0] * k[1], oh * ow),
                   out_slot="Y", name=name)


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    align_corners=True, align_mode=1, data_format="NCHW"):
    """ref: layers/nn.py resize_bilinear."""
    oh, ow = (out_shape if out_shape else (-1, -1))
    n, c = input.shape[0], input.shape[1]
    return _simple("bilinear_interp_v2", input,
                   {"out_h": oh, "out_w": ow, "scale": scale or 0.0,
                    "align_corners": align_corners,
                    "align_mode": align_mode},
                   out_shape=(n, c, oh, ow), name=name)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   align_corners=True, data_format="NCHW"):
    oh, ow = (out_shape if out_shape else (-1, -1))
    n, c = input.shape[0], input.shape[1]
    return _simple("nearest_interp_v2", input,
                   {"out_h": oh, "out_w": ow, "scale": scale or 0.0,
                    "align_corners": align_corners},
                   out_shape=(n, c, oh, ow), name=name)


def resize_trilinear(input, out_shape=None, scale=None, name=None,
                     align_corners=True, align_mode=1,
                     data_format="NCDHW"):
    od, oh, ow = (out_shape if out_shape else (-1, -1, -1))
    n, c = input.shape[0], input.shape[1]
    if (od is None or od < 0) and scale:
        od = int(input.shape[2] * scale)
        oh = int(input.shape[3] * scale)
        ow = int(input.shape[4] * scale)
    return _simple("trilinear_interp", input,
                   {"out_d": od, "out_h": oh, "out_w": ow,
                    "scale": scale or 0.0,
                    "align_corners": align_corners},
                   out_shape=(n, c, od, oh, ow), name=name)
