"""Loss layers (ref: python/paddle/fluid/layers/loss.py)."""

from __future__ import annotations

from ..framework.layer_helper import LayerHelper


def cross_entropy(input, label, soft_label=False, ignore_index=-100,
                  name=None):
    helper = LayerHelper("cross_entropy", name=name)
    shape = tuple(input.shape[:-1]) + (1,)
    out = helper.create_variable_for_type_inference(input.dtype, shape)
    helper.append_op(type="cross_entropy",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, return_softmax=False,
                               axis=-1, name=None):
    helper = LayerHelper("softmax_with_cross_entropy", name=name)
    nd = len(logits.shape)
    ax = axis % nd
    loss_shape = tuple(1 if i == ax else s for i, s in enumerate(logits.shape))
    softmax = helper.create_variable_for_type_inference(logits.dtype,
                                                        logits.shape)
    loss = helper.create_variable_for_type_inference(logits.dtype, loss_shape)
    helper.append_op(type="softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Softmax": [softmax], "Loss": [loss]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index, "axis": axis})
    if return_softmax:
        return loss, softmax
    return loss


def lm_head_loss(input, label, vocab_size, param_attr=None, name=None):
    """Untied language-model head and per-token cross-entropy in one op
    (ops/decoder_lm_ops.py): ``input`` [B, S, d] times a ``[d,
    vocab_size]`` matrix, ``label`` [B, S]; returns the float32 loss of
    every position [B, S].  The logits are formed a block of rows at a
    time and never all at once in float32."""
    helper = LayerHelper("lm_head_loss", name=name)
    w = helper.create_parameter(param_attr,
                                [int(input.shape[-1]), vocab_size],
                                input.dtype)
    loss = helper.create_variable_for_type_inference("float32", label.shape)
    helper.append_op(type="lm_head_loss",
                     inputs={"X": [input], "W": [w], "Label": [label]},
                     outputs={"Loss": [loss]})
    return loss


def square_error_cost(input, label, name=None):
    helper = LayerHelper("square_error_cost", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op(type="square_error_cost",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Out": [out]})
    return out


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      normalize=False, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(type="sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x], "Label": [label]},
                     outputs={"Out": [out]},
                     attrs={"ignore_index": ignore_index,
                            "normalize": normalize})
    return out


def smooth_l1(x, y, sigma=1.0, name=None):
    helper = LayerHelper("smooth_l1_loss", name=name)
    out = helper.create_variable_for_type_inference(
        x.dtype, (x.shape[0], 1))
    diff = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(type="smooth_l1_loss",
                     inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out], "Diff": [diff]},
                     attrs={"sigma": sigma})
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    shape = () if reduction in ("mean", "sum", "batchmean") else x.shape
    out = helper.create_variable_for_type_inference(x.dtype, shape)
    helper.append_op(type="kldiv_loss",
                     inputs={"X": [x], "Target": [target]},
                     outputs={"Loss": [out]}, attrs={"reduction": reduction})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(label.dtype, label.shape)
    ins = {"X": [label]}
    if prior_dist is not None:
        ins["PriorDist"] = [prior_dist]
    helper.append_op(type="label_smooth", inputs=ins,
                     outputs={"Out": [out]}, attrs={"epsilon": epsilon})
    return out
