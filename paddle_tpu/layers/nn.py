"""Op-builder layer API (parity: python/paddle/fluid/layers/nn.py, ~200 fns).

Each function appends ops to the current block and returns output Variables.
"""

from .. import flags as _flags
from ..framework import Variable, convert_np_dtype_to_dtype_
from ..layer_helper import LayerHelper
from ..ops.common import dtype_enum

__all__ = [
    "fc",
    "embedding",
    "flash_attention",
    "ring_attention",
    "dropout",
    "softmax",
    "log_softmax",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "square_error_cost",
    "accuracy",
    "auc",
    "topk",
    "matmul",
    "mul",
    "conv2d",
    "conv2d_bn_relu",
    "conv2d_transpose",
    "pool2d",
    "adaptive_pool2d",
    "batch_norm",
    "layer_norm",
    "fused_dropout_add_ln",
    "group_norm",
    "instance_norm",
    "relu",
    "label_smooth",
    "mean",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "reduce_all",
    "reduce_any",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "elementwise_mod",
    "elementwise_floordiv",
    "clip",
    "clip_by_norm",
    "l2_normalize",
    "scale",
    "sums",
    "transpose",
    "reshape",
    "squeeze",
    "unsqueeze",
    "flatten",
    "concat",
    "split",
    "stack",
    "unstack",
    "expand",
    "slice",
    "strided_slice",
    "gather",
    "gather_nd",
    "scatter",
    "one_hot",
    "pad",
    "pad2d",
    "lod_reset",
    "shape",
    "argmax",
    "argmin",
    "argsort",
    "where",
    "gelu",
    "leaky_relu",
    "prelu",
    "elu",
    "relu6",
    "pow",
    "hard_sigmoid",
    "swish",
    "image_resize",
    "resize_bilinear",
    "resize_nearest",
    "cos_sim",
    "smooth_l1",
    "huber_loss",
    "kldiv_loss",
    "log_loss",
    "mse_loss",
    "npair_loss",
    "uniform_random_batch_size_like",
    "gaussian_random",
    "sampled_softmax_with_cross_entropy",
    "unfold",
    "pixel_shuffle",
]


def _single_out_layer(op_type, helper_name=None, x_slot="X", out_slot="Out"):
    """Build a layers.* function for a single-in single-out op."""

    def layer(x, *args, name=None, **attrs):
        helper = LayerHelper(helper_name or op_type, name=name)
        out = helper.create_variable_for_type_inference(dtype=x.dtype)
        helper.append_op(
            type=op_type,
            inputs={x_slot: [x]},
            outputs={out_slot: [out]},
            attrs=attrs,
        )
        return out

    layer.__name__ = helper_name or op_type
    return layer


# -- dense / matmul ----------------------------------------------------------


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """Fully-connected layer (reference layers/nn.py:fc): mul per input +
    sum + bias + act."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype()
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = param_attr if isinstance(param_attr, (list, tuple)) else [
        param_attr
    ] * len(inputs)
    mul_results = []
    for inp, pattr in zip(inputs, param_attrs):
        input_shape = inp.shape
        in_features = 1
        for d in input_shape[num_flatten_dims:]:
            in_features *= int(d)
        w = helper.create_parameter(
            attr=pattr, shape=[in_features, size], dtype=dtype
        )
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [inp], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="sum",
            inputs={"X": mul_results},
            outputs={"Out": [pre_bias]},
        )
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32", name=None):
    helper = LayerHelper("embedding", name=name)
    w = helper.create_parameter(attr=param_attr, shape=list(size), dtype=dtype)
    if is_distributed and not w.sharding:
        # TPU-native equivalent of the reference's pserver-sharded table
        # (distributed_lookup_table_op + parameter_prefetch): row-shard the
        # table over the mesh "model" axis; under pjit XLA inserts the
        # gather collectives over ICI.  On meshes without a "model" axis the
        # annotation is dropped (table replicated).
        w.sharding = ("model", None)
    out = helper.create_variable_for_type_inference(dtype)
    pad = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx
    )
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={"is_sparse": is_sparse, "is_distributed": is_distributed,
               "padding_idx": pad},
    )
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="matmul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
               "alpha": float(alpha)},
    )
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="mul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


# -- losses ------------------------------------------------------------------


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax = helper.create_variable_for_type_inference(dtype=logits.dtype)
    loss = helper.create_variable_for_type_inference(dtype=logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax], "Loss": [loss]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index,
               "numeric_stable_mode": numeric_stable_mode, "axis": axis},
    )
    if return_softmax:
        return loss, softmax
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      normalize=False, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": [x], "Label": [label]},
        outputs={"Out": [out]},
        attrs={"ignore_index": ignore_index, "normalize": normalize},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    diff = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="elementwise_sub",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [diff]},
    )
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="square", inputs={"X": [diff]}, outputs={"Out": [out]}
    )
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1")
    diff = helper.create_variable_for_type_inference(dtype=x.dtype)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(
        type="smooth_l1_loss",
        inputs=inputs,
        outputs={"Diff": [diff], "Out": [out]},
        attrs={"sigma": sigma if sigma is not None else 1.0},
    )
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    residual = helper.create_variable_for_type_inference(dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="huber_loss",
        inputs={"X": [input], "Y": [label]},
        outputs={"Residual": [residual], "Out": [out]},
        attrs={"delta": delta},
    )
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="kldiv_loss",
        inputs={"X": [x], "Target": [target]},
        outputs={"Loss": [out]},
        attrs={"reduction": reduction},
    )
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="log_loss",
        inputs={"Predicted": [input], "Labels": [label]},
        outputs={"Loss": [out]},
        attrs={"epsilon": epsilon},
    )
    return out


def mse_loss(input, label):
    return reduce_mean(square_error_cost(input, label))


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """N-pair loss (reference layers/loss.py) composed from primitives."""
    from . import tensor as ltensor

    l2loss = reduce_mean(reduce_sum(elementwise_mul(anchor, anchor), dim=[1]))
    l2loss = elementwise_add(
        l2loss,
        reduce_mean(reduce_sum(elementwise_mul(positive, positive), dim=[1]))
    )
    l2loss = scale(l2loss, scale=l2_reg * 0.25)
    similarity = matmul(anchor, positive, transpose_y=True)
    softlab = softmax(similarity)
    xent = cross_entropy(softlab, labels, soft_label=True)
    return elementwise_add(reduce_mean(xent), l2loss)


def sampled_softmax_with_cross_entropy(logits, label, num_samples, **kwargs):
    # TPU: dense softmax is MXU-fast; sampling is rarely a win — full softmax
    return softmax_with_cross_entropy(logits, label)


# -- metrics -----------------------------------------------------------------


def accuracy(input, label, k=1, correct=None, total=None):
    helper = LayerHelper("accuracy")
    topk_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    topk_indices = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [topk_out], "Indices": [topk_indices]},
        attrs={"k": k},
    )
    acc_out = helper.create_variable_for_type_inference(dtype="float32")
    if correct is None:
        correct = helper.create_variable_for_type_inference(dtype="int32")
    if total is None:
        total = helper.create_variable_for_type_inference(dtype="int32")
    helper.append_op(
        type="accuracy",
        inputs={"Out": [topk_out], "Indices": [topk_indices],
                "Label": [label]},
        outputs={"Accuracy": [acc_out], "Correct": [correct],
                 "Total": [total]},
    )
    return acc_out


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1,
        slide_steps=1):
    helper = LayerHelper("auc")
    stat_pos = helper.create_or_get_global_variable(
        name=helper.name + "_stat_pos", shape=[num_thresholds + 1],
        dtype="int64", persistable=True
    )
    stat_neg = helper.create_or_get_global_variable(
        name=helper.name + "_stat_neg", shape=[num_thresholds + 1],
        dtype="int64", persistable=True
    )
    from ..initializer import Constant

    for v in (stat_pos, stat_neg):
        Constant(0)(v)
    auc_out = helper.create_variable_for_type_inference(dtype="float64")
    helper.append_op(
        type="auc",
        inputs={"Predict": [input], "Label": [label],
                "StatPos": [stat_pos], "StatNeg": [stat_neg]},
        outputs={"AUC": [auc_out], "StatPosOut": [stat_pos],
                 "StatNegOut": [stat_neg]},
        attrs={"curve": curve, "num_thresholds": num_thresholds,
               "slide_steps": slide_steps},
    )
    return auc_out, [auc_out], [stat_pos, stat_neg]


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(dtype=input.dtype)
    indices = helper.create_variable_for_type_inference(dtype="int64")
    inputs = {"X": [input]}
    attrs = {}
    if isinstance(k, Variable):
        inputs["K"] = [k]
    else:
        attrs = {"k": k}
    helper.append_op(
        type="top_k",
        inputs=inputs,
        outputs={"Out": [values], "Indices": [indices]},
        attrs=attrs,
    )
    return values, indices


# -- elementwise/reduce/scale family ----------------------------------------


def _elementwise_layer(op_type):
    def layer(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, act=act, name=name)
        out = helper.create_variable_for_type_inference(dtype=x.dtype)
        helper.append_op(
            type=op_type,
            inputs={"X": [x], "Y": [y]},
            outputs={"Out": [out]},
            attrs={"axis": axis},
        )
        return helper.append_activation(out)

    layer.__name__ = op_type
    return layer


elementwise_add = _elementwise_layer("elementwise_add")
elementwise_sub = _elementwise_layer("elementwise_sub")
elementwise_mul = _elementwise_layer("elementwise_mul")
elementwise_div = _elementwise_layer("elementwise_div")
elementwise_max = _elementwise_layer("elementwise_max")
elementwise_min = _elementwise_layer("elementwise_min")
elementwise_pow = _elementwise_layer("elementwise_pow")
elementwise_mod = _elementwise_layer("elementwise_mod")
elementwise_floordiv = _elementwise_layer("elementwise_floordiv")


def _reduce_layer(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_variable_for_type_inference(dtype=input.dtype)
        if dim is None:
            dim_attr = [0]
            reduce_all = True
        else:
            dim_attr = dim if isinstance(dim, (list, tuple)) else [dim]
            reduce_all = False
        helper.append_op(
            type=op_type,
            inputs={"X": [input]},
            outputs={"Out": [out]},
            attrs={"dim": list(dim_attr), "keep_dim": keep_dim,
                   "reduce_all": reduce_all},
        )
        return out

    layer.__name__ = op_type
    return layer


reduce_sum = _reduce_layer("reduce_sum")
reduce_mean = _reduce_layer("reduce_mean")
reduce_max = _reduce_layer("reduce_max")
reduce_min = _reduce_layer("reduce_min")
reduce_prod = _reduce_layer("reduce_prod")
reduce_all = _reduce_layer("reduce_all")
reduce_any = _reduce_layer("reduce_any")


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", act=act, name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="scale",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"scale": float(scale), "bias": float(bias),
               "bias_after_scale": bias_after_scale},
    )
    return helper.append_activation(out)


def sums(input, out=None):
    helper = LayerHelper("sum")
    if out is None:
        out = helper.create_variable_for_type_inference(
            dtype=helper.input_dtype("input") if False else input[0].dtype
        )
    helper.append_op(
        type="sum", inputs={"X": input}, outputs={"Out": [out]}
    )
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="clip",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"min": float(min), "max": float(max)},
    )
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="clip_by_norm",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"max_norm": float(max_norm)},
    )
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    """x / sqrt(sum(x^2, axis) + eps) via the norm op (norm_op.cc) — the
    fluid elementwise broadcast rules can't express a same-rank keepdim
    divisor at axis=-1, so this must NOT be composed from elementwise_div."""
    helper = LayerHelper("l2_normalize", name=name)
    norm_out = helper.create_variable_for_type_inference(x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="norm", inputs={"X": [x]},
        outputs={"Norm": [norm_out], "Out": [out]},
        attrs={"axis": int(axis), "epsilon": float(epsilon)},
    )
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    xn = l2_normalize(X, axis=-1)
    yn = l2_normalize(Y, axis=-1)
    prod = elementwise_mul(xn, yn)
    return reduce_sum(prod, dim=[-1], keep_dim=True)


# -- activations -------------------------------------------------------------

relu = _single_out_layer("relu")
softmax_ = None


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="softmax",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="log_softmax",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def gelu(x, approximate=False):
    helper = LayerHelper("gelu")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="gelu", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"approximate": approximate},
    )
    return out


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="leaky_relu", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"alpha": alpha},
    )
    return out


def elu(x, alpha=1.0, name=None):
    helper = LayerHelper("elu", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="elu", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"alpha": alpha},
    )
    return out


def relu6(x, threshold=6.0, name=None):
    helper = LayerHelper("relu6", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="relu6", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"threshold": threshold},
    )
    return out


def pow(x, factor=1.0, name=None):
    helper = LayerHelper("pow", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="pow", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"factor": factor},
    )
    return out


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    helper = LayerHelper("hard_sigmoid", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="hard_sigmoid", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"slope": slope, "offset": offset},
    )
    return out


def swish(x, beta=1.0, name=None):
    helper = LayerHelper("swish", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="swish", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"beta": beta},
    )
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    alpha_shape = [1]
    if mode == "channel":
        alpha_shape = [1, x.shape[1], 1, 1]
    elif mode == "element":
        alpha_shape = list(x.shape[1:])
    from ..initializer import Constant

    alpha = helper.create_parameter(
        attr=param_attr, shape=alpha_shape, dtype=x.dtype,
        default_initializer=Constant(0.25)
    )
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="prelu",
        inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]},
        attrs={"mode": mode},
    )
    return out


# -- dropout / label smoothing ----------------------------------------------


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(
        dtype="uint8", stop_gradient=True
    )
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "fix_seed": seed is not None,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(
        type="label_smooth",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


# -- conv / pool / norm (ops registered in ops/nn.py) ------------------------


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d", bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    num_channels = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    padding = [padding, padding] if isinstance(padding, int) else list(padding)
    dilation = [dilation, dilation] if isinstance(dilation, int) else list(dilation)
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)

    import math as _math

    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    from ..initializer import Normal

    std = _math.sqrt(2.0 / fan_in)
    w = helper.create_parameter(
        attr=param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=Normal(0.0, std),
    )
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "data_format": data_format,
        },
    )
    if bias_attr is False:
        pre_act = pre_bias
    else:
        b = helper.create_parameter(
            attr=helper.kwargs.get("bias_attr"), shape=[num_filters],
            dtype=dtype, is_bias=True
        )
        if b is None:
            pre_act = pre_bias
        else:
            pre_act = helper.create_variable_for_type_inference(dtype)
            helper.append_op(
                type="elementwise_add",
                inputs={"X": [pre_bias], "Y": [b]},
                outputs={"Out": [pre_act]},
                attrs={"axis": 1 if data_format == "NCHW" else 3},
            )
    return helper.append_activation(pre_act)


def conv2d_bn_relu(input, num_filters, filter_size, stride=1, padding=0,
                   dilation=1, groups=1, param_attr=None, bn_param_attr=None,
                   bn_bias_attr=None, act="relu", momentum=0.9, epsilon=1e-5,
                   is_test=False, moving_mean_name=None,
                   moving_variance_name=None, name=None, data_format="NCHW"):
    """Fused conv + batch-norm (+ relu) trunk block: ONE `conv2d_bn_relu`
    op instead of the conv2d / batch_norm / relu triple; it lowers to
    exactly that composition.  The conv carries
    no bias: the BN affine absorbs it (the reference's conv_bn_fuse_pass
    precondition).  Only act in (None, "relu") is expressible."""
    if act not in (None, "relu"):
        raise ValueError("conv2d_bn_relu supports act None or 'relu', got %r"
                         % (act,))
    helper = LayerHelper("conv2d_bn_relu", name=name)
    dtype = input.dtype
    num_channels = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    padding = [padding, padding] if isinstance(padding, int) else list(padding)
    dilation = [dilation, dilation] if isinstance(dilation, int) \
        else list(dilation)
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)

    import math as _math

    from ..initializer import Constant, Normal

    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    w = helper.create_parameter(
        attr=param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=Normal(0.0, _math.sqrt(2.0 / fan_in)),
    )
    scale_p = helper.create_parameter(
        attr=bn_param_attr, shape=[num_filters], dtype=dtype,
        default_initializer=Constant(1.0)
    )
    bias_p = helper.create_parameter(
        attr=bn_bias_attr, shape=[num_filters], dtype=dtype, is_bias=True,
        default_initializer=Constant(0.0)
    )
    mean = helper.create_or_get_global_variable(
        name=moving_mean_name or helper.name + ".mean",
        shape=[num_filters], dtype=dtype, persistable=True
    )
    mean.stop_gradient = True
    variance = helper.create_or_get_global_variable(
        name=moving_variance_name or helper.name + ".var",
        shape=[num_filters], dtype=dtype, persistable=True
    )
    variance.stop_gradient = True
    if not getattr(mean, "_bn_initialized", False):
        Constant(0.0)(mean)
        Constant(1.0)(variance)
        mean._bn_initialized = True
        variance._bn_initialized = True

    saved_mean = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True
    )
    saved_var = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True
    )
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d_bn_relu",
        inputs={
            "Input": [input],
            "Filter": [w],
            "Scale": [scale_p],
            "Bias": [bias_p],
            "Mean": [mean],
            "Variance": [variance],
        },
        outputs={
            "Output": [out],
            "MeanOut": [mean],
            "VarianceOut": [variance],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_var],
        },
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "data_format": data_format,
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "with_relu": act == "relu",
        },
    )
    return out


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d_transpose", bias_attr=bias_attr, act=act,
                         name=name)
    dtype = input.dtype
    num_channels = input.shape[1]
    groups = groups or 1
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    padding = [padding, padding] if isinstance(padding, int) else list(padding)
    dilation = [dilation, dilation] if isinstance(dilation, int) else list(dilation)
    if filter_size is None:
        raise ValueError("filter_size required")
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [num_channels, num_filters // groups] + list(filter_size)
    w = helper.create_parameter(attr=param_attr, shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "output_size": list(output_size) if output_size else [],
            "data_format": data_format,
        },
    )
    if bias_attr is False:
        pre_act = pre_bias
    else:
        b = helper.create_parameter(
            attr=helper.kwargs.get("bias_attr"), shape=[num_filters],
            dtype=dtype, is_bias=True
        )
        if b is None:
            pre_act = pre_bias
        else:
            pre_act = helper.create_variable_for_type_inference(dtype)
            helper.append_op(
                type="elementwise_add",
                inputs={"X": [pre_bias], "Y": [b]},
                outputs={"Out": [pre_act]},
                attrs={"axis": 1},
            )
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True, data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    pool_size = [pool_size, pool_size] if isinstance(pool_size, int) else list(pool_size)
    pool_stride = [pool_stride, pool_stride] if isinstance(pool_stride, int) else list(pool_stride)
    pool_padding = [pool_padding, pool_padding] if isinstance(pool_padding, int) else list(pool_padding)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": pool_size,
            "strides": pool_stride,
            "paddings": pool_padding,
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
            "data_format": data_format,
        },
    )
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False,
                    name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    pool_size = [pool_size, pool_size] if isinstance(pool_size, int) else list(pool_size)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": pool_size,
            "adaptive": True,
            "strides": [1, 1],
            "paddings": [0, 0],
        },
    )
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    helper = LayerHelper("batch_norm", act=act, name=name)
    dtype = input.dtype
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    from ..initializer import Constant

    scale_p = helper.create_parameter(
        attr=param_attr, shape=[c], dtype=dtype,
        default_initializer=Constant(1.0)
    )
    bias_p = helper.create_parameter(
        attr=bias_attr, shape=[c], dtype=dtype, is_bias=True,
        default_initializer=Constant(0.0)
    )
    mean = helper.create_or_get_global_variable(
        name=moving_mean_name or helper.name + ".mean",
        shape=[c], dtype=dtype, persistable=True
    )
    mean.stop_gradient = True
    variance = helper.create_or_get_global_variable(
        name=moving_variance_name or helper.name + ".var",
        shape=[c], dtype=dtype, persistable=True
    )
    variance.stop_gradient = True
    if not getattr(mean, "_bn_initialized", False):
        Constant(0.0)(mean)
        Constant(1.0)(variance)
        mean._bn_initialized = True
        variance._bn_initialized = True

    saved_mean = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True
    )
    saved_var = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True
    )
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="batch_norm",
        inputs={
            "X": [input],
            "Scale": [scale_p],
            "Bias": [bias_p],
            "Mean": [mean],
            "Variance": [variance],
        },
        outputs={
            "Y": [out],
            "MeanOut": [mean],
            "VarianceOut": [variance],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_var],
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
            "use_global_stats": use_global_stats,
            # ghost-batch statistics (FLAGS_bn_stat_subsample, default 1 =
            # exact): estimate batch stats from every k-th sample — cuts the
            # dominant stat-pass HBM traffic on bandwidth-bound devices
            "stat_subsample": int(_flags.flag("bn_stat_subsample") or 1),
        },
    )
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", act=act, name=name)
    dtype = input.dtype
    norm_size = 1
    for d in input.shape[begin_norm_axis:]:
        norm_size *= int(d)
    inputs = {"X": [input]}
    from ..initializer import Constant

    scale_p = bias_p = None
    if scale:
        scale_p = helper.create_parameter(
            attr=param_attr, shape=[norm_size], dtype=dtype,
            default_initializer=Constant(1.0)
        )
        inputs["Scale"] = [scale_p]
    if shift:
        bias_p = helper.create_parameter(
            attr=bias_attr, shape=[norm_size], dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [bias_p]
    out = helper.create_variable_for_type_inference(dtype)
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [var_out]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(out)


def fused_dropout_add_ln(x, y, dropout_prob=0.0, is_test=False,
                         begin_norm_axis=1, epsilon=1e-5, param_attr=None,
                         bias_attr=None, name=None, seed=None):
    """LayerNorm(x + dropout(y)) as ONE op — the transformer-encoder
    epilogue, lowered to a fused single-pass Pallas kernel on TPU (see
    ops/nn.py fused_dropout_add_ln; reference analog:
    paddle/fluid/operators/fused/fused_fc_elementwise_layernorm_op.cu,
    extended with in-kernel dropout for training).  Exactly equivalent to

        layer_norm(elementwise_add(x, dropout(y, dropout_prob,
                   dropout_implementation="upscale_in_train")), ...)

    with dropout's keep probability realized at 2^-32 granularity."""
    helper = LayerHelper("fused_dropout_add_ln", name=name)
    dtype = x.dtype
    norm_size = 1
    for d in x.shape[begin_norm_axis:]:
        norm_size *= int(d)
    from ..initializer import Constant

    scale_p = helper.create_parameter(
        attr=param_attr, shape=[norm_size], dtype=dtype,
        default_initializer=Constant(1.0))
    bias_p = helper.create_parameter(
        attr=bias_attr, shape=[norm_size], dtype=dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(dtype)
    r_out = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    mean_out = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    seed_out = helper.create_variable_for_type_inference(
        "int32", stop_gradient=True)
    helper.append_op(
        type="fused_dropout_add_ln",
        inputs={"X": [x], "Y": [y], "Scale": [scale_p], "Bias": [bias_p]},
        outputs={"Out": [out], "R": [r_out], "Mean": [mean_out],
                 "Variance": [var_out], "Seed": [seed_out]},
        attrs={"dropout_prob": float(dropout_prob), "is_test": is_test,
               "epsilon": epsilon, "begin_norm_axis": begin_norm_axis,
               "fix_seed": seed is not None, "seed": seed or 0},
    )
    return out


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", act=act, name=name)
    dtype = input.dtype
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    from ..initializer import Constant

    inputs = {"X": [input]}
    if param_attr is not False:
        scale_p = helper.create_parameter(
            attr=param_attr, shape=[c], dtype=dtype,
            default_initializer=Constant(1.0)
        )
        inputs["Scale"] = [scale_p]
    if bias_attr is not False:
        bias_p = helper.create_parameter(
            attr=bias_attr, shape=[c], dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [bias_p]
    out = helper.create_variable_for_type_inference(dtype)
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="group_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [var_out]},
        attrs={"epsilon": epsilon, "groups": groups,
               "data_layout": data_layout},
    )
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper("instance_norm", name=name)
    dtype = input.dtype
    c = input.shape[1]
    from ..initializer import Constant

    scale_p = helper.create_parameter(
        attr=param_attr, shape=[c], dtype=dtype,
        default_initializer=Constant(1.0)
    )
    bias_p = helper.create_parameter(
        attr=bias_attr, shape=[c], dtype=dtype, is_bias=True
    )
    out = helper.create_variable_for_type_inference(dtype)
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="instance_norm",
        inputs={"X": [input], "Scale": [scale_p], "Bias": [bias_p]},
        outputs={"Y": [out], "SavedMean": [mean_out],
                 "SavedVariance": [var_out]},
        attrs={"epsilon": epsilon},
    )
    return out


# -- shape manipulation ------------------------------------------------------


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    xshape = helper.create_variable_for_type_inference(
        dtype=x.dtype, stop_gradient=True
    )
    helper.append_op(
        type="transpose2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axis": list(perm)},
    )
    return out


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", act=act, name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    xshape = helper.create_variable_for_type_inference(
        dtype=x.dtype, stop_gradient=True
    )
    helper.append_op(
        type="reshape2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"shape": list(shape)},
    )
    return helper.append_activation(out)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    xshape = helper.create_variable_for_type_inference(
        dtype=input.dtype, stop_gradient=True
    )
    helper.append_op(
        type="squeeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axes": list(axes)},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    xshape = helper.create_variable_for_type_inference(
        dtype=input.dtype, stop_gradient=True
    )
    helper.append_op(
        type="unsqueeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axes": list(axes)},
    )
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    xshape = helper.create_variable_for_type_inference(
        dtype=x.dtype, stop_gradient=True
    )
    helper.append_op(
        type="flatten2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axis": axis},
    )
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat", name=name)
    out = helper.create_variable_for_type_inference(dtype=input[0].dtype)
    helper.append_op(
        type="concat",
        inputs={"X": input},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        attrs = {"num": num, "axis": dim, "sections": []}
        n_out = num
    else:
        attrs = {"num": 0, "axis": dim, "sections": list(num_or_sections)}
        n_out = len(num_or_sections)
    outs = [
        helper.create_variable_for_type_inference(dtype=input.dtype)
        for _ in range(n_out)
    ]
    helper.append_op(
        type="split", inputs={"X": [input]}, outputs={"Out": outs}, attrs=attrs
    )
    return outs


def stack(x, axis=0):
    helper = LayerHelper("stack")
    x = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(dtype=x[0].dtype)
    helper.append_op(
        type="stack", inputs={"X": x}, outputs={"Y": [out]},
        attrs={"axis": axis},
    )
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    if num is None:
        num = x.shape[axis]
    outs = [
        helper.create_variable_for_type_inference(dtype=x.dtype)
        for _ in range(num)
    ]
    helper.append_op(
        type="unstack", inputs={"X": [x]}, outputs={"Y": outs},
        attrs={"axis": axis, "num": num},
    )
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="expand", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"expand_times": list(expand_times)},
    )
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="slice",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"axes": list(axes), "starts": list(starts),
               "ends": list(ends)},
    )
    return out


def strided_slice(input, axes, starts, ends, strides):
    helper = LayerHelper("strided_slice")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="strided_slice",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"axes": list(axes), "starts": list(starts),
               "ends": list(ends), "strides": list(strides)},
    )
    return out


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="gather",
        inputs={"X": [input], "Index": [index]},
        outputs={"Out": [out]},
        attrs={"overwrite": overwrite},
    )
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="gather_nd",
        inputs={"X": [input], "Index": [index]},
        outputs={"Out": [out]},
    )
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]},
        attrs={"overwrite": overwrite},
    )
    return out


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op(
        type="one_hot",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"depth": depth, "allow_out_of_range": allow_out_of_range},
    )
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="pad",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "pad_value": float(pad_value)},
    )
    return out


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="pad2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "mode": mode,
               "pad_value": float(pad_value), "data_format": data_format},
    )
    return out


def lod_reset(x, y=None, target_lod=None):
    # LoD is metadata-only on TPU (masks/padding carry sequence info)
    return x


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference(dtype="int32")
    helper.append_op(
        type="shape", inputs={"Input": [input]}, outputs={"Out": [out]}
    )
    return out


def argmax(x, axis=0):
    helper = LayerHelper("arg_max")
    out = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="arg_max", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def argmin(x, axis=0):
    helper = LayerHelper("arg_min")
    out = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="arg_min", inputs={"X": [x]}, outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def argsort(input, axis=-1, descending=False, name=None):
    helper = LayerHelper("argsort", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    ids = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="argsort",
        inputs={"X": [input]},
        outputs={"Out": [out], "Indices": [ids]},
        attrs={"axis": axis, "descending": descending},
    )
    return out, ids


def where(condition):
    helper = LayerHelper("where_index")
    out = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="where_index",
        inputs={"Condition": [condition]},
        outputs={"Out": [out]},
    )
    return out


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="uniform_random_batch_size_like",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "input_dim_idx": input_dim_idx,
               "output_dim_idx": output_dim_idx, "min": min, "max": max,
               "seed": seed, "dtype": dtype_enum(dtype)},
    )
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="gaussian_random",
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "mean": mean, "std": std, "seed": seed,
               "dtype": dtype_enum(dtype)},
    )
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True,
                 align_mode=1, data_format="NCHW"):
    helper = LayerHelper("image_resize", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    if out_shape is None:
        h = int(input.shape[2] * scale)
        w = int(input.shape[3] * scale)
        out_shape = [h, w]
    helper.append_op(
        type="bilinear_interp" if resample.upper() == "BILINEAR" else "nearest_interp",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"out_h": int(out_shape[0]), "out_w": int(out_shape[1]),
               "align_corners": align_corners, "align_mode": align_mode,
               "data_layout": data_format},
    )
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "BILINEAR",
                        actual_shape, align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True):
    return image_resize(input, out_shape, scale, name, "NEAREST",
                        actual_shape, align_corners)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    helper = LayerHelper("unfold", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    ks = [kernel_sizes] * 2 if isinstance(kernel_sizes, int) else list(kernel_sizes)
    st = [strides] * 2 if isinstance(strides, int) else list(strides)
    pd = [paddings] * 4 if isinstance(paddings, int) else list(paddings)
    dl = [dilations] * 2 if isinstance(dilations, int) else list(dilations)
    helper.append_op(
        type="unfold",
        inputs={"X": [x]},
        outputs={"Y": [out]},
        attrs={"kernel_sizes": ks, "strides": st, "paddings": pd,
               "dilations": dl},
    )
    return out


def pixel_shuffle(x, upscale_factor):
    helper = LayerHelper("pixel_shuffle")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="pixel_shuffle",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"upscale_factor": upscale_factor},
    )
    return out


def flash_attention(q, k, v, bias_qk=None, causal=False, scale=0.0,
                    layout="BHSD", dropout_prob=0.0, is_test=False,
                    name=None):
    """Fused blockwise multi-head attention (Pallas TPU kernel; see
    paddle_tpu/pallas_kernels/flash_attention.py).  Analog of the
    reference's fused attention (multihead_matmul_op.cu) but
    differentiable/trainable.

    layout: "BHSD" (default) or "BSHD" ([B, S, H, D] — transpose-free
    emission: split heads with a reshape, no transpose, no relayout
    copies).  dropout_prob > 0 applies attention-prob dropout inside the
    op when not is_test.  bias_qk is an additive mask (no gradient flows
    to it).  scale=0.0 means "use 1/sqrt(head_dim)"; pass scale=1.0 if q
    is already pre-scaled."""
    helper = LayerHelper("flash_attention", name=name)
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    # Mask must be DECLARED: with dropout active the custom grad replays
    # with this saved mask (an undeclared slot would silently drop it and
    # the backward would run mask-free — decoupled from the sampled loss).
    # On the small-seq fused-kernel path the mask is never materialized:
    # Seed (2 words) + Lse replay it instead (see ops/nn.py).
    mask = helper.create_variable_for_type_inference(dtype="uint8")
    mask.stop_gradient = True
    seed_out = helper.create_variable_for_type_inference(dtype="int32")
    seed_out.stop_gradient = True
    lse = helper.create_variable_for_type_inference(dtype="float32")
    lse.stop_gradient = True
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias_qk is not None:
        inputs["BiasQK"] = [bias_qk]
    helper.append_op(
        type="flash_attention",
        inputs=inputs,
        outputs={"Out": [out], "Mask": [mask], "Seed": [seed_out],
                 "Lse": [lse]},
        attrs={"causal": causal, "scale": float(scale),
               "layout": layout, "dropout_prob": float(dropout_prob),
               "is_test": is_test},
    )
    return out


def ring_attention(q, k, v, causal=False, scale=0.0, axis="sp", name=None):
    """Context-parallel ring attention over mesh axis `axis` (sequence dim
    sharded); dense flash attention when unsharded.  See
    paddle_tpu/parallel/ring_attention.py."""
    helper = LayerHelper("ring_attention", name=name)
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    helper.append_op(
        type="ring_attention",
        inputs={"Q": [q], "K": [k], "V": [v]},
        outputs={"Out": [out]},
        attrs={"causal": causal, "scale": float(scale), "axis": axis},
    )
    return out
