"""NN ops: conv2d, pooling, batch/layer/group/instance norm, dropout,
interpolation.

Parity: conv_op.cc (+conv_cudnn_op.cu), pool_op.cc, batch_norm_op.cc,
layer_norm_op.cc, group_norm_op.cc, instance_norm_op.cc, dropout_op.cc,
label_smooth_op.cc, interpolate_op.cc, unfold_op.cc, pixel_shuffle_op.cc
(paddle/fluid/operators/).  Convs lower to lax.conv_general_dilated (MXU);
norms are jnp compositions XLA fuses; dropout uses functional PRNG with an
explicit Mask output so the grad replays exactly.
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import GradOpDesc, register_op
from ..framework import _grad_var_name
from .common import (attr_dtype, bernoulli_bytes, dtype_enum,
                     realized_keep_prob)


# -- conv --------------------------------------------------------------------


def _conv_dims(data_format):
    # Filters are always OIHW (the layer API creates them that way, so
    # checkpoints are layout-independent); only the activation layout varies.
    if data_format in ("NCHW", "AnyLayout"):
        return ("NCHW", "OIHW", "NCHW")
    return ("NHWC", "OIHW", "NHWC")


@register_op(
    "conv2d",
    inputs=("Input", "Filter"),
    outputs=("Output",),
    attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
           "groups": 1, "data_format": "NCHW", "padding_algorithm": "EXPLICIT",
           "use_cudnn": True, "use_mkldnn": False, "fuse_relu_before_depthwise_conv": False,
           "workspace_size_MB": 512, "exhaustive_search": False},
)
def conv2d(ctx, x, w, strides=(1, 1), paddings=(0, 0), dilations=(1, 1),
           groups=1, data_format="NCHW", padding_algorithm="EXPLICIT", **_):
    if padding_algorithm == "SAME":
        pad = "SAME"
    elif padding_algorithm == "VALID":
        pad = "VALID"
    else:
        p = list(paddings)
        if len(p) == 2:
            pad = [(p[0], p[0]), (p[1], p[1])]
        else:  # [top, bottom, left, right]
            pad = [(p[0], p[1]), (p[2], p[3])]
    dn = lax.conv_dimension_numbers(x.shape, w.shape, _conv_dims(data_format))
    # AMP: bf16 operands (MXU accumulates f32 internally), cast up after —
    # keeping operand/cotangent dtypes uniform so the conv transpose rule
    # stays well-typed under vjp
    amp = ctx is not None and ctx.amp_bf16() and x.dtype in (
        jnp.float32, jnp.bfloat16)
    xc, wc = (x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)) if amp else (x, w)
    out = lax.conv_general_dilated(
        xc, wc,
        window_strides=tuple(strides),
        padding=pad,
        rhs_dilation=tuple(dilations),
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    # bf16-carry policy: under AMP the activation stays bf16 (weights remain
    # f32 master copies); without AMP preserve the input dtype
    return out if amp else out.astype(x.dtype)


@register_op(
    "depthwise_conv2d",
    inputs=("Input", "Filter"),
    outputs=("Output",),
    attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
           "groups": 1, "data_format": "NCHW", "padding_algorithm": "EXPLICIT",
           "use_cudnn": False},
)
def depthwise_conv2d(ctx, x, w, strides=(1, 1), paddings=(0, 0),
                     dilations=(1, 1), groups=1, data_format="NCHW",
                     padding_algorithm="EXPLICIT", **_):
    return conv2d(ctx, x, w, strides, paddings, dilations, groups,
                  data_format, padding_algorithm)


def _transpose_conv_filter(w, groups, spatial_axes):
    """Fluid transpose-conv filter [C_in, F/g, *k] -> grouped forward-conv
    filter [F, C_in/g, *k] (flipped spatially).  groups=1 reduces to the
    classic flip+swapaxes; groups>1 needs the block regrouping or
    feature_group_count rejects the shape."""
    wf = jnp.flip(w, axis=spatial_axes)
    if groups == 1:
        return jnp.swapaxes(wf, 0, 1)
    c_in, f_per_g = wf.shape[0], wf.shape[1]
    k = wf.shape[2:]
    wg = wf.reshape((groups, c_in // groups, f_per_g) + k)
    wg = jnp.swapaxes(wg, 1, 2)  # [g, F/g, C_in/g, *k]
    return wg.reshape((groups * f_per_g, c_in // groups) + k)


def _transpose_conv_extra_pad(in_sizes, k_sizes, strides, pads, dilations,
                              output_size):
    """Per-dim extra high-side padding so the lhs-dilated conv emits
    exactly `output_size` (the stride>1 inverse is ambiguous; the
    reference uses output_size/output_padding to disambiguate —
    conv_transpose_op.cc)."""
    extras = []
    for i, tgt in enumerate(output_size):
        default = ((in_sizes[i] - 1) * strides[i] - pads[i][0] - pads[i][1]
                   + dilations[i] * (k_sizes[i] - 1) + 1)
        extra = int(tgt) - default
        if extra < 0 or extra >= strides[i]:
            raise ValueError(
                "output_size[%d]=%s unreachable (valid range [%d, %d))"
                % (i, tgt, default, default + strides[i]))
        extras.append(extra)
    return extras


@register_op(
    "conv2d_transpose",
    inputs=("Input", "Filter"),
    outputs=("Output",),
    attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
           "groups": 1, "data_format": "NCHW", "output_size": [],
           "padding_algorithm": "EXPLICIT", "use_cudnn": True},
)
def conv2d_transpose(ctx, x, w, strides=(1, 1), paddings=(0, 0),
                     dilations=(1, 1), groups=1, data_format="NCHW",
                     output_size=(), padding_algorithm="EXPLICIT", **_):
    # filter layout IOHW (fluid conv2d_transpose: [in_c, out_c/g, kh, kw])
    p = list(paddings)
    pads = [(p[0], p[0]), (p[1], p[1])] if len(p) == 2 else [
        (p[0], p[1]), (p[2], p[3])
    ]
    kh, kw = w.shape[2], w.shape[3]
    sh, sw = strides
    dil = list(dilations)
    extra = [0, 0]
    if output_size:
        extra = _transpose_conv_extra_pad(
            (x.shape[2], x.shape[3]), (kh, kw), (sh, sw), pads, dil,
            output_size)
    # transpose conv = lhs-dilated conv with flipped kernel
    wt = _transpose_conv_filter(w, groups, (2, 3))
    dn = lax.conv_dimension_numbers(x.shape, wt.shape, ("NCHW", "OIHW", "NCHW"))
    out = lax.conv_general_dilated(
        x, wt,
        window_strides=(1, 1),
        padding=[(kh - 1 - pads[0][0], kh - 1 - pads[0][1] + extra[0]),
                 (kw - 1 - pads[1][0], kw - 1 - pads[1][1] + extra[1])],
        lhs_dilation=(sh, sw),
        rhs_dilation=tuple(dil),
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    return out


# -- pooling -----------------------------------------------------------------


@register_op(
    "pool2d",
    inputs=("X",),
    outputs=("Out",),
    attrs={"pooling_type": "max", "ksize": [1, 1], "strides": [1, 1],
           "paddings": [0, 0], "global_pooling": False, "ceil_mode": False,
           "exclusive": True, "adaptive": False, "data_format": "NCHW",
           "padding_algorithm": "EXPLICIT", "use_cudnn": True},
)
def pool2d(ctx, x, pooling_type="max", ksize=(1, 1), strides=(1, 1),
           paddings=(0, 0), global_pooling=False, ceil_mode=False,
           exclusive=True, adaptive=False, data_format="NCHW", **_):
    nchw = data_format in ("NCHW", "AnyLayout")
    h_ax, w_ax = (2, 3) if nchw else (1, 2)
    if global_pooling:
        if pooling_type == "max":
            return jnp.max(x, axis=(h_ax, w_ax), keepdims=True)
        return jnp.mean(x, axis=(h_ax, w_ax), keepdims=True)
    if adaptive:
        oh, ow = int(ksize[0]), int(ksize[1])
        H, W = x.shape[h_ax], x.shape[w_ax]
        if H % oh == 0 and W % ow == 0:
            fh, fw = H // oh, W // ow
            if nchw:
                r = x.reshape(x.shape[0], x.shape[1], oh, fh, ow, fw)
                return (jnp.max(r, axis=(3, 5)) if pooling_type == "max"
                        else jnp.mean(r, axis=(3, 5)))
            r = x.reshape(x.shape[0], oh, fh, ow, fw, x.shape[3])
            return (jnp.max(r, axis=(2, 4)) if pooling_type == "max"
                    else jnp.mean(r, axis=(2, 4)))
        # arbitrary output sizes (reference pooling.h AdaptStartIndex/
        # AdaptEndIndex: start = floor(i*I/O), end = ceil((i+1)*I/O)).
        # Bin boundaries are Python ints at trace time, so this stays
        # static-shaped: one slice-reduce per output cell, fused by XLA.
        red = jnp.max if pooling_type == "max" else jnp.mean
        rows = []
        for i in range(oh):
            hs, he = (i * H) // oh, -((-(i + 1) * H) // oh)
            cols = []
            for j in range(ow):
                ws, we = (j * W) // ow, -((-(j + 1) * W) // ow)
                if nchw:
                    patch = x[:, :, hs:he, ws:we]
                    cols.append(red(patch, axis=(2, 3)))
                else:
                    patch = x[:, hs:he, ws:we, :]
                    cols.append(red(patch, axis=(1, 2)))
            rows.append(jnp.stack(cols, axis=-1 if nchw else 1))
        if nchw:
            return jnp.stack(rows, axis=2)  # [N, C, oh, ow]
        return jnp.stack(rows, axis=1)      # [N, oh, ow, C]

    kh, kw = int(ksize[0]), int(ksize[1])
    sh, sw = int(strides[0]), int(strides[1])
    ph, pw = int(paddings[0]), int(paddings[1])
    if ceil_mode:
        H, W = x.shape[h_ax], x.shape[w_ax]
        extra_h = -(H + 2 * ph - kh) % sh
        extra_w = -(W + 2 * pw - kw) % sw
        pad_h = (ph, ph + extra_h)
        pad_w = (pw, pw + extra_w)
    else:
        pad_h, pad_w = (ph, ph), (pw, pw)
    if nchw:
        window = (1, 1, kh, kw)
        strides_ = (1, 1, sh, sw)
        pads = ((0, 0), (0, 0), pad_h, pad_w)
    else:
        window = (1, kh, kw, 1)
        strides_ = (1, sh, sw, 1)
        pads = ((0, 0), pad_h, pad_w, (0, 0))
    # NB: init values must be Python scalars for JAX to select the
    # differentiable reduce_window_{max,sum} primitives
    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else int(
            jnp.iinfo(x.dtype).min)
        return lax.reduce_window(x, init, lax.max, window, strides_, pads)
    s = lax.reduce_window(x, 0.0, lax.add, window, strides_, pads)
    if exclusive and (pad_h != (0, 0) or pad_w != (0, 0)):
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides_, pads)
        return s / cnt
    return s / (kh * kw)


# -- normalization -----------------------------------------------------------


def _bn_impl(x, scale, bias, mean, variance, axes, cshape, momentum,
             epsilon, use_stored_stats, axis_name=None, stat_subsample=1):
    """Shared batch_norm / sync_batch_norm body: f32 statistics (optionally
    pmean'd over the data-parallel axis — the reference's in-kernel
    ncclAllReduce, sync_batch_norm_op.cu), bf16-carry output.

    stat_subsample>1 estimates the batch statistics from every k-th sample
    (ghost batch norm).  On bandwidth-starved devices the statistics passes
    re-read every conv output at the reduction-bandwidth cap, so this
    directly cuts the dominant HBM traffic; statistically it is the
    well-studied small-ghost-batch estimator (neutral-to-helpful at large
    batch).  Default 1 = exact reference semantics."""
    if use_stored_stats:
        m, v = mean, variance
        new_mean, new_var = mean, variance
    else:
        if stat_subsample > 1 and isinstance(x.shape[0], int):
            # contiguous prefix (batches are shuffled): a strided slice on
            # the sublane-packed batch axis costs more than it saves.  The
            # int guard keeps symbolic-batch shape inference on the exact
            # path (stat shapes do not depend on the subsample).  Slice the
            # carry-dtype tensor BEFORE the f32 convert so the full-size
            # f32 copy is never materialized.
            xs = x[: max(x.shape[0] // stat_subsample, 1)].astype(jnp.float32)
        else:
            xs = x.astype(jnp.float32)
        m = jnp.mean(xs, axis=axes)
        msq = jnp.mean(jnp.square(xs), axis=axes)
        if axis_name is not None:
            # cross-replica moments: mean of means is exact for equal shards
            m = lax.pmean(m, axis_name)
            msq = lax.pmean(msq, axis_name)
        v = msq - jnp.square(m)
        new_mean = momentum * mean + (1 - momentum) * m
        new_var = momentum * variance + (1 - momentum) * v
    inv = 1.0 / jnp.sqrt(v + epsilon)
    # fold the normalization into one per-channel affine computed in f32 and
    # applied in the carry dtype: the big-tensor pass is a single bf16
    # multiply-add instead of sub/mul/mul/add in f32 (the elementwise BN
    # passes are pure HBM-bandwidth + VPU cost, ~20% of a ResNet-50 step)
    a = (inv * scale).reshape(cshape)
    b = (bias - m * inv * scale).reshape(cshape)
    y = x * a.astype(x.dtype) + b.astype(x.dtype)
    return (y, new_mean, new_var, m, inv, None)


def _bn_grad_maker(op, no_grad_set):
    """batch_norm grad: differentiate through Y only (running stats are
    stop-gradient); uses SavedMean/SavedVariance like batch_norm_grad op."""
    inputs = {
        "X": list(op.input("X")),
        "Scale": list(op.input("Scale")),
        "Bias": list(op.input("Bias")),
        "SavedMean": list(op.output("SavedMean")),
        "SavedVariance": list(op.output("SavedVariance")),
        "GRAD@Y": [_grad_var_name(op.output("Y")[0])],
    }
    outputs = {}
    for slot in ("X", "Scale", "Bias"):
        n = op.input(slot)[0]
        if n not in no_grad_set:
            outputs["X@" + slot] = [_grad_var_name(n)]
    if not outputs:
        return []
    return [GradOpDesc("batch_norm_grad", inputs, outputs, dict(op.attrs))]


@register_op(
    "batch_norm",
    inputs=("X", "Scale", "Bias", "Mean", "Variance"),
    outputs=("Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance",
             "ReserveSpace"),
    attrs={"momentum": 0.9, "epsilon": 1e-5, "is_test": False,
           "data_layout": "NCHW", "use_global_stats": False,
           "trainable_statistics": False, "fuse_with_relu": False,
           "stat_subsample": 1},
    grad_maker=_bn_grad_maker,
)
def batch_norm(ctx, x, scale, bias, mean, variance, momentum=0.9,
               epsilon=1e-5, is_test=False, data_layout="NCHW",
               use_global_stats=False, stat_subsample=1, **_):
    nchw = data_layout in ("NCHW", "AnyLayout")
    axes = (0, 2, 3) if (nchw and x.ndim == 4) else tuple(
        i for i in range(x.ndim) if i != (1 if nchw else x.ndim - 1)
    )
    cshape = [1] * x.ndim
    c_ax = 1 if nchw else x.ndim - 1
    cshape[c_ax] = x.shape[c_ax]

    return _bn_impl(x, scale, bias, mean, variance, axes, cshape, momentum,
                    epsilon, is_test or use_global_stats, axis_name=None,
                    stat_subsample=int(stat_subsample))


@register_op(
    "batch_norm_grad",
    inputs=("X", "Scale", "Bias", "SavedMean", "SavedVariance", "GRAD@Y"),
    outputs=("X@X", "X@Scale", "X@Bias"),
    attrs={"momentum": 0.9, "epsilon": 1e-5, "is_test": False,
           "data_layout": "NCHW", "use_global_stats": False},
    grad_maker=None,
    optional_inputs=("GRAD@Y",),
)
def batch_norm_grad(ctx, x, scale, bias, saved_mean, saved_inv_std, dy,
                    momentum=0.9, epsilon=1e-5, is_test=False,
                    data_layout="NCHW", use_global_stats=False, **_):
    nchw = data_layout in ("NCHW", "AnyLayout")
    c_ax = 1 if nchw else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_ax)
    cshape = [1] * x.ndim
    cshape[c_ax] = x.shape[c_ax]
    if dy is None:
        dy = jnp.zeros_like(x)
    n = 1
    for i in axes:
        n *= x.shape[i]
    f32 = jnp.float32
    mu = saved_mean.reshape(cshape).astype(f32)
    inv = saved_inv_std.reshape(cshape).astype(f32)
    # reductions promote to f32 inside the fused reduce (reads stay bf16)
    dyf = dy.astype(f32)
    xhatf = (x.astype(f32) - mu) * inv
    dscale = jnp.sum(dyf * xhatf, axis=axes)
    dbias = jnp.sum(dyf, axis=axes)
    s = scale.astype(f32)
    if is_test or use_global_stats:
        a1 = (s.reshape(cshape) * inv)
        dx = dy * a1.astype(x.dtype)
    else:
        # dx = s*inv/n * (n*dy - dbias - xhat*dscale) rearranged into one
        # per-channel affine a1*dy + a2*x + a3 applied in the carry dtype
        # (same bandwidth-motivated folding as the forward)
        sinv = s.reshape(cshape) * inv
        a1 = sinv
        a2 = -sinv * inv * dscale.reshape(cshape) / n
        a3 = (-sinv * dbias.reshape(cshape)
              + sinv * inv * dscale.reshape(cshape) * mu) / n
        dx = (dy * a1.astype(x.dtype) + x * a2.astype(x.dtype)
              + a3.astype(x.dtype))
    return dx, dscale.astype(scale.dtype), dbias.astype(scale.dtype)


@register_op(
    "conv2d_bn_relu",
    inputs=("Input", "Filter", "Scale", "Bias", "Mean", "Variance"),
    outputs=("Output", "MeanOut", "VarianceOut", "SavedMean",
             "SavedVariance"),
    attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
           "groups": 1, "data_format": "NCHW", "momentum": 0.9,
           "epsilon": 1e-5, "is_test": False, "with_relu": True},
    no_grad_inputs=("Mean", "Variance"),
)
def conv2d_bn_relu(ctx, x, w, scale, bias, mean, variance, strides=(1, 1),
                   paddings=(0, 0), dilations=(1, 1), groups=1,
                   data_format="NCHW", momentum=0.9, epsilon=1e-5,
                   is_test=False, with_relu=True, **_):
    """Fused conv + batch-norm (+ relu) trunk block — the reference's
    conv_bn_fuse_pass / conv2d_fusion analogue.  Lowers to the exact
    conv2d + _bn_impl (+ relu) composition (any stride, padding, dilation
    and groups; AMP handled by the conv2d lowering), which XLA fuses.
    SavedVariance holds the INVERSE std, mirroring batch_norm.  Gradients
    come from the auto grad maker (jax.vjp over this lowering)."""
    conv = conv2d(ctx, x, w, strides, paddings, dilations, groups,
                  data_format)
    nchw = data_format in ("NCHW", "AnyLayout")
    c_ax = 1 if nchw else conv.ndim - 1
    axes = tuple(i for i in range(conv.ndim) if i != c_ax)
    cshape = [1] * conv.ndim
    cshape[c_ax] = conv.shape[c_ax]
    y, new_mean, new_var, m, inv, _r = _bn_impl(
        conv, scale, bias, mean, variance, axes, cshape, momentum, epsilon,
        is_test)
    if with_relu:
        y = jnp.maximum(y, jnp.zeros((), y.dtype))
    return y, new_mean, new_var, m, inv


@register_op(
    "layer_norm",
    inputs=("X", "Scale", "Bias"),
    outputs=("Y", "Mean", "Variance"),
    attrs={"epsilon": 1e-5, "begin_norm_axis": 1},
    optional_inputs=("Scale", "Bias"),
)
def layer_norm(ctx, x, scale, bias, epsilon=1e-5, begin_norm_axis=1):
    lead = x.shape[:begin_norm_axis]
    tail = x.shape[begin_norm_axis:]
    axes = tuple(range(begin_norm_axis, x.ndim))
    # bf16 inputs (the AMP carry dtype) get f32 internal statistics — an
    # 8-bit-mantissa mean/var costs accuracy (same policy as the Pallas
    # kernel and _bn_impl); the carry dtype is restored on the outputs
    xf = x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    m = jnp.mean(xf, axis=axes, keepdims=True)
    v = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - m) / jnp.sqrt(v + epsilon)
    if scale is not None:
        y = y * scale.reshape(tail)
    if bias is not None:
        y = y + bias.reshape(tail)
    return (y.astype(x.dtype), m.astype(x.dtype).reshape(lead),
            v.astype(x.dtype).reshape(lead))


@register_op(
    "group_norm",
    inputs=("X", "Scale", "Bias"),
    outputs=("Y", "Mean", "Variance"),
    attrs={"epsilon": 1e-5, "groups": 1, "data_layout": "NCHW"},
    optional_inputs=("Scale", "Bias"),
)
def group_norm(ctx, x, scale, bias, epsilon=1e-5, groups=1,
               data_layout="NCHW"):
    N = x.shape[0]
    if data_layout == "NCHW":
        C = x.shape[1]
        r = x.reshape(N, groups, C // groups, *x.shape[2:])
        axes = tuple(range(2, r.ndim))
        m = jnp.mean(r, axis=axes, keepdims=True)
        v = jnp.var(r, axis=axes, keepdims=True)
        y = ((r - m) / jnp.sqrt(v + epsilon)).reshape(x.shape)
        cshape = (1, C) + (1,) * (x.ndim - 2)
    else:
        C = x.shape[-1]
        r = x.reshape(N, *x.shape[1:-1], groups, C // groups)
        axes = tuple(range(1, r.ndim - 2)) + (r.ndim - 1,)
        m = jnp.mean(r, axis=axes, keepdims=True)
        v = jnp.var(r, axis=axes, keepdims=True)
        y = ((r - m) / jnp.sqrt(v + epsilon)).reshape(x.shape)
        cshape = (1,) * (x.ndim - 1) + (C,)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    return y, m.reshape(N, groups), v.reshape(N, groups)


@register_op(
    "instance_norm",
    inputs=("X", "Scale", "Bias"),
    outputs=("Y", "SavedMean", "SavedVariance"),
    attrs={"epsilon": 1e-5},
    optional_inputs=("Scale", "Bias"),
)
def instance_norm(ctx, x, scale, bias, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    y = (x - m) / jnp.sqrt(v + epsilon)
    cshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    return y, jnp.squeeze(m, axes), 1.0 / jnp.sqrt(jnp.squeeze(v, axes) + epsilon)


@register_op(
    "norm",
    inputs=("X",),
    outputs=("Norm", "Out"),
    attrs={"axis": 1, "epsilon": 1e-10},
)
def norm(ctx, x, axis=1, epsilon=1e-10):
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + epsilon)
    return norm, x / norm


# -- dropout -----------------------------------------------------------------


def _dropout_grad_maker(op, no_grad_set):
    x = op.input("X")[0]
    if x in no_grad_set:
        return []
    return [
        GradOpDesc(
            "dropout_grad",
            {"Mask": list(op.output("Mask")),
             "GRAD@Out": [_grad_var_name(op.output("Out")[0])]},
            {"X@X": [_grad_var_name(x)]},
            dict(op.attrs),
        )
    ]


@register_op(
    "dropout",
    inputs=("X",),
    outputs=("Out", "Mask"),
    attrs={"dropout_prob": 0.5, "is_test": False, "fix_seed": False,
           "seed": 0, "dropout_implementation": "downgrade_in_infer"},
    grad_maker=_dropout_grad_maker,
    n_rng=1,
)
def dropout(ctx, x, dropout_prob=0.5, is_test=False, fix_seed=False, seed=0,
            dropout_implementation="downgrade_in_infer", **_):
    if is_test:
        if dropout_implementation == "upscale_in_train":
            return x, jnp.ones_like(x, dtype=jnp.uint8)
        # downgrade inference scales by the NOMINAL (1-p) — exact reference
        # parity for imported models (no sampling happens at inference, so
        # nothing forces the quantized grid here).  Known asymmetry: the
        # TRAIN side masks with the 256-quantized realized keep prob, so
        # E[train out] and this infer out differ by up to 2^-9 relative —
        # inference parity is deliberately preferred over expectation
        # consistency (ADVICE round 5).
        return (x * (1.0 - dropout_prob),
                jnp.ones_like(x, dtype=jnp.uint8))
    # training scale factors use the REALIZED keep probability of the
    # quantized byte draw (round(keep*256)/256) so E[out] = x exactly
    q = realized_keep_prob(1.0 - dropout_prob)
    key = jax.random.key(seed) if fix_seed else ctx.rng()
    keep = bernoulli_bytes(key, 1.0 - dropout_prob, x.shape)
    mask = keep.astype(jnp.uint8)
    if dropout_implementation == "upscale_in_train":
        out = jnp.where(keep, x / q, 0.0)
    else:
        out = jnp.where(keep, x, 0.0)
    return out, mask


@register_op(
    "dropout_grad",
    inputs=("Mask", "GRAD@Out"),
    outputs=("X@X",),
    attrs={"dropout_prob": 0.5, "is_test": False, "fix_seed": False,
           "seed": 0, "dropout_implementation": "downgrade_in_infer"},
    grad_maker=None,
)
def dropout_grad(ctx, mask, dy, dropout_prob=0.5, is_test=False,
                 dropout_implementation="downgrade_in_infer", **_):
    m = mask.astype(dy.dtype)
    if dropout_implementation == "upscale_in_train":
        # same realized-keep divisor as the forward (see dropout)
        return dy * m / realized_keep_prob(1.0 - dropout_prob)
    return dy * m


@register_op(
    "label_smooth",
    inputs=("X", "PriorDist"),
    outputs=("Out",),
    attrs={"epsilon": 0.1},
    optional_inputs=("PriorDist",),
)
def label_smooth(ctx, x, prior, epsilon=0.1):
    k = x.shape[-1]
    if prior is not None:
        return (1.0 - epsilon) * x + epsilon * prior.reshape((1,) * (x.ndim - 1) + (k,))
    return (1.0 - epsilon) * x + epsilon / k


# -- interpolation / layout --------------------------------------------------


def _interp(x, out_h, out_w, method, data_layout):
    nchw = data_layout in ("NCHW", "AnyLayout")
    if nchw:
        shape = (x.shape[0], x.shape[1], out_h, out_w)
    else:
        shape = (x.shape[0], out_h, out_w, x.shape[3])
    return jax.image.resize(x, shape, method=method)


@register_op(
    "bilinear_interp",
    inputs=("X", "OutSize", "SizeTensor", "Scale"),
    outputs=("Out",),
    attrs={"out_h": -1, "out_w": -1, "align_corners": True, "align_mode": 1,
           "data_layout": "NCHW", "interp_method": "bilinear", "scale": 0.0},
    optional_inputs=("OutSize", "SizeTensor", "Scale"),
    duplicable_inputs=("SizeTensor",),
)
def bilinear_interp(ctx, x, out_size, size_tensor, scale_t, out_h=-1,
                    out_w=-1, align_corners=True, align_mode=1,
                    data_layout="NCHW", scale=0.0, **_):
    if scale and out_h < 0:
        out_h = int(x.shape[2] * scale)
        out_w = int(x.shape[3] * scale)
    return _interp(x, out_h, out_w, "bilinear", data_layout)


@register_op(
    "nearest_interp",
    inputs=("X", "OutSize", "SizeTensor", "Scale"),
    outputs=("Out",),
    attrs={"out_h": -1, "out_w": -1, "align_corners": True,
           "data_layout": "NCHW", "interp_method": "nearest", "scale": 0.0},
    optional_inputs=("OutSize", "SizeTensor", "Scale"),
    duplicable_inputs=("SizeTensor",),
)
def nearest_interp(ctx, x, out_size, size_tensor, scale_t, out_h=-1,
                   out_w=-1, align_corners=True, data_layout="NCHW",
                   scale=0.0, **_):
    if scale and out_h < 0:
        out_h = int(x.shape[2] * scale)
        out_w = int(x.shape[3] * scale)
    return _interp(x, out_h, out_w, "nearest", data_layout)


@register_op(
    "unfold",
    inputs=("X",),
    outputs=("Y",),
    attrs={"kernel_sizes": [1, 1], "strides": [1, 1],
           "paddings": [0, 0, 0, 0], "dilations": [1, 1]},
)
def unfold(ctx, x, kernel_sizes=(1, 1), strides=(1, 1),
           paddings=(0, 0, 0, 0), dilations=(1, 1)):
    patches = lax.conv_general_dilated_patches(
        x,
        filter_shape=tuple(kernel_sizes),
        window_strides=tuple(strides),
        padding=[(paddings[0], paddings[2]), (paddings[1], paddings[3])],
        rhs_dilation=tuple(dilations),
        dimension_numbers=lax.conv_dimension_numbers(
            x.shape, (1, 1) + tuple(kernel_sizes), ("NCHW", "OIHW", "NCHW")
        ),
    )
    n, ckk, oh, ow = patches.shape
    return patches.reshape(n, ckk, oh * ow)


@register_op(
    "pixel_shuffle",
    inputs=("X",),
    outputs=("Out",),
    attrs={"upscale_factor": 1},
)
def pixel_shuffle(ctx, x, upscale_factor=1):
    n, c, h, w = x.shape
    r = upscale_factor
    out = x.reshape(n, c // (r * r), r, r, h, w)
    out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
    return out.reshape(n, c // (r * r), h * r, w * r)


@register_op(
    "uniform_random_batch_size_like",
    inputs=("Input",),
    outputs=("Out",),
    attrs={"shape": [], "input_dim_idx": 0, "output_dim_idx": 0,
           "min": -1.0, "max": 1.0, "seed": 0, "dtype": 5},
    grad_maker=None,
    n_rng=1,
)
def uniform_random_batch_size_like(ctx, input, shape=(), input_dim_idx=0,
                                   output_dim_idx=0, min=-1.0, max=1.0,
                                   seed=0, dtype=5):
    out_shape = list(int(s) for s in shape)
    out_shape[output_dim_idx] = input.shape[input_dim_idx]
    key = jax.random.key(seed) if seed else ctx.rng()
    return jax.random.uniform(key, tuple(out_shape), dtype=attr_dtype(dtype),
                              minval=min, maxval=max)


def _attention_composed(q, k, v, bias, causal, sm_scale, keep_mask=None,
                        dropout_prob=0.0, bshd=True):
    """Composed attention with optional attention-prob dropout
    (upscale_in_train) applied via an explicit KEEP MASK (so forward and
    backward share the exact same mask — cf. the dropout op's saved
    Mask).  Einsums run in the carry dtype (bf16 under AMP; the MXU
    accumulates f32 internally); softmax normalizes in f32 like
    _ref_attention.  bshd=True takes [B, S, H, D] operands transpose-free
    (dot_general batches the non-adjacent dims); bshd=False [B, H, S, D].
    """
    eq_s = "bqhd,bkhd->bhqk" if bshd else "bhqd,bhkd->bhqk"
    eq_o = "bhqk,bkhd->bqhd" if bshd else "bhqk,bhkd->bhqd"
    s = jnp.einsum(eq_s, q, k) * jnp.asarray(sm_scale, q.dtype)
    if bias is not None:
        s = s + bias.astype(s.dtype)
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        kj = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        s = jnp.where(kj <= qi, s, jnp.asarray(-1e30, s.dtype))
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    if keep_mask is not None:
        kq = realized_keep_prob(1.0 - dropout_prob)
        p = jnp.where(keep_mask.astype(bool),
                      p / jnp.asarray(kq, p.dtype),
                      jnp.asarray(0.0, p.dtype))
    return jnp.einsum(eq_o, p, v)


def _fa_check_layout(layout):
    if layout not in ("BHSD", "BSHD"):
        raise ValueError(
            "flash_attention layout must be 'BHSD' or 'BSHD', got %r"
            % (layout,))


def _fa_uses_dropout(attrs):
    return (float(attrs.get("dropout_prob", 0.0) or 0.0) > 0.0
            and not attrs.get("is_test", False))


def _flash_attention_grad_maker(op, no_grad_set):
    inputs = {
        "Q": list(op.input("Q")),
        "K": list(op.input("K")),
        "V": list(op.input("V")),
        "Mask": list(op.output("Mask")),
        "Out": list(op.output("Out")),
        "Seed": list(op.output("Seed")),
        "Lse": list(op.output("Lse")),
        "GRAD@Out": [_grad_var_name(op.output("Out")[0])],
    }
    if op.input("BiasQK"):
        inputs["BiasQK"] = list(op.input("BiasQK"))
    outputs = {}
    for slot in ("Q", "K", "V"):
        n = op.input(slot)[0]
        if n not in no_grad_set:
            outputs["X@" + slot] = [_grad_var_name(n)]
    if not outputs:
        return []
    return [GradOpDesc("flash_attention_grad", inputs, outputs,
                       dict(op.attrs))]


@register_op(
    "flash_attention",
    inputs=("Q", "K", "V", "BiasQK"),
    outputs=("Out", "Mask", "Seed", "Lse"),
    attrs={"causal": False, "scale": 0.0, "layout": "BHSD",
           "dropout_prob": 0.0, "is_test": False},
    optional_inputs=("BiasQK",),
    no_grad_inputs=("BiasQK",),
    grad_maker=_flash_attention_grad_maker,
    n_rng=1,  # drawn only when dropout is active — see rng_when below
)
def flash_attention_op(ctx, q, k, v, bias_qk=None, causal=False, scale=0.0,
                       layout="BHSD", dropout_prob=0.0, is_test=False):
    """Fused blockwise attention (Pallas TPU kernel with jnp fallback).

    TPU-native replacement for the reference's fused inference attention
    (paddle/fluid/operators/fused/multihead_matmul_op.cu) — but trainable:
    the kernel carries a FlashAttention backward (pallas_kernels/
    flash_attention.py).  q/k/v: [B, H, S, D] (layout="BHSD", default) or
    [B, S, H, D] (layout="BSHD" — transpose-free: the head split is a
    plain reshape and dot_general batches over non-adjacent dims; on the
    bench chip XLA re-inserts equivalent layout copies, so this is a
    capability, not a measured win — BASELINE.md); bias_qk:
    [B, 1|H, Sq, Sk].

    dropout_prob > 0 (training mode) applies attention-prob dropout
    (upscale_in_train) inside the op via a sampled keep mask that is
    SAVED as the Mask output, so the custom backward replays with the
    exact forward mask (the dropout-op contract; an rng re-draw in the
    backward would decouple gradients from the sampled loss).  The Pallas
    kernel engages for dropout-free BHSD at the measured seq cutoff.

    BiasQK is an additive MASK, not a trainable tensor: the backward
    returns no bias cotangent, so it is registered no-grad on every
    backend.  scale=0.0 (the default) means "use 1/sqrt(head_dim)"; pass
    scale=1.0 explicitly if the scaling is already folded into q.
    """
    from ..pallas_kernels import flash_attention as _fa

    _fa_check_layout(layout)
    head_dim = q.shape[-1]
    sm_scale = scale if scale else head_dim ** -0.5
    bshd = layout == "BSHD"
    attrs = {"dropout_prob": dropout_prob, "is_test": is_test,
             "causal": causal, "layout": layout}
    seed_ph = jnp.zeros((2,), jnp.int32)
    lse_ph = jnp.zeros((1, 1, 1, 1), jnp.float32)
    if _fa_uses_dropout(attrs):
        B = q.shape[0]
        H = q.shape[2] if bshd else q.shape[1]
        Sq = q.shape[1] if bshd else q.shape[2]
        Sk = k.shape[1] if bshd else k.shape[2]
        keep = bernoulli_bytes(ctx.rng(), 1.0 - dropout_prob,
                               (B, H, Sq, Sk))
        out = _attention_composed(q, k, v, bias_qk, causal, sm_scale,
                                  keep, dropout_prob, bshd)
        return out, keep.astype(jnp.uint8), seed_ph, lse_ph
    mask_placeholder = jnp.zeros((1,), jnp.uint8)
    if bshd:
        return (_attention_composed(q, k, v, bias_qk, causal, sm_scale,
                                    bshd=True), mask_placeholder, seed_ph,
                lse_ph)
    return (_fa(q, k, v, bias=bias_qk, causal=causal, sm_scale=sm_scale),
            mask_placeholder, seed_ph, lse_ph)


@register_op(
    "flash_attention_grad",
    inputs=("Q", "K", "V", "BiasQK", "Mask", "Out", "Seed", "Lse",
            "GRAD@Out"),
    outputs=("X@Q", "X@K", "X@V"),
    attrs={"causal": False, "scale": 0.0, "layout": "BHSD",
           "dropout_prob": 0.0, "is_test": False},
    optional_inputs=("BiasQK",),
    grad_maker=None,
)
def flash_attention_grad_op(ctx, q, k, v, bias_qk, mask, out, seed_words,
                            lse, dy, causal=False, scale=0.0,
                            layout="BHSD", dropout_prob=0.0,
                            is_test=False):
    """Backward: the composed dropout path replays with the SAVED Mask; the
    dropout-free path differentiates the kernel's own custom vjp.  Routing
    mirrors the forward (same static predicate).  Seed and Lse are the
    forward's placeholders on every path."""
    from ..pallas_kernels import flash_attention as _fa

    _fa_check_layout(layout)
    sm_scale = scale if scale else q.shape[-1] ** -0.5
    bshd = layout == "BSHD"
    attrs = {"dropout_prob": dropout_prob, "is_test": is_test,
             "causal": causal, "layout": layout}
    if _fa_uses_dropout(attrs):
        fn = lambda a, b, c: _attention_composed(
            a, b, c, bias_qk, causal, sm_scale, mask, dropout_prob, bshd)
    elif bshd:
        fn = lambda a, b, c: _attention_composed(
            a, b, c, bias_qk, causal, sm_scale, bshd=True)
    else:
        fn = lambda a, b, c: _fa(a, b, c, bias=bias_qk, causal=causal,
                                 sm_scale=sm_scale)
    _, vjp = jax.vjp(fn, q, k, v)
    return vjp(dy)


flash_attention_op.opdef.rng_when = _fa_uses_dropout


def _fdaln_uses_dropout(attrs):
    return (float(attrs.get("dropout_prob", 0.0) or 0.0) > 0.0
            and not attrs.get("is_test", False))


def _fdaln_rows_over(ctx, x, begin_norm_axis):
    """(mesh, axis) when the epilogue runs per shard of x's leading
    dimension (LowerCtx.rows_axis: the GSPMD route on a data-only mesh that
    divides it), else None: one rule for the op and its grad op, so both
    split alike and the mask replays."""
    if begin_norm_axis < 1 or x.ndim < 2:
        return None
    axis = ctx.rows_axis(x.shape[0])
    return None if axis is None else (ctx.mesh, axis)


def _fused_dropout_add_ln_grad_maker(op, no_grad_set):
    inputs = {
        "R": list(op.output("R")),
        "Scale": list(op.input("Scale")),
        "Seed": list(op.output("Seed")),
        "Mean": list(op.output("Mean")),
        "Variance": list(op.output("Variance")),
        "GRAD@Out": [_grad_var_name(op.output("Out")[0])],
    }
    outputs = {}
    for slot in ("X", "Y", "Scale"):
        n = op.input(slot)[0]
        if n not in no_grad_set:
            outputs["X@" + slot] = [_grad_var_name(n)]
    n = op.input("Bias")[0]
    if n not in no_grad_set:
        outputs["X@Bias"] = [_grad_var_name(n)]
    if not outputs:
        return []
    return [GradOpDesc("fused_dropout_add_ln_grad", inputs, outputs,
                       dict(op.attrs))]


@register_op(
    "fused_dropout_add_ln",
    inputs=("X", "Y", "Scale", "Bias"),
    outputs=("Out", "R", "Mean", "Variance", "Seed"),
    attrs={"dropout_prob": 0.0, "is_test": False, "epsilon": 1e-5,
           "begin_norm_axis": 1, "fix_seed": False, "seed": 0},
    grad_maker=_fused_dropout_add_ln_grad_maker,
    n_rng=1,
)
def fused_dropout_add_ln_op(ctx, x, y, scale, bias, dropout_prob=0.0,
                            is_test=False, epsilon=1e-5, begin_norm_axis=1,
                            fix_seed=False, seed=0, **_):
    """Out = LayerNorm(X + dropout_upscale(Y)): the transformer-encoder
    epilogue as ONE op, lowered to a single-HBM-pass Pallas kernel on TPU
    (pallas_kernels/fused_ln.py; jnp fallback elsewhere).

    TPU-native counterpart of the reference's
    fused_fc_elementwise_layernorm op
    (paddle/fluid/operators/fused/fused_fc_elementwise_layernorm_op.cu —
    inference-only there), extended with in-kernel dropout for training:
    measured 1.82x the composed dropout->add->layer_norm emission fwd+bwd
    at the flagship BERT shape.

    The dropout mask is never materialized: the forward draws it from the
    on-core PRNG seeded by the Seed output (two u32 words stored as
    int32), and the grad op re-draws the identical mask from that saved
    seed — the Mask-output contract of the dropout op at 1/12288th the
    memory.  The backward's only large residual is the R output (the
    post-dropout residual sum); X and Y are NOT saved for it (dx == dr,
    dy == mask*dr/q).  Dropout semantics are upscale_in_train with the
    realized keep probability round(q*2^32)/2^32.
    """
    from ..pallas_kernels import fused_ln as _fln

    p = 0.0 if is_test else float(dropout_prob)
    if p > 0.0:
        key = jax.random.key(seed) if fix_seed else ctx.rng()
        seed_arr = jax.random.bits(key, (2,), jnp.uint32)
    else:
        seed_arr = jnp.zeros((2,), jnp.uint32)
    z, r, mean, var = _fln.fused_ln_fwd(
        x, y, scale, bias, p, seed_arr, epsilon, begin_norm_axis,
        rows_over=_fdaln_rows_over(ctx, x, begin_norm_axis))
    return z, r, mean, var, seed_arr.astype(jnp.int32)


@register_op(
    "fused_dropout_add_ln_grad",
    inputs=("R", "Scale", "Seed", "Mean", "Variance", "GRAD@Out"),
    outputs=("X@X", "X@Y", "X@Scale", "X@Bias"),
    attrs={"dropout_prob": 0.0, "is_test": False, "epsilon": 1e-5,
           "begin_norm_axis": 1, "fix_seed": False, "seed": 0},
    grad_maker=None,
)
def fused_dropout_add_ln_grad_op(ctx, r, scale, seed_words, mean, var,
                                 dz, dropout_prob=0.0, is_test=False,
                                 epsilon=1e-5, begin_norm_axis=1, **_):
    # NB: the Seed INPUT is named seed_words because the attr dict also
    # carries a (fix_seed-mode) "seed" attr passed as a kwarg
    from ..pallas_kernels import fused_ln as _fln

    p = 0.0 if is_test else float(dropout_prob)
    return _fln.fused_ln_bwd(
        r, scale, seed_words, mean, var, dz, p, epsilon, begin_norm_axis,
        rows_over=_fdaln_rows_over(ctx, r, begin_norm_axis))


fused_dropout_add_ln_op.opdef.rng_when = _fdaln_uses_dropout


@register_op(
    "ring_attention",
    inputs=("Q", "K", "V"),
    outputs=("Out",),
    attrs={"causal": False, "scale": 0.0, "axis": "sp"},
)
def ring_attention_op(ctx, q, k, v, causal=False, scale=0.0, axis="sp"):
    """Context-parallel attention: when lowered inside a shard_map whose
    mesh has `axis` sharding the SEQUENCE dim, runs the K/V-rotation ring
    (parallel/ring_attention.py); otherwise falls back to dense flash
    attention (single-device semantics are identical).

    NEW capability vs the reference (no CP/SP existed; SURVEY.md §5).
    scale=0.0 means 1/sqrt(head_dim).

    The batch-DP executor shards feeds on dim 0 over ctx.data_axis — that
    axis must NOT trigger the ring (each rank already holds full sequences;
    treating batch shards as sequence chunks would be silently wrong).  The
    ring engages only for a distinct sequence axis, i.e. under a
    seq-sharded shard_map such as parallel.make_ring_attention_sharded.
    """
    sm_scale = scale if scale else None
    if axis in ctx.axis_names and axis != ctx.data_axis:
        from ..parallel import ring_attention as _ring

        return _ring(q, k, v, axis, causal=causal, sm_scale=sm_scale)
    from ..pallas_kernels import flash_attention as _fa

    return _fa(q, k, v, causal=causal, sm_scale=sm_scale)


@register_op(
    "sync_batch_norm",
    inputs=("X", "Scale", "Bias", "Mean", "Variance"),
    outputs=("Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance",
             "ReserveSpace"),
    attrs={"momentum": 0.9, "epsilon": 1e-5, "is_test": False,
           "data_layout": "NCHW", "use_global_stats": False},
    grad_maker="auto",
    no_grad_inputs=("Mean", "Variance"),
)
def sync_batch_norm(ctx, x, scale, bias, mean, variance, momentum=0.9,
                    epsilon=1e-5, is_test=False, data_layout="NCHW",
                    use_global_stats=False, **_):
    """Cross-replica batch norm (sync_batch_norm_op.cu): statistics are
    reduced over the data-parallel mesh axis with lax.pmean — the TPU
    replacement for the reference's in-kernel ncclAllReduce.  Outside a
    shard_map (single device) it degenerates to plain batch_norm."""
    nchw = data_layout in ("NCHW", "AnyLayout")
    c_ax = 1 if nchw else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_ax)
    cshape = [1] * x.ndim
    cshape[c_ax] = x.shape[c_ax]

    axis_name = ctx.axis_names[0] if (ctx is not None and ctx.axis_names) \
        else None
    return _bn_impl(x, scale, bias, mean, variance, axes, cshape, momentum,
                    epsilon, is_test or use_global_stats,
                    axis_name=axis_name)


