"""Fused conv + batch-norm + relu trunk block (Pallas TPU kernel).

TPU-native answer to the reference's conv_bn fusion passes
(paddle/fluid/framework/ir/conv_bn_fuse_pass.cc and the fused
conv2d_fusion CUDA op): the XLA composition materializes the conv output
to HBM, re-reads it for the BN statistics pass, and re-reads it AGAIN for
the normalize+relu pass — at ResNet-50 trunk shapes the BN elementwise
passes are pure HBM-bandwidth cost (~20% of a step, BASELINE.md round-3
profile).  This kernel keeps one image's conv output VMEM-resident and
applies the folded BN affine (+ relu) before it ever leaves the core.

Two variants, per the reference's is_test split:

* **inference** — the BN affine folds to per-channel (a, b) from the
  RUNNING statistics outside the kernel; one pass computes
  ``relu(conv(x, w) * a + b)``.
* **training** — pass 1 computes the conv and accumulates per-image
  per-channel sum / sum-of-squares partials (the batch statistics the op
  contract must emit); the cross-image reduction and the affine fold are
  scalar work outside; pass 2 is a small elementwise affine+relu kernel
  over the VMEM-blocked conv output.

The conv itself is the standard shifted-matmul decomposition: for a
``kh x kw`` filter, kh*kw MXU matmuls ``[OH*OW, C_in] @ [C_in, C_out]``
over strided slices of the padded input — channels ride the lane
dimension, accumulation is f32.

Gradients: the public training entry is a ``custom_vjp`` whose backward
is the jnp fallback composition's VJP (conv transpose rules + the BN
affine chain) — the kernel carries no hand-written backward, so the grads
agree with the reference composition by construction (interp-mode parity
test: tests/test_pallas_blocks.py).

Adoption is probe-gated (adoption.py): FLAGS_use_pallas_conv_block off,
shape/dtype ineligibility, or a missing/sub-1.1x tools/probes row all
fall back to the jnp composition with a counted reason.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl

from . import adoption

__all__ = ["conv_bn_relu_inference", "conv_bn_relu_train",
           "conv_bn_relu_reference", "conv_block_checks"]

# VMEM plan cap for one grid step (input plane + output plane + filter +
# f32 accumulator), conservative against the ~16 MB budget
_VMEM_CAP = 12 * 1024 * 1024


def _out_size(h, k, s, p):
    return (h + 2 * p - k) // s + 1


def conv_block_checks(x_shape, w_shape, strides, paddings, dilations=(1, 1),
                      groups=1, data_format="NCHW", itemsize=4):
    """Ordered (reason, ok) eligibility pairs for adoption.decide().

    The reasons are the telemetry labels — keep them short and stable."""
    sh = tuple(strides)
    pd = tuple(paddings)
    static = all(isinstance(d, int) for d in tuple(x_shape) + tuple(w_shape))
    checks = [
        ("backend", adoption.interpret_mode()
         or jax.default_backend() == "tpu"),
        ("layout", data_format in ("NCHW", "AnyLayout")),
        ("symbolic_shape", static),
        ("rank", len(x_shape) == 4 and len(w_shape) == 4),
        ("groups", int(groups) == 1),
        ("dilation", tuple(dilations) in ((1, 1), ())),
        ("stride", len(sh) == 2 and sh[0] == sh[1] and sh[0] in (1, 2)),
        ("padding", len(pd) == 2 and pd[0] == pd[1]),
    ]
    if not (static and len(x_shape) == 4 and len(w_shape) == 4
            and len(sh) == 2 and len(pd) == 2):
        return checks
    n, c, h, w_ = x_shape
    co, ci, kh, kw = w_shape
    checks += [
        ("kernel_size", kh == kw and kh in (1, 3, 5, 7)),
        ("channels", c % 8 == 0 or c in (3, 4)),  # conv1 takes RGB
        ("out_channels", co % 8 == 0),
    ]
    oh = _out_size(h, kh, sh[0], pd[0])
    ow = _out_size(w_, kw, sh[0], pd[0])
    checks.append(("out_size", oh > 0 and ow > 0))
    plan = (c * (h + 2 * pd[0]) * (w_ + 2 * pd[0]) * 4
            + co * ci * kh * kw * 4 + 2 * co * oh * ow * 4)
    checks.append(("vmem", plan <= _VMEM_CAP))
    return checks


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _conv_image(x, w, stride, pad, oh, ow):
    """[OH*OW, C_out] f32 conv of one image: kh*kw shifted MXU matmuls.

    x: [C, H, W] f32, w: [C_out, C_in, kh, kw] f32.  The kh*kw python
    loop unrolls at trace time; each strided slice is a free VMEM view."""
    c = x.shape[0]
    co, _, kh, kw = w.shape
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)))
    acc = jnp.zeros((oh * ow, co), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            patch = lax.slice(
                xp, (0, i, j),
                (c, i + (oh - 1) * stride + 1, j + (ow - 1) * stride + 1),
                (1, stride, stride))
            rows = patch.reshape(c, oh * ow).T
            acc = acc + lax.dot_general(
                rows, w[:, :, i, j].T,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    return acc


def _infer_kernel(x_ref, w_ref, a_ref, b_ref, y_ref, *, stride, pad, relu):
    oh, ow = y_ref.shape[2], y_ref.shape[3]
    acc = _conv_image(x_ref[0].astype(jnp.float32),
                      w_ref[...].astype(jnp.float32), stride, pad, oh, ow)
    y = acc * a_ref[...] + b_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[0] = y.T.reshape(y_ref.shape[1], oh, ow).astype(y_ref.dtype)


def _train_conv_kernel(x_ref, w_ref, conv_ref, s_ref, ss_ref, *, stride,
                       pad):
    oh, ow = conv_ref.shape[2], conv_ref.shape[3]
    acc = _conv_image(x_ref[0].astype(jnp.float32),
                      w_ref[...].astype(jnp.float32), stride, pad, oh, ow)
    conv_ref[0] = acc.T.reshape(conv_ref.shape[1], oh, ow)
    # per-image per-channel partials: the batch moments reduce over these
    # [N, C_out] strips outside the kernel (one tiny jnp sum)
    s_ref[...] = jnp.sum(acc, axis=0, keepdims=True)
    ss_ref[...] = jnp.sum(acc * acc, axis=0, keepdims=True)


def _affine_relu_kernel(c_ref, a_ref, b_ref, y_ref, *, relu):
    cv = c_ref[0]
    co = cv.shape[0]
    y = cv * a_ref[...].reshape(co, 1, 1) + b_ref[...].reshape(co, 1, 1)
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[0] = y.astype(y_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing (grid over the batch; one image per step)
# ---------------------------------------------------------------------------


def _infer_pallas(x, w, a, b, stride, pad, relu):
    n, c, h, w_ = x.shape
    co, _, kh, kw = w.shape
    oh, ow = _out_size(h, kh, stride, pad), _out_size(w_, kw, stride, pad)
    return pl.pallas_call(
        functools.partial(_infer_kernel, stride=stride, pad=pad, relu=relu),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, c, h, w_), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((co, c, kh, kw), lambda i: (0, 0, 0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, co, oh, ow), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, co, oh, ow), x.dtype),
        interpret=adoption.interpret(),
    )(x, w, a.reshape(1, co).astype(jnp.float32),
      b.reshape(1, co).astype(jnp.float32))


def _train_pallas(x, w, stride, pad):
    n, c, h, w_ = x.shape
    co, _, kh, kw = w.shape
    oh, ow = _out_size(h, kh, stride, pad), _out_size(w_, kw, stride, pad)
    conv, s, ss = pl.pallas_call(
        functools.partial(_train_conv_kernel, stride=stride, pad=pad),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, c, h, w_), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((co, c, kh, kw), lambda i: (0, 0, 0, 0))],
        out_specs=[pl.BlockSpec((1, co, oh, ow), lambda i: (i, 0, 0, 0)),
                   pl.BlockSpec((1, co), lambda i: (i, 0)),
                   pl.BlockSpec((1, co), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, co, oh, ow), jnp.float32),
                   jax.ShapeDtypeStruct((n, co), jnp.float32),
                   jax.ShapeDtypeStruct((n, co), jnp.float32)],
        interpret=adoption.interpret(),
    )(x, w)
    return conv, s, ss


def _affine_pallas(conv, a, b, relu, out_dtype):
    n, co, oh, ow = conv.shape
    return pl.pallas_call(
        functools.partial(_affine_relu_kernel, relu=relu),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, co, oh, ow), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0)),
                  pl.BlockSpec((1, co), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, co, oh, ow), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, co, oh, ow), out_dtype),
        interpret=adoption.interpret(),
    )(conv, a.reshape(1, co).astype(jnp.float32),
      b.reshape(1, co).astype(jnp.float32))


# ---------------------------------------------------------------------------
# jnp reference composition (the fallback AND the backward)
# ---------------------------------------------------------------------------


def _ref_conv(x, w, stride, pad):
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride), padding=[(pad, pad), (pad, pad)],
        dimension_numbers=dn)


def _fold_affine(scale, bias, mean, var, eps):
    inv = 1.0 / jnp.sqrt(var.astype(jnp.float32) + eps)
    a = inv * scale.astype(jnp.float32)
    return a, bias.astype(jnp.float32) - mean.astype(jnp.float32) * a


def _ref_train(x, w, scale, bias, eps, stride, pad, relu):
    conv = _ref_conv(x, w, stride, pad)
    m = jnp.mean(conv, axis=(0, 2, 3))
    v = jnp.mean(jnp.square(conv), axis=(0, 2, 3)) - jnp.square(m)
    a, b = _fold_affine(scale, bias, m, v, eps)
    y = conv * a.reshape(1, -1, 1, 1) + b.reshape(1, -1, 1, 1)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype), m, v


def _ref_infer(x, w, scale, bias, mean, var, eps, stride, pad, relu):
    a, b = _fold_affine(scale, bias, mean, var, eps)
    y = _ref_conv(x, w, stride, pad) * a.reshape(1, -1, 1, 1) \
        + b.reshape(1, -1, 1, 1)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def conv_bn_relu_reference(x, w, scale, bias, mean, var, eps=1e-5, stride=1,
                           pad=0, relu=True, is_test=False):
    """The jnp fallback.  Training returns (y, batch_mean, batch_var);
    inference returns (y, mean, var) — running stats passed through."""
    if is_test:
        return (_ref_infer(x, w, scale, bias, mean, var, eps, stride, pad,
                           relu), mean.astype(jnp.float32),
                var.astype(jnp.float32))
    return _ref_train(x, w, scale, bias, eps, stride, pad, relu)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def conv_bn_relu_inference(x, w, scale, bias, mean, var, eps=1e-5, stride=1,
                           pad=0, relu=True):
    """Folded-scale inference block: relu(conv(x, w) * a + b) in one
    kernel pass, a/b folded from the RUNNING statistics.  Backward (rare —
    is_test graphs — but the op contract stays differentiable) is the
    reference composition's VJP."""
    a, b = _fold_affine(scale, bias, mean, var, eps)
    return _infer_pallas(x, w, a, b, stride, pad, relu)


def _infer_fwd(x, w, scale, bias, mean, var, eps, stride, pad, relu):
    a, b = _fold_affine(scale, bias, mean, var, eps)
    return (_infer_pallas(x, w, a, b, stride, pad, relu),
            (x, w, scale, bias, mean, var))


def _infer_bwd(eps, stride, pad, relu, res, ct):
    x, w, scale, bias, mean, var = res
    _, vjp_fn = jax.vjp(
        lambda *args: _ref_infer(*args, eps, stride, pad, relu),
        x, w, scale, bias, mean, var)
    return vjp_fn(ct)


conv_bn_relu_inference.defvjp(_infer_fwd, _infer_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def conv_bn_relu_train(x, w, scale, bias, eps, stride, pad, relu):
    """Training block: (y, batch_mean [C_out] f32, batch_var [C_out] f32).

    Forward runs the two-pass kernel (conv+stat partials, then
    affine+relu); backward is the reference composition's VJP."""
    return _train_fwd_impl(x, w, scale, bias, eps, stride, pad, relu)


def _train_fwd_impl(x, w, scale, bias, eps, stride, pad, relu):
    conv, s, ss = _train_pallas(x, w, stride, pad)
    n, co, oh, ow = conv.shape
    cnt = float(n * oh * ow)
    m = jnp.sum(s, axis=0) / cnt
    v = jnp.sum(ss, axis=0) / cnt - jnp.square(m)
    a, b = _fold_affine(scale, bias, m, v, eps)
    y = _affine_pallas(conv, a, b, relu, x.dtype)
    return y, m, v


def _train_fwd(x, w, scale, bias, eps, stride, pad, relu):
    outs = _train_fwd_impl(x, w, scale, bias, eps, stride, pad, relu)
    return outs, (x, w, scale, bias)


def _train_bwd(eps, stride, pad, relu, res, cts):
    x, w, scale, bias = res
    _, vjp_fn = jax.vjp(
        lambda x_, w_, s_, b_: _ref_train(x_, w_, s_, b_, eps, stride, pad,
                                          relu),
        x, w, scale, bias)
    cts = tuple(
        c if c is not None else jnp.zeros(o.shape, o.dtype)
        for c, o in zip(cts, _abstract_train_outs(x, w, scale, stride, pad)))
    return vjp_fn(cts)


def _abstract_train_outs(x, w, scale, stride, pad):
    co = w.shape[0]
    oh = _out_size(x.shape[2], w.shape[2], stride, pad)
    ow = _out_size(x.shape[3], w.shape[3], stride, pad)
    return (jax.ShapeDtypeStruct((x.shape[0], co, oh, ow), x.dtype),
            jax.ShapeDtypeStruct((co,), jnp.float32),
            jax.ShapeDtypeStruct((co,), jnp.float32))


conv_bn_relu_train.defvjp(_train_fwd, _train_bwd)
