"""Optimizer update ops — fused XLA update computations.

Parity: paddle/fluid/operators/optimizers/ (sgd_op.cc, momentum_op.cc,
adam_op.cc, adagrad_op.cc, rmsprop_op.cc, lamb_op.cc, lars_momentum_op.cc,
adadelta_op.cc, adamax_op.cc, decayed_adagrad_op.cc, ftrl_op.cc,
proximal_gd_op.cc).  Each op is pure: reads Param/accumulators, returns the
updated values; the executor stores them back to the scope (donated buffers,
so updates are in-place at the XLA level).
"""

import numpy as np
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op


def _lr(lr):
    return lr.reshape(())


@register_op("sgd", inputs=("Param", "Grad", "LearningRate"),
             outputs=("ParamOut",), grad_maker=None)
def sgd(ctx, param, grad, lr):
    return param - _lr(lr).astype(param.dtype) * grad.astype(param.dtype)


@register_op(
    "momentum",
    inputs=("Param", "Grad", "Velocity", "LearningRate"),
    outputs=("ParamOut", "VelocityOut"),
    attrs={"mu": 0.0, "use_nesterov": False, "regularization_method": "",
           "regularization_coeff": 0.0},
    grad_maker=None,
)
def momentum(ctx, param, grad, velocity, lr, mu=0.0, use_nesterov=False,
             regularization_method="", regularization_coeff=0.0):
    lr = _lr(lr).astype(param.dtype)
    g = grad.astype(param.dtype)
    if regularization_method == "l2_decay":
        g = g + regularization_coeff * param
    v = mu * velocity + g
    if use_nesterov:
        p = param - (g + mu * v) * lr
    else:
        p = param - lr * v
    return p, v


@register_op(
    "adam",
    inputs=("Param", "Grad", "Moment1", "Moment2", "LearningRate",
            "Beta1Pow", "Beta2Pow", "Beta1Tensor", "Beta2Tensor"),
    outputs=("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut"),
    attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8, "lazy_mode": False,
           "min_row_size_to_use_multithread": 1000},
    optional_inputs=("Beta1Tensor", "Beta2Tensor"),
    grad_maker=None,
)
def adam(ctx, param, grad, m1, m2, lr, b1pow, b2pow, b1t, b2t, beta1=0.9,
         beta2=0.999, epsilon=1e-8, **_):
    dt = param.dtype
    beta1 = b1t.reshape(()).astype(dt) if b1t is not None else jnp.asarray(beta1, dt)
    beta2 = b2t.reshape(()).astype(dt) if b2t is not None else jnp.asarray(beta2, dt)
    lr = _lr(lr).astype(dt)
    g = grad.astype(dt)
    m1n = beta1 * m1 + (1.0 - beta1) * g
    m2n = beta2 * m2 + (1.0 - beta2) * g * g
    b1p = b1pow.reshape(()).astype(dt)
    b2p = b2pow.reshape(()).astype(dt)
    lr_t = lr * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
    p = param - lr_t * m1n / (jnp.sqrt(m2n) + epsilon)
    return p, m1n, m2n, (b1pow * beta1).astype(b1pow.dtype), (
        b2pow * beta2
    ).astype(b2pow.dtype)


@register_op(
    "adamax",
    inputs=("Param", "Grad", "Moment", "InfNorm", "LearningRate", "Beta1Pow"),
    outputs=("ParamOut", "MomentOut", "InfNormOut"),
    attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
    grad_maker=None,
)
def adamax(ctx, param, grad, moment, inf_norm, lr, b1pow, beta1=0.9,
           beta2=0.999, epsilon=1e-8):
    lr = _lr(lr)
    m = beta1 * moment + (1.0 - beta1) * grad
    inf = jnp.maximum(beta2 * inf_norm, jnp.abs(grad) + epsilon)
    lr_t = lr / (1.0 - b1pow.reshape(()))
    p = param - lr_t * m / inf
    return p, m, inf


@register_op(
    "adagrad",
    inputs=("Param", "Grad", "Moment", "LearningRate"),
    outputs=("ParamOut", "MomentOut"),
    attrs={"epsilon": 1e-6},
    grad_maker=None,
)
def adagrad(ctx, param, grad, moment, lr, epsilon=1e-6):
    m = moment + grad * grad
    p = param - _lr(lr) * grad / (jnp.sqrt(m) + epsilon)
    return p, m


@register_op(
    "decayed_adagrad",
    inputs=("Param", "Grad", "Moment", "LearningRate"),
    outputs=("ParamOut", "MomentOut"),
    attrs={"decay": 0.95, "epsilon": 1e-6},
    grad_maker=None,
)
def decayed_adagrad(ctx, param, grad, moment, lr, decay=0.95, epsilon=1e-6):
    m = decay * moment + (1.0 - decay) * grad * grad
    p = param - _lr(lr) * grad / (jnp.sqrt(m) + epsilon)
    return p, m


@register_op(
    "adadelta",
    inputs=("Param", "Grad", "AvgSquaredGrad", "AvgSquaredUpdate"),
    outputs=("ParamOut", "AvgSquaredGradOut", "AvgSquaredUpdateOut"),
    attrs={"rho": 0.95, "epsilon": 1e-6},
    grad_maker=None,
)
def adadelta(ctx, param, grad, avg_sq_grad, avg_sq_update, rho=0.95,
             epsilon=1e-6):
    g2 = rho * avg_sq_grad + (1.0 - rho) * grad * grad
    update = -jnp.sqrt((avg_sq_update + epsilon) / (g2 + epsilon)) * grad
    u2 = rho * avg_sq_update + (1.0 - rho) * update * update
    return param + update, g2, u2


@register_op(
    "rmsprop",
    inputs=("Param", "Grad", "MeanSquare", "MeanGrad", "Moment",
            "LearningRate"),
    outputs=("ParamOut", "MomentOut", "MeanSquareOut", "MeanGradOut"),
    attrs={"decay": 0.9, "momentum": 0.0, "epsilon": 1e-10, "centered": False},
    optional_inputs=("MeanGrad",),
    grad_maker=None,
)
def rmsprop(ctx, param, grad, mean_square, mean_grad, moment, lr, decay=0.9,
            momentum=0.0, epsilon=1e-10, centered=False):
    lr = _lr(lr)
    ms = decay * mean_square + (1.0 - decay) * grad * grad
    if centered:
        mg = decay * mean_grad + (1.0 - decay) * grad
        mom = momentum * moment + lr * grad / jnp.sqrt(ms - mg * mg + epsilon)
    else:
        mg = mean_grad
        mom = momentum * moment + lr * grad / jnp.sqrt(ms + epsilon)
    p = param - mom
    return p, mom, ms, mg


@register_op(
    "lars_momentum",
    inputs=("Param", "Grad", "Velocity", "LearningRate"),
    outputs=("ParamOut", "VelocityOut"),
    attrs={"mu": 0.0, "lars_coeff": 0.001, "lars_weight_decay": 0.0005,
           "epsilon": 0.0},
    grad_maker=None,
)
def lars_momentum(ctx, param, grad, velocity, lr, mu=0.0, lars_coeff=0.001,
                  lars_weight_decay=0.0005, epsilon=0.0):
    lr = _lr(lr)
    p_norm = jnp.sqrt(jnp.sum(jnp.square(param)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(grad)))
    local_lr = lr * lars_coeff * p_norm / (
        g_norm + lars_weight_decay * p_norm + epsilon + 1e-20
    )
    v = mu * velocity + local_lr * (grad + lars_weight_decay * param)
    return param - v, v


@register_op(
    "lamb",
    inputs=("Param", "Grad", "Moment1", "Moment2", "LearningRate",
            "Beta1Pow", "Beta2Pow"),
    outputs=("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut"),
    attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6, "weight_decay": 0.01},
    grad_maker=None,
)
def lamb(ctx, param, grad, m1, m2, lr, b1pow, b2pow, beta1=0.9, beta2=0.999,
         epsilon=1e-6, weight_decay=0.01):
    lr = _lr(lr)
    m1n = beta1 * m1 + (1.0 - beta1) * grad
    m2n = beta2 * m2 + (1.0 - beta2) * grad * grad
    b1p = b1pow.reshape(())
    b2p = b2pow.reshape(())
    m1h = m1n / (1.0 - b1p)
    m2h = m2n / (1.0 - b2p)
    r = m1h / (jnp.sqrt(m2h) + epsilon) + weight_decay * param
    w_norm = jnp.sqrt(jnp.sum(jnp.square(param)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
    p = param - lr * ratio * r
    return p, m1n, m2n, b1pow * beta1, b2pow * beta2


@register_op(
    "ftrl",
    inputs=("Param", "SquaredAccumulator", "LinearAccumulator", "Grad",
            "LearningRate"),
    outputs=("ParamOut", "SquaredAccumOut", "LinearAccumOut"),
    attrs={"l1": 0.0, "l2": 0.0, "lr_power": -0.5},
    grad_maker=None,
)
def ftrl(ctx, param, sq_accum, lin_accum, grad, lr, l1=0.0, l2=0.0,
         lr_power=-0.5):
    lr = _lr(lr)
    new_accum = sq_accum + grad * grad
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_accum) - jnp.sqrt(sq_accum)) / lr
    else:
        sigma = (jnp.power(new_accum, -lr_power) - jnp.power(sq_accum, -lr_power)) / lr
    lin = lin_accum + grad - sigma * param
    if lr_power == -0.5:
        denom = jnp.sqrt(new_accum) / lr + 2 * l2
    else:
        denom = jnp.power(new_accum, -lr_power) / lr + 2 * l2
    pre = jnp.clip(lin, -l1, l1) - lin
    p = jnp.where(jnp.abs(lin) > l1, pre / denom, jnp.zeros_like(param))
    return p, new_accum, lin


@register_op(
    "dpsgd",
    inputs=("Param", "Grad", "LearningRate"),
    outputs=("ParamOut",),
    attrs={"clip": 10.0, "batch_size": 16.0, "sigma": 1.0, "seed": 0},
    grad_maker=None,
    n_rng=1,
)
def dpsgd(ctx, param, grad, lr, clip=10.0, batch_size=16.0, sigma=1.0, seed=0):
    import jax

    g_norm = jnp.sqrt(jnp.sum(jnp.square(grad)))
    g = grad / jnp.maximum(1.0, g_norm / clip)
    key = jax.random.key(seed) if seed else ctx.rng()
    noise = jax.random.normal(key, param.shape, dtype=param.dtype) * sigma * clip
    return param - _lr(lr) * (g + noise / batch_size)


@register_op("dgc", inputs=("U", "V", "Grad"),
             outputs=("UOut", "VOut", "EncodeGrad", "GradOut"),
             attrs={"m": 0.9, "ratio": 0.001, "use_nesterov": False,
                    "rampup_begin_step": 0.0, "rampup_step": 0.0,
                    "current_step": 0.0},
             grad_maker=None)
def dgc(ctx, u, v, grad, m=0.9, ratio=0.001, use_nesterov=False,
        rampup_begin_step=0.0, rampup_step=0.0, current_step=0.0):
    """Deep Gradient Compression (dgc_op.h; Lin et al. 2017): momentum
    correction + local gradient accumulation + top-ratio sparsification
    with error feedback.  EncodeGrad is dense-with-zeros (the reference
    allgathers sparse (idx, val) pairs; summing dense-with-zeros over the
    ring computes the same allreduce on TPU, where the dense psum rides
    ICI).  k = max(1, ratio * numel)."""
    g = grad.astype(jnp.float32)
    u_new = m * u + g                     # momentum correction
    # nesterov variant accumulates the lookahead m*u + g (dgc_op.h)
    v_new = v + (m * u_new + g if use_nesterov else u_new)
    flat = v_new.reshape(-1)
    n = flat.shape[0]
    k = max(int(n * float(ratio)), 1)
    thr = lax.top_k(jnp.abs(flat), k)[0][-1]
    mask = (jnp.abs(v_new) >= thr).astype(g.dtype)
    encode = v_new * mask
    v_out = v_new * (1.0 - mask)          # error feedback residual
    u_out = u_new * (1.0 - mask)
    return u_out, v_out, encode, encode.astype(grad.dtype)


# -- horizontally-fused optimizer families -----------------------------------
#
# The reference fuses per-parameter optimizer ops into one kernel over
# coalesced buffers (ir/fuse_optimizer_ops_pass.cc + coalesce_tensor).
# TPU profile (round 3): 315 tiny per-weight update fusions cost ~46 ms of
# a 211 ms ResNet-50 step — each ~64 KB fusion pays a fixed launch/DMA
# cost.  The fused lowerings concatenate the flattened group into ONE
# update computation (a single elementwise pass over ~100 MB), then split
# back; emitted by ir.py fuse_optimizer_ops_pass.


def _flatten_group(tensors):
    import numpy as _np

    sizes = [int(_np.prod(t.shape)) for t in tensors]
    flat = jnp.concatenate([t.reshape(-1) for t in tensors])
    return flat, sizes


def _split_group(flat, sizes, shapes):
    outs, off = [], 0
    for n, shp in zip(sizes, shapes):
        outs.append(flat[off:off + n].reshape(shp))
        off += n
    return outs


@register_op(
    "fused_sgd",
    inputs=("Param", "Grad", "LearningRate"),
    outputs=("ParamOut",),
    duplicable_inputs=("Param", "Grad"),
    duplicable_outputs=("ParamOut",),
    grad_maker=None,
)
def fused_sgd(ctx, params, grads, lr):
    lr_ = _lr(lr).astype(params[0].dtype)
    p_flat, sizes = _flatten_group(params)
    g_flat, _ = _flatten_group([g.astype(params[0].dtype) for g in grads])
    out = p_flat - lr_ * g_flat
    return (_split_group(out, sizes, [p.shape for p in params]),)


@register_op(
    "fused_momentum",
    inputs=("Param", "Grad", "Velocity", "LearningRate"),
    outputs=("ParamOut", "VelocityOut"),
    duplicable_inputs=("Param", "Grad", "Velocity"),
    duplicable_outputs=("ParamOut", "VelocityOut"),
    attrs={"mu": 0.0, "use_nesterov": False, "regularization_method": "",
           "regularization_coeff": 0.0},
    grad_maker=None,
)
def fused_momentum(ctx, params, grads, vels, lr, mu=0.0,
                   use_nesterov=False, regularization_method="",
                   regularization_coeff=0.0):
    dt = params[0].dtype
    lr_ = _lr(lr).astype(dt)
    p_flat, sizes = _flatten_group(params)
    g_flat, _ = _flatten_group([g.astype(dt) for g in grads])
    v_flat, _ = _flatten_group(vels)
    if regularization_method == "l2_decay":
        g_flat = g_flat + regularization_coeff * p_flat
    v_new = mu * v_flat + g_flat
    if use_nesterov:
        p_new = p_flat - (g_flat + mu * v_new) * lr_
    else:
        p_new = p_flat - lr_ * v_new
    shapes = [p.shape for p in params]
    return (_split_group(p_new, sizes, shapes),
            _split_group(v_new, sizes, shapes))


@register_op(
    "fused_adam",
    inputs=("Param", "Grad", "Moment1", "Moment2", "LearningRate",
            "Beta1Pow", "Beta2Pow"),
    outputs=("ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
             "Beta2PowOut"),
    duplicable_inputs=("Param", "Grad", "Moment1", "Moment2", "Beta1Pow",
                       "Beta2Pow"),
    duplicable_outputs=("ParamOut", "Moment1Out", "Moment2Out",
                        "Beta1PowOut", "Beta2PowOut"),
    attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
    grad_maker=None,
)
def fused_adam(ctx, params, grads, m1s, m2s, lr, b1pows, b2pows,
               beta1=0.9, beta2=0.999, epsilon=1e-8):
    dt = params[0].dtype
    lr_ = _lr(lr).astype(dt)
    b1 = jnp.asarray(beta1, dt)
    b2 = jnp.asarray(beta2, dt)
    sizes = [int(np.prod(p.shape)) for p in params]
    g_flat, _ = _flatten_group([g.astype(dt) for g in grads])
    m1_flat, _ = _flatten_group(m1s)
    m2_flat, _ = _flatten_group(m2s)
    m1n = b1 * m1_flat + (1.0 - b1) * g_flat
    m2n = b2 * m2_flat + (1.0 - b2) * g_flat * g_flat
    u_flat = m1n / (jnp.sqrt(m2n) + epsilon)
    # The moment recurrences run as ONE flat elementwise pass (the launch
    # savings the fusion exists for), but the final AXPY applies per-member
    # against the ORIGINAL unconcatenated params.  This drops the p_flat
    # concat, the group-sized lr_t broadcast concat (~param-bytes of pure
    # HBM traffic each at BERT scale: one full extra read+write of the
    # parameter set), and the p_new split copies, while staying bitwise
    # identical — lr_t is piecewise-constant per member, and each ParamOut
    # slice is the same elementwise expression either way.  Per-member
    # bias correction is kept: beta-pow accumulators may diverge (param
    # added mid-training, partial checkpoint restore), so each param gets
    # ITS OWN scalar lr_t — exact parity with the unfused ops.
    p_news, off = [], 0
    for p, b1pow, b2pow, n in zip(params, b1pows, b2pows, sizes):
        b1p = b1pow.reshape(()).astype(dt)
        b2p = b2pow.reshape(()).astype(dt)
        lr_t = lr_ * jnp.sqrt(1.0 - b2p) / (1.0 - b1p)
        p_news.append(p - lr_t * u_flat[off:off + n].reshape(p.shape))
        off += n
    shapes = [p.shape for p in params]
    return (p_news,
            _split_group(m1n, sizes, shapes),
            _split_group(m2n, sizes, shapes),
            [(b.reshape(()) * b1).reshape(b.shape) for b in b1pows],
            [(b.reshape(()) * b2).reshape(b.shape) for b in b2pows])
