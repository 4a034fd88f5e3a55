"""Shape/layout manipulation ops: reshape, transpose, concat, split, slice,
gather, embedding lookup, one_hot, pad, stack…

Parity: reshape_op.cc, transpose_op.cc, concat_op.cc, split_op.cc,
slice_op.cc, strided_slice_op.cc, gather_op.cc, scatter_op.cc,
lookup_table_op.cc / lookup_table_v2_op.cc, one_hot_op.cc, pad_op.cc,
stack_op.cc, squeeze_op.cc, unsqueeze_op.cc, flatten_op.cc, expand_op.cc
(paddle/fluid/operators/).
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..framework import _grad_var_name
from .common import attr_dtype


def _resolve_shape(x, shape):
    """Fluid reshape semantics: 0 copies the input dim, one -1 is inferred."""
    shape = list(int(s) for s in shape)
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    if -1 in shape:
        known = 1
        for s in shape:
            if s != -1:
                known *= s
        total = 1
        for d in x.shape:
            total *= d
        shape[shape.index(-1)] = total // known
    return tuple(shape)


def _reshape_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    shape = list(op.attr("shape") or [])
    xshape = list(x.shape or [])
    res = []
    for i, s in enumerate(shape):
        if s == 0:
            res.append(xshape[i] if i < len(xshape) else -1)
        else:
            res.append(s)
    if -1 in res and -1 not in xshape:
        known = 1
        for s in res:
            if s != -1:
                known *= s
        total = 1
        for d in xshape:
            total *= d
        res[res.index(-1)] = total // known
    out.shape = tuple(res)
    if out.dtype is None:
        out.dtype = x.dtype
    xs_names = op.output("XShape")
    if xs_names:
        xs = block.var(xs_names[0])
        xs.shape = tuple([0] + xshape)
        if xs.dtype is None:
            xs.dtype = x.dtype


@register_op("reshape2", inputs=("X", "Shape", "ShapeTensor"),
             outputs=("Out", "XShape"),
             attrs={"shape": []},
             optional_inputs=("Shape", "ShapeTensor"),
             duplicable_inputs=("ShapeTensor",),
             infer_shape=_reshape_infer)
def reshape2(ctx, x, shape_t, shape_tensor, shape=()):
    return jnp.reshape(x, _resolve_shape(x, shape)), None


@register_op("reshape", inputs=("X", "Shape"), outputs=("Out",),
             attrs={"shape": []}, optional_inputs=("Shape",),
             infer_shape=_reshape_infer)
def reshape(ctx, x, shape_t, shape=()):
    return jnp.reshape(x, _resolve_shape(x, shape))


def _transpose_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    axis = op.attr("axis")
    if x.shape is not None:
        out.shape = tuple(x.shape[a] for a in axis)
    if out.dtype is None:
        out.dtype = x.dtype
    xs_names = op.output("XShape")
    if xs_names:
        xs = block.var(xs_names[0])
        xs.shape = tuple([0] + list(x.shape or []))
        xs.dtype = x.dtype


@register_op("transpose2", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"axis": []}, infer_shape=_transpose_infer)
def transpose2(ctx, x, axis=()):
    return jnp.transpose(x, axis), None


@register_op("transpose", inputs=("X",), outputs=("Out",),
             attrs={"axis": []}, infer_shape=_transpose_infer)
def transpose(ctx, x, axis=()):
    return jnp.transpose(x, axis)


@register_op("concat", inputs=("X", "AxisTensor"), outputs=("Out",),
             attrs={"axis": 0},
             duplicable_inputs=("X",), optional_inputs=("AxisTensor",))
def concat(ctx, xs, axis_tensor, axis=0):
    return jnp.concatenate(xs, axis=axis)


def _split_infer(op, block):
    x = block.var(op.input("X")[0])
    outs = [block.var(n) for n in op.output("Out")]
    axis = op.attr("axis") or 0
    num = op.attr("num") or 0
    sections = op.attr("sections") or []
    if x.shape is None:
        return
    ax = axis if axis >= 0 else axis + len(x.shape)
    dim = x.shape[ax]
    if num:
        sizes = [dim // num] * num if dim != -1 else [-1] * num
    else:
        sizes = list(sections)
    for o, s in zip(outs, sizes):
        shp = list(x.shape)
        shp[ax] = s
        o.shape = tuple(shp)
        if o.dtype is None:
            o.dtype = x.dtype


@register_op("split", inputs=("X", "AxisTensor", "SectionsTensorList"),
             outputs=("Out",),
             attrs={"axis": 0, "num": 0, "sections": []},
             optional_inputs=("AxisTensor", "SectionsTensorList"),
             duplicable_inputs=("SectionsTensorList",),
             duplicable_outputs=("Out",),
             infer_shape=_split_infer)
def split(ctx, x, axis_tensor, sections_list, axis=0, num=0, sections=()):
    if num:
        return list(jnp.split(x, num, axis=axis))
    idx = np.cumsum(sections)[:-1]
    return list(jnp.split(x, idx, axis=axis))


@register_op("slice", inputs=("Input", "StartsTensor", "EndsTensor"),
             outputs=("Out",),
             attrs={"axes": [], "starts": [], "ends": [],
                    "decrease_axis": [], "infer_flags": []},
             optional_inputs=("StartsTensor", "EndsTensor"))
def slice_op(ctx, input, starts_t, ends_t, axes=(), starts=(), ends=(),
             decrease_axis=(), infer_flags=()):
    idx = [slice(None)] * input.ndim
    for ax, st, en in zip(axes, starts, ends):
        d = input.shape[ax]
        st = int(st)
        en = int(en)
        if st < 0:
            st += d
        if en < 0:
            en += d
        en = min(en, d)
        st = min(max(st, 0), d)
        idx[ax] = slice(st, en)
    out = input[tuple(idx)]
    if decrease_axis:
        out = jnp.squeeze(out, axis=tuple(decrease_axis))
        if out.ndim == 0:
            out = out.reshape((1,))
    return out


@register_op("strided_slice", inputs=("Input",), outputs=("Out",),
             attrs={"axes": [], "starts": [], "ends": [], "strides": [],
                    "decrease_axis": [], "infer_flags": []})
def strided_slice(ctx, input, axes=(), starts=(), ends=(), strides=(),
                  decrease_axis=(), infer_flags=()):
    idx = [slice(None)] * input.ndim
    for ax, st, en, sd in zip(axes, starts, ends, strides):
        idx[ax] = slice(int(st), int(en), int(sd))
    out = input[tuple(idx)]
    if decrease_axis:
        out = jnp.squeeze(out, axis=tuple(decrease_axis))
    return out


def _squeeze_axes(x, axes):
    if axes:
        return tuple(a if a >= 0 else a + x.ndim for a in axes if x.shape[a if a >= 0 else a + x.ndim] == 1)
    return tuple(i for i, d in enumerate(x.shape) if d == 1)


@register_op("squeeze2", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"axes": []})
def squeeze2(ctx, x, axes=()):
    return jnp.squeeze(x, axis=_squeeze_axes(x, axes)), None


@register_op("squeeze", inputs=("X",), outputs=("Out",), attrs={"axes": []})
def squeeze(ctx, x, axes=()):
    return jnp.squeeze(x, axis=_squeeze_axes(x, axes))


@register_op("unsqueeze2", inputs=("X", "AxesTensor"), outputs=("Out", "XShape"),
             attrs={"axes": []}, optional_inputs=("AxesTensor",))
def unsqueeze2(ctx, x, axes_t, axes=()):
    return jnp.expand_dims(x, tuple(axes)), None


@register_op("unsqueeze", inputs=("X",), outputs=("Out",), attrs={"axes": []})
def unsqueeze(ctx, x, axes=()):
    return jnp.expand_dims(x, tuple(axes))


@register_op("flatten2", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"axis": 1})
def flatten2(ctx, x, axis=1):
    lead = 1
    for d in x.shape[:axis]:
        lead *= d
    return jnp.reshape(x, (lead, -1)), None


@register_op("flatten", inputs=("X",), outputs=("Out",), attrs={"axis": 1})
def flatten(ctx, x, axis=1):
    lead = 1
    for d in x.shape[:axis]:
        lead *= d
    return jnp.reshape(x, (lead, -1))


@register_op("flatten_contiguous_range", inputs=("X",), outputs=("Out", "XShape"),
             attrs={"start_axis": 1, "stop_axis": -1})
def flatten_contiguous_range(ctx, x, start_axis=1, stop_axis=-1):
    stop = stop_axis if stop_axis >= 0 else x.ndim + stop_axis
    mid = 1
    for d in x.shape[start_axis:stop + 1]:
        mid *= d
    return jnp.reshape(x, x.shape[:start_axis] + (mid,) + x.shape[stop + 1:]), None


@register_op("stack", inputs=("X",), outputs=("Y",), attrs={"axis": 0},
             duplicable_inputs=("X",))
def stack(ctx, xs, axis=0):
    return jnp.stack(xs, axis=axis)


@register_op("unstack", inputs=("X",), outputs=("Y",),
             attrs={"axis": 0, "num": 0}, duplicable_outputs=("Y",))
def unstack(ctx, x, axis=0, num=0):
    n = num or x.shape[axis]
    return [jnp.squeeze(a, axis) for a in jnp.split(x, n, axis=axis)]


@register_op("expand", inputs=("X", "ExpandTimes"), outputs=("Out",),
             attrs={"expand_times": []}, optional_inputs=("ExpandTimes",))
def expand(ctx, x, expand_times_t, expand_times=()):
    return jnp.tile(x, tuple(int(t) for t in expand_times))


@register_op("expand_as", inputs=("X", "target_tensor"), outputs=("Out",),
             no_grad_inputs=("target_tensor",))
def expand_as(ctx, x, target):
    reps = tuple(t // s for t, s in zip(target.shape, x.shape))
    return jnp.tile(x, reps)


@register_op("tile", inputs=("X",), outputs=("Out",),
             attrs={"repeat_times": []})
def tile(ctx, x, repeat_times=()):
    return jnp.tile(x, tuple(int(t) for t in repeat_times))


@register_op("gather", inputs=("X", "Index"), outputs=("Out",),
             attrs={"overwrite": True}, no_grad_inputs=("Index",))
def gather(ctx, x, index, overwrite=True):
    idx = index.reshape(-1) if index.ndim > 1 else index
    return jnp.take(x, idx.astype(jnp.int32), axis=0)


@register_op("gather_nd", inputs=("X", "Index"), outputs=("Out",),
             no_grad_inputs=("Index",))
def gather_nd(ctx, x, index):
    idx = tuple(jnp.moveaxis(index.astype(jnp.int32), -1, 0))
    return x[idx]


@register_op("scatter", inputs=("X", "Ids", "Updates"), outputs=("Out",),
             attrs={"overwrite": True}, no_grad_inputs=("Ids",))
def scatter(ctx, x, ids, updates, overwrite=True):
    ids = ids.reshape(-1).astype(jnp.int32)
    if overwrite:
        return x.at[ids].set(updates)
    return x.at[ids].add(updates)


@register_op("scatter_nd_add", inputs=("X", "Index", "Updates"),
             outputs=("Out",), no_grad_inputs=("Index",))
def scatter_nd_add(ctx, x, index, updates):
    idx = tuple(jnp.moveaxis(index.astype(jnp.int32), -1, 0))
    return x.at[idx].add(updates)


def _lookup(table, ids, padding_idx):
    out = jnp.take(table, ids.astype(jnp.int32), axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids == padding_idx)[..., None]
        out = jnp.where(mask, 0.0, out)
    return out


@register_op("lookup_table", inputs=("W", "Ids"), outputs=("Out",),
             attrs={"is_sparse": False, "is_distributed": False,
                    "padding_idx": -1, "remote_prefetch": False,
                    "entry_config": "", "entry": "none", "table_names": [],
                    "epmap": [], "height_sections": [], "trainer_id": 0},
             no_grad_inputs=("Ids",))
def lookup_table(ctx, w, ids, padding_idx=-1, **_):
    # fluid v1 lookup_table requires ids shape [..., 1]
    idx = ids
    if idx.ndim >= 2 and idx.shape[-1] == 1:
        idx = jnp.squeeze(idx, -1)
    return _lookup(w, idx, padding_idx)


@register_op("lookup_table_v2", inputs=("W", "Ids"), outputs=("Out",),
             attrs={"is_sparse": False, "is_distributed": False,
                    "padding_idx": -1, "remote_prefetch": False,
                    "table_names": [], "epmap": [], "trainer_id": 0},
             no_grad_inputs=("Ids",))
def lookup_table_v2(ctx, w, ids, padding_idx=-1, **_):
    return _lookup(w, ids, padding_idx)


@register_op("embedding_bag", inputs=("W", "Ids"), outputs=("Out",),
             attrs={"mode": "sum"}, no_grad_inputs=("Ids",))
def embedding_bag(ctx, w, ids, mode="sum"):
    """Bagged lookup: Out[b] = sum_k W[Ids[b, k]] over Ids >= 0 (-1 pads
    ragged bags) — the multi-hot feature read of the recommender path
    (distributed/sparse_table.py lookup_bag): a masked take + sum.  W
    grads (scatter-add) come from its VJP."""
    if mode != "sum":
        raise ValueError("embedding_bag supports mode='sum', got %r"
                         % (mode,))
    g = jnp.take(w, jnp.maximum(ids, 0), axis=0)              # [B, K, D]
    mask = (ids >= 0)[..., None]
    return jnp.sum(jnp.where(mask, g, 0.0), axis=1).astype(w.dtype)


@register_op("one_hot", inputs=("X", "depth_tensor"), outputs=("Out",),
             attrs={"depth": 1, "dtype": 5, "allow_out_of_range": False},
             optional_inputs=("depth_tensor",), grad_maker=None)
def one_hot(ctx, x, depth_t, depth=1, dtype=5, allow_out_of_range=False):
    idx = x
    if idx.ndim >= 2 and idx.shape[-1] == 1:
        idx = jnp.squeeze(idx, -1)
    return jax.nn.one_hot(idx.astype(jnp.int32), depth, dtype=attr_dtype(dtype))


@register_op("one_hot_v2", inputs=("X",), outputs=("Out",),
             attrs={"depth": 1, "dtype": 5, "allow_out_of_range": False},
             grad_maker=None)
def one_hot_v2(ctx, x, depth=1, dtype=5, allow_out_of_range=False):
    return jax.nn.one_hot(x.astype(jnp.int32), depth, dtype=attr_dtype(dtype))


@register_op("pad", inputs=("X",), outputs=("Out",),
             attrs={"paddings": [], "pad_value": 0.0})
def pad(ctx, x, paddings=(), pad_value=0.0):
    cfg = [(int(paddings[2 * i]), int(paddings[2 * i + 1])) for i in range(x.ndim)]
    return jnp.pad(x, cfg, constant_values=pad_value)


@register_op("pad2d", inputs=("X",), outputs=("Out",),
             attrs={"paddings": [0, 0, 0, 0], "mode": "constant",
                    "pad_value": 0.0, "data_format": "NCHW"})
def pad2d(ctx, x, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW"):
    t, b, l, r = (int(p) for p in paddings)
    if data_format == "NCHW":
        cfg = [(0, 0), (0, 0), (t, b), (l, r)]
    else:
        cfg = [(0, 0), (t, b), (l, r), (0, 0)]
    jmode = {"constant": "constant", "reflect": "reflect", "edge": "edge"}[mode]
    if jmode == "constant":
        return jnp.pad(x, cfg, constant_values=pad_value)
    return jnp.pad(x, cfg, mode=jmode)


@register_op("reverse", inputs=("X",), outputs=("Out",), attrs={"axis": []})
def reverse(ctx, x, axis=()):
    return jnp.flip(x, axis=tuple(axis))


@register_op("roll", inputs=("X",), outputs=("Out",),
             attrs={"shifts": [], "axis": []})
def roll(ctx, x, shifts=(), axis=()):
    return jnp.roll(x, tuple(shifts), axis=tuple(axis) if axis else None)


@register_op("where", inputs=("Condition", "X", "Y"), outputs=("Out",),
             no_grad_inputs=("Condition",))
def where(ctx, cond, x, y):
    return jnp.where(cond, x, y)


@register_op("where_index", inputs=("Condition",), outputs=("Out",),
             grad_maker=None)
def where_index(ctx, cond):
    # dynamic output shape: host-side only (not jittable on TPU)
    return jnp.stack(jnp.nonzero(cond), axis=1).astype(jnp.int64)


@register_op("tril_triu", inputs=("X",), outputs=("Out",),
             attrs={"diagonal": 0, "lower": True})
def tril_triu(ctx, x, diagonal=0, lower=True):
    return jnp.tril(x, diagonal) if lower else jnp.triu(x, diagonal)


@register_op("meshgrid", inputs=("X",), outputs=("Out",),
             duplicable_inputs=("X",), duplicable_outputs=("Out",))
def meshgrid(ctx, xs):
    return list(jnp.meshgrid(*xs, indexing="ij"))


@register_op("index_select", inputs=("X", "Index"), outputs=("Out",),
             attrs={"dim": 0}, no_grad_inputs=("Index",))
def index_select(ctx, x, index, dim=0):
    return jnp.take(x, index.astype(jnp.int32), axis=dim)
