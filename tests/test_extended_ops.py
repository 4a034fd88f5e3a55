"""Tests for the extended op surface (vision/detection/losses/misc),
following the reference's OpTest pattern: numpy reference vs op output
(tests/unittests/test_*_op.py analogs)."""

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.lowering import LowerCtx
from paddle_tpu.core.registry import get_op_def


def run_op(op_type, *args, **attrs):
    """Eager single-op evaluation through the registry (OpTest-style)."""
    opdef = get_op_def(op_type)
    n_rng = opdef.n_rng
    import jax

    ctx = LowerCtx(rng_key=jax.random.key(0) if n_rng else None, mode="eager")
    full = dict(opdef.default_attrs)
    full.update(attrs)
    return opdef.lower(ctx, *args, **full)


def test_lrn_matches_naive():
    x = np.random.RandomState(0).rand(2, 8, 4, 4).astype("f")
    out, mid = run_op("lrn", jnp.asarray(x), n=5, k=2.0, alpha=1e-4, beta=0.75)
    # naive
    sq = x ** 2
    want = np.zeros_like(x)
    for c in range(8):
        lo, hi = max(0, c - 2), min(8, c + 3)
        acc = sq[:, lo:hi].sum(1)
        want[:, c] = x[:, c] / (2.0 + 1e-4 * acc) ** 0.75
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


def test_shuffle_space_temporal():
    x = np.arange(2 * 4 * 4 * 4, dtype="f").reshape(2, 4, 4, 4)
    out = run_op("shuffle_channel", jnp.asarray(x), group=2)
    want = x.reshape(2, 2, 2, 4, 4).transpose(0, 2, 1, 3, 4).reshape(2, 4, 4, 4)
    np.testing.assert_array_equal(np.asarray(out), want)
    s2d = run_op("space_to_depth", jnp.asarray(x), blocksize=2)
    assert s2d.shape == (2, 16, 2, 2)
    ts = run_op("temporal_shift", jnp.asarray(x), seg_num=2, shift_ratio=0.25)
    assert ts.shape == x.shape
    # first quarter channels shifted forward: segment 0 reads zeros
    np.testing.assert_array_equal(np.asarray(ts)[0, 0], np.zeros((4, 4)))


def test_grid_sampler_identity():
    x = np.random.RandomState(0).rand(1, 2, 5, 5).astype("f")
    ys, xs = np.meshgrid(np.linspace(-1, 1, 5), np.linspace(-1, 1, 5),
                         indexing="ij")
    grid = np.stack([xs, ys], -1)[None].astype("f")
    out = run_op("grid_sampler", jnp.asarray(x), jnp.asarray(grid))
    np.testing.assert_allclose(np.asarray(out), x, atol=1e-5)


def test_conv3d_pool3d_shapes():
    x = np.random.RandomState(0).rand(2, 3, 8, 8, 8).astype("f")
    w = np.random.RandomState(1).rand(4, 3, 3, 3, 3).astype("f")
    out = run_op("conv3d", jnp.asarray(x), jnp.asarray(w),
                 strides=[1, 1, 1], paddings=[1, 1, 1])
    assert out.shape == (2, 4, 8, 8, 8)
    p = run_op("pool3d", jnp.asarray(x), pooling_type="max",
               ksize=[2, 2, 2], strides=[2, 2, 2], paddings=[0, 0, 0])
    assert p.shape == (2, 3, 4, 4, 4)
    np.testing.assert_allclose(
        np.asarray(p)[0, 0, 0, 0, 0], x[0, 0, :2, :2, :2].max(), rtol=1e-6)


def test_bilinear_tensor_product():
    x = np.random.RandomState(0).rand(3, 4).astype("f")
    y = np.random.RandomState(1).rand(3, 5).astype("f")
    w = np.random.RandomState(2).rand(2, 4, 5).astype("f")
    out = run_op("bilinear_tensor_product", jnp.asarray(x), jnp.asarray(y),
                 jnp.asarray(w), None)
    want = np.einsum("bi,kij,bj->bk", x, w, y)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


def test_spectral_norm_normalizes():
    w = np.random.RandomState(0).randn(6, 4).astype("f")
    u = np.random.RandomState(1).randn(6).astype("f")
    v = np.random.RandomState(2).randn(4).astype("f")
    out = run_op("spectral_norm", jnp.asarray(w), jnp.asarray(u),
                 jnp.asarray(v), dim=0, power_iters=20)
    sigma = np.linalg.svd(w, compute_uv=False)[0]
    np.testing.assert_allclose(np.linalg.svd(np.asarray(out),
                                             compute_uv=False)[0],
                               1.0, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(out), w / sigma, rtol=1e-3)


# -- losses -------------------------------------------------------------------


def test_rank_and_margin_losses():
    lbl = np.array([[1.0], [0.0]], "f")
    l = np.array([[2.0], [0.5]], "f")
    r = np.array([[1.0], [1.5]], "f")
    out = run_op("rank_loss", jnp.asarray(lbl), jnp.asarray(l), jnp.asarray(r))
    want = l - r
    want = want * (1 - lbl) + np.log1p(np.exp(-np.abs(want))) + np.maximum(
        -(l - r), 0)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)

    mlbl = np.array([[1.0], [-1.0]], "f")
    out, act = run_op("margin_rank_loss", jnp.asarray(mlbl), jnp.asarray(l),
                      jnp.asarray(r), margin=0.1)
    want = np.maximum(0, -mlbl * (l - r) + 0.1)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


def test_bpr_loss_positive():
    x = np.random.RandomState(0).rand(4, 5).astype("f")
    lbl = np.array([[0], [1], [2], [3]], "int64")
    out = run_op("bpr_loss", jnp.asarray(x), jnp.asarray(lbl))
    assert out.shape == (4, 1)
    assert (np.asarray(out) > 0).all()


def test_mean_iou_perfect_and_half():
    pred = np.array([0, 1, 1, 0], "int64")
    lbl = np.array([0, 1, 0, 0], "int64")
    miou, wrong, correct = run_op("mean_iou", jnp.asarray(pred),
                                  jnp.asarray(lbl), num_classes=2)
    # class0: inter 2, union 3 -> 2/3; class1: inter 1, union 2 -> 0.5
    np.testing.assert_allclose(float(miou), (2 / 3 + 0.5) / 2, rtol=1e-5)


def test_warpctc_matches_simple_case():
    # single sequence, T=2, single label: loss = -log P(paths)
    B, T, C, L = 1, 2, 3, 1
    logits = np.log(np.array([[[0.6, 0.3, 0.1], [0.5, 0.4, 0.1]]], "f"))
    label = np.array([[1]], "int64")
    _, loss = run_op("warpctc", jnp.asarray(logits), jnp.asarray(label),
                     blank=0)
    # paths for label [1]: (b,1),(1,b),(1,1)
    p = 0.6 * 0.4 + 0.3 * 0.5 + 0.3 * 0.4
    np.testing.assert_allclose(float(np.asarray(loss)[0, 0]), -np.log(p),
                               rtol=1e-4)


def test_warpctc_trains_in_program():
    rng = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8, 16])
        lbl = fluid.layers.data("lbl", shape=[3], dtype="int64")
        logits = fluid.layers.fc(x, 5, num_flatten_dims=2)
        loss = fluid.layers.mean(fluid.layers.warpctc(logits, lbl))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": rng.rand(2, 8, 16).astype("f"),
            "lbl": np.array([[1, 2, -1], [3, -1, -1]], "int64")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        l0, = exe.run(main, feed=feed, fetch_list=[loss])
        for _ in range(10):
            l1, = exe.run(main, feed=feed, fetch_list=[loss])
    assert float(np.asarray(l1).ravel()[0]) < float(np.asarray(l0).ravel()[0])


def test_edit_distance():
    hyps = np.array([[1, 2, 3, -1], [1, -1, -1, -1]], "int64")
    refs = np.array([[1, 3, -1], [2, 2, -1]], "int64")
    out, n = run_op("edit_distance", jnp.asarray(hyps), jnp.asarray(refs),
                    normalized=False)
    np.testing.assert_allclose(np.asarray(out).ravel(), [1.0, 2.0])


# -- misc ---------------------------------------------------------------------


def test_multiplex_and_crop():
    x1 = np.ones((3, 2), "f")
    x2 = np.full((3, 2), 2.0, "f")
    ids = np.array([[1], [0], [1]], "int32")
    out = run_op("multiplex", jnp.asarray(ids),
                 [jnp.asarray(x1), jnp.asarray(x2)])
    np.testing.assert_array_equal(np.asarray(out)[:, 0], [2, 1, 2])

    x = np.arange(16, dtype="f").reshape(4, 4)
    c = run_op("crop_tensor", jnp.asarray(x), offsets=[1, 1], shape=[2, 2])
    np.testing.assert_array_equal(np.asarray(c), x[1:3, 1:3])


def test_shard_index_and_unique():
    x = np.array([[0], [5], [9], [3]], "int64")
    out = run_op("shard_index", jnp.asarray(x), index_num=10, nshards=2,
                 shard_id=0, ignore_value=-1)
    np.testing.assert_array_equal(np.asarray(out).ravel(), [0, -1, -1, 3])
    u, idx, cnt = run_op("unique_with_counts",
                         jnp.asarray(np.array([2, 3, 2, 5], "int64")))
    c = np.asarray(cnt)
    assert c.sum() == 4 and (c > 0).sum() == 3


def test_gather_tree():
    ids = np.array([[[2, 2]], [[3, 4]], [[5, 6]]], "int64")      # [T=3,B=1,K=2]
    parents = np.array([[[0, 0]], [[0, 0]], [[1, 0]]], "int64")
    out = run_op("gather_tree", jnp.asarray(ids), jnp.asarray(parents))
    # beam 0 at t=2 came from parent 1 at t=1 (id 4), which came from 0 (2)
    np.testing.assert_array_equal(np.asarray(out)[:, 0, 0], [2, 4, 5])


# -- detection ----------------------------------------------------------------


def test_iou_and_box_coder_roundtrip():
    a = np.array([[0, 0, 2, 2]], "f")
    b = np.array([[1, 1, 3, 3], [0, 0, 2, 2]], "f")
    iou = run_op("iou_similarity", jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(iou).ravel(), [1 / 7, 1.0],
                               rtol=1e-5)

    prior = np.array([[0.1, 0.1, 0.5, 0.5], [0.2, 0.2, 0.8, 0.8]], "f")
    target = np.array([[0.15, 0.2, 0.55, 0.7]], "f")
    enc = run_op("box_coder", jnp.asarray(prior), None, jnp.asarray(target),
                 code_type="encode_center_size")
    dec = run_op("box_coder", jnp.asarray(prior), None, jnp.asarray(enc),
                 code_type="decode_center_size")
    np.testing.assert_allclose(np.asarray(dec)[0][0], target[0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(dec)[0][1], target[0], atol=1e-5)


def test_prior_box_properties():
    feat = np.zeros((1, 8, 4, 4), "f")
    img = np.zeros((1, 3, 32, 32), "f")
    boxes, var = run_op("prior_box", jnp.asarray(feat), jnp.asarray(img),
                        min_sizes=[8.0], aspect_ratios=[1.0, 2.0],
                        variances=[0.1, 0.1, 0.2, 0.2], clip=True)
    assert boxes.shape == (4, 4, 2, 4)  # aspect ratios {1, 2}, no max_size
    b = np.asarray(boxes)
    assert (b >= 0).all() and (b <= 1).all()
    assert (b[..., 2] >= b[..., 0]).all()


def test_bipartite_match_greedy():
    dist = np.array([[0.9, 0.1], [0.8, 0.7]], "f")
    idx, d = run_op("bipartite_match", jnp.asarray(dist))
    # greedy: (0,0)=0.9 first, then (1,1)=0.7
    np.testing.assert_array_equal(np.asarray(idx).ravel(), [0, 1])
    np.testing.assert_allclose(np.asarray(d).ravel(), [0.9, 0.7], rtol=1e-6)


def test_multiclass_nms_suppresses():
    boxes = np.array([[[0, 0, 10, 10], [0, 0, 10.5, 10.5],
                       [20, 20, 30, 30]]], "f")
    scores = np.array([[[0.9, 0.85, 0.6]]], "f")  # [N=1, C=1... wrong]
    scores = np.transpose(scores, (0, 2, 1))  # [1, 1, 3]? need [N,C,M]
    scores = np.array([[[0.9, 0.85, 0.6]]], "f")  # [1, 1, 3] = N,C,M
    out = run_op("multiclass_nms", jnp.asarray(boxes), jnp.asarray(scores),
                 background_label=-1, nms_threshold=0.5, nms_top_k=3,
                 keep_top_k=3, score_threshold=0.1)
    o = np.asarray(out)[0]
    kept = o[o[:, 0] >= 0]
    # the two overlapping boxes collapse to one; the far box survives
    assert kept.shape[0] == 2
    np.testing.assert_allclose(sorted(kept[:, 1]), [0.6, 0.9], rtol=1e-6)


def test_roi_align_pool_shapes_and_values():
    x = np.arange(16, dtype="f").reshape(1, 1, 4, 4)
    rois = np.array([[0, 0, 0, 4, 4]], "f")  # whole image
    out = run_op("roi_pool", jnp.asarray(x), jnp.asarray(rois),
                 pooled_height=2, pooled_width=2, spatial_scale=1.0)[0]
    np.testing.assert_allclose(np.asarray(out)[0, 0],
                               [[5, 7], [13, 15]])
    oa = run_op("roi_align", jnp.asarray(x), jnp.asarray(rois),
                pooled_height=2, pooled_width=2, spatial_scale=1.0)
    assert oa.shape == (1, 1, 2, 2)


def test_yolo_box_shapes():
    N, A, C, H, W = 1, 2, 3, 2, 2
    x = np.random.RandomState(0).randn(N, A * (5 + C), H, W).astype("f")
    img = np.array([[64, 64]], "int32")
    boxes, scores = run_op("yolo_box", jnp.asarray(x), jnp.asarray(img),
                           anchors=[10, 14, 23, 27], class_num=C,
                           conf_thresh=0.0, downsample_ratio=32)
    assert boxes.shape == (N, A * H * W, 4)
    assert scores.shape == (N, A * H * W, C)
    b = np.asarray(boxes)
    assert (b >= 0).all() and (b <= 64).all()


def test_detection_layers_in_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feat = fluid.layers.data("feat", shape=[8, 4, 4])
        img = fluid.layers.data("img", shape=[3, 32, 32])
        boxes, var = fluid.layers.prior_box(feat, img, min_sizes=[8.0])
        out = fluid.layers.reduce_sum(boxes)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, = exe.run(main, feed={"feat": np.zeros((1, 8, 4, 4), "f"),
                                 "img": np.zeros((1, 3, 32, 32), "f")},
                     fetch_list=[out])
    assert np.isfinite(np.asarray(o)).all()


def test_positional_attr_layers():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4, 4, 4])
        a = fluid.layers.space_to_depth(x, 2)
        b = fluid.layers.shuffle_channel(x, 2)
        c = fluid.layers.lrn(x, 5)
    assert a.name and b.name and c.name


def test_single_class_nms_no_crash():
    boxes = np.array([[[0, 0, 10, 10], [20, 20, 30, 30]]], "f")
    scores = np.array([[[0.9, 0.6]]], "f")  # [N=1, C=1, M=2]
    out = run_op("multiclass_nms", jnp.asarray(boxes), jnp.asarray(scores),
                 background_label=0, nms_threshold=0.5, nms_top_k=2,
                 keep_top_k=2, score_threshold=0.1)
    o = np.asarray(out)[0]
    assert (o[:, 0] >= 0).sum() == 2


def test_prior_box_min_max_order():
    feat = np.zeros((1, 8, 2, 2), "f")
    img = np.zeros((1, 3, 16, 16), "f")
    boxes, _ = run_op("prior_box", jnp.asarray(feat), jnp.asarray(img),
                      min_sizes=[4.0], max_sizes=[8.0],
                      aspect_ratios=[1.0, 2.0],
                      variances=[0.1, 0.1, 0.2, 0.2],
                      min_max_aspect_ratios_order=True)
    b = np.asarray(boxes)
    # order: min square, max square, ar=2 — widths at cell (0,0):
    w = (b[0, 0, :, 2] - b[0, 0, :, 0]) * 16
    np.testing.assert_allclose(w, [4.0, (4 * 8) ** 0.5, 4 * 2 ** 0.5],
                               rtol=1e-5)


# -- CRF ----------------------------------------------------------------------


def _crf_brute(em, trans_full, lens):
    """Enumerate all paths: returns (logZ, best_path) per sequence."""
    import itertools
    start, stop, trans = trans_full[0], trans_full[1], trans_full[2:]
    B, T, C = em.shape
    logZs, paths = [], []
    for b in range(B):
        L = lens[b]
        scores = {}
        for path in itertools.product(range(C), repeat=L):
            s = start[path[0]] + em[b, 0, path[0]]
            for t in range(1, L):
                s += trans[path[t - 1], path[t]] + em[b, t, path[t]]
            s += stop[path[-1]]
            scores[path] = s
        vals = np.array(list(scores.values()))
        m = vals.max()
        logZs.append(m + np.log(np.exp(vals - m).sum()))
        paths.append(list(max(scores, key=scores.get)))
    return np.array(logZs), paths


def test_linear_chain_crf_matches_bruteforce():
    rng = np.random.RandomState(0)
    B, T, C = 2, 4, 3
    em = rng.randn(B, T, C).astype("f")
    trans = rng.randn(C + 2, C).astype("f") * 0.5
    label = rng.randint(0, C, (B, T)).astype("int64")
    lens = np.array([4, 3], "int64")
    _, _, _, nll = run_op("linear_chain_crf", jnp.asarray(em),
                          jnp.asarray(trans), jnp.asarray(label),
                          jnp.asarray(lens))
    logZ, _ = _crf_brute(em, trans, lens)
    # gold scores by hand
    start, stop, tr = trans[0], trans[1], trans[2:]
    for b in range(B):
        L = lens[b]
        g = start[label[b, 0]] + em[b, 0, label[b, 0]]
        for t in range(1, L):
            g += tr[label[b, t - 1], label[b, t]] + em[b, t, label[b, t]]
        g += stop[label[b, L - 1]]
        np.testing.assert_allclose(float(np.asarray(nll)[b, 0]),
                                   logZ[b] - g, rtol=1e-4)


def test_crf_decoding_matches_bruteforce():
    rng = np.random.RandomState(1)
    B, T, C = 2, 4, 3
    em = rng.randn(B, T, C).astype("f")
    trans = rng.randn(C + 2, C).astype("f") * 0.5
    lens = np.array([4, 3], "int64")
    path = run_op("crf_decoding", jnp.asarray(em), jnp.asarray(trans),
                  None, jnp.asarray(lens))
    _, best = _crf_brute(em, trans, lens)
    p = np.asarray(path)
    for b in range(B):
        np.testing.assert_array_equal(p[b, :lens[b]], best[b])


def test_crf_trains_in_program():
    rng = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[5, 8])
        lbl = fluid.layers.data("lbl", shape=[5], dtype="int64")
        em = fluid.layers.fc(x, 4, num_flatten_dims=2)
        nll = fluid.layers.linear_chain_crf(em, lbl)
        loss = fluid.layers.mean(nll)
        fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": rng.rand(3, 5, 8).astype("f"),
            "lbl": rng.randint(0, 4, (3, 5)).astype("int64")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        l0, = exe.run(main, feed=feed, fetch_list=[loss])
        for _ in range(20):
            l1, = exe.run(main, feed=feed, fetch_list=[loss])
    assert float(np.asarray(l1).ravel()[0]) < float(np.asarray(l0).ravel()[0])


def test_stacked_lstm_and_lstmp():
    rng = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6, 8])
        out, lh, lc = fluid.layers.lstm(x, None, None, 6, hidden_size=10,
                                        num_layers=2, is_bidirec=True)
        proj, cells = fluid.layers.dynamic_lstmp(
            fluid.layers.fc(x, 32, num_flatten_dims=2), 32, proj_size=5)
        loss = fluid.layers.reduce_mean(out) + fluid.layers.reduce_mean(proj)
        fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": rng.rand(2, 6, 8).astype("f")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, p, l0 = exe.run(main, feed=feed, fetch_list=[out, proj, loss])
        for _ in range(5):
            _, _, l1 = exe.run(main, feed=feed, fetch_list=[out, proj, loss])
    assert np.asarray(o).shape == (2, 6, 20)   # bidirectional 2*10
    assert np.asarray(p).shape == (2, 6, 5)
    assert float(np.asarray(l1).ravel()[0]) < float(np.asarray(l0).ravel()[0])


def test_nce_hsigmoid_train():
    rng = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        nce_cost = fluid.layers.nce(h, y, num_total_classes=20,
                                    num_neg_samples=5)
        hs_cost = fluid.layers.hsigmoid(h, y, num_classes=20)
        loss = fluid.layers.mean(nce_cost) + fluid.layers.mean(hs_cost)
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": rng.rand(16, 8).astype("f"),
            "y": rng.randint(0, 20, (16, 1)).astype("int64")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        l0, = exe.run(main, feed=feed, fetch_list=[loss])
        for _ in range(15):
            l1, = exe.run(main, feed=feed, fetch_list=[loss])
    assert float(np.asarray(l1).ravel()[0]) < float(np.asarray(l0).ravel()[0])


def test_hsigmoid_is_valid_distribution():
    # sum over classes of exp(-loss(c)) must be 1 for a binary tree
    import jax
    x = jnp.asarray(np.random.RandomState(0).rand(1, 4).astype("f"))
    w = jnp.asarray(np.random.RandomState(1).randn(8, 4).astype("f") * 0.5)
    tot = 0.0
    for c in range(8):
        loss, _, _ = run_op("hierarchical_sigmoid", x, w,
                            jnp.asarray(np.array([[c]], "int64")), None,
                            None, None, num_classes=8)
        tot += float(np.exp(-np.asarray(loss)[0, 0]))
    np.testing.assert_allclose(tot, 1.0, rtol=1e-4)


def test_py_func_callback():
    def double_plus_one(a):
        return a * 2 + 1

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        out = fluid.layers.data("out_placeholder", shape=[4])
        out = main.global_block().create_var(name="pyout", shape=(2, 4),
                                             dtype="float32")
        fluid.layers.py_func(double_plus_one, x, out)
        s = fluid.layers.reduce_sum(out)
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.arange(8, dtype="f").reshape(2, 4)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, = exe.run(main, feed={"x": xv}, fetch_list=[s])
    np.testing.assert_allclose(float(np.asarray(o).ravel()[0]),
                               (xv * 2 + 1).sum(), rtol=1e-6)


# -- sync BN / QAT / Print ----------------------------------------------------


def test_sync_batch_norm_matches_bn_on_mesh():
    # 4-way data-parallel sync BN must equal single-device BN on the full
    # batch (the exact property the reference's NCCL kernel provides)
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(8, 6, 4, 4).astype("f"))
    scale = jnp.ones((6,), "float32")
    bias = jnp.zeros((6,), "float32")
    mean = jnp.zeros((6,), "float32")
    var = jnp.ones((6,), "float32")

    # single-device reference
    y_ref, m_ref, v_ref, _, _, _ = run_op(
        "batch_norm", x, scale, bias, mean, var, is_test=False)

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    opdef = get_op_def("sync_batch_norm")
    from paddle_tpu.core.lowering import LowerCtx

    def shard_fn(xs):
        ctx = LowerCtx(mode="eager", axis_names=("data",))
        y, m, v, _, _, _ = opdef.lower(ctx, xs, scale, bias, mean, var,
                                       momentum=0.9, epsilon=1e-5,
                                       is_test=False, data_layout="NCHW",
                                       use_global_stats=False)
        return y, m, v

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(P("data"),),
                       out_specs=(P("data"), P(), P()), check_vma=False)
    y, m, v = jax.jit(fn)(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref), rtol=1e-5)


def test_fake_quantize_ops():
    x = jnp.asarray(np.array([[0.5, -1.0], [0.25, 0.74]], "f"))
    out, scale = run_op("fake_quantize_abs_max", x, bit_length=8)
    assert float(scale[0]) == 1.0
    np.testing.assert_allclose(np.asarray(out),
                               np.round(np.asarray(x) * 127) / 127,
                               rtol=1e-6)
    w = jnp.asarray(np.random.RandomState(0).randn(4, 3).astype("f"))
    qw, sc = run_op("fake_channel_wise_quantize_abs_max", w, quant_axis=0)
    assert sc.shape == (4,)
    np.testing.assert_allclose(np.asarray(sc),
                               np.abs(np.asarray(w)).max(1), rtol=1e-6)


def test_qat_pass_rewrites_and_trains():
    from paddle_tpu.contrib.slim.quantization import (
        QuantizationTransformPass)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        logits = fluid.layers.fc(h, 4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
    n = QuantizationTransformPass().apply(main, startup)
    assert n == 2  # both fc muls rewritten
    with fluid.program_guard(main, startup):
        fluid.optimizer.SGD(0.1).minimize(loss)
    types = [op.type for op in main.global_block().ops]
    assert "fake_channel_wise_quantize_abs_max" in types
    assert "fake_quantize_moving_average_abs_max" in types
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(16, 8).astype("f"),
            "y": rng.randint(0, 4, (16, 1)).astype("int64")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        l0, = exe.run(main, feed=feed, fetch_list=[loss])
        for _ in range(20):
            l1, = exe.run(main, feed=feed, fetch_list=[loss])
    assert float(np.asarray(l1).ravel()[0]) < float(np.asarray(l0).ravel()[0])


def test_print_layer_passthrough(capsys):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[2])
        p = fluid.layers.Print(x, message="dbg: ")
        out = fluid.layers.reduce_sum(p)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, = exe.run(main, feed={"x": np.ones((1, 2), "f")},
                     fetch_list=[out])
    assert float(np.asarray(o).ravel()[0]) == 2.0


def test_rnn_cell_classes():
    rng = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[5, 6])
        gout, glast = fluid.layers.rnn(fluid.layers.GRUCell(8), x)
        lout, llast = fluid.layers.rnn(fluid.layers.LSTMCell(8), x)
        loss = fluid.layers.reduce_mean(gout) + fluid.layers.reduce_mean(lout)
        fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": rng.rand(3, 5, 6).astype("f")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        g, l, l0 = exe.run(main, feed=feed, fetch_list=[gout, lout, loss])
        for _ in range(5):
            _, _, l1 = exe.run(main, feed=feed, fetch_list=[gout, lout, loss])
    assert np.asarray(g).shape == (3, 5, 8)
    assert np.asarray(l).shape == (3, 5, 8)
    assert float(np.asarray(l1).ravel()[0]) < float(np.asarray(l0).ravel()[0])


def test_rnn_cell_final_states_structure():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4, 3])
        out, (h, c) = fluid.layers.rnn(fluid.layers.LSTMCell(6), x)
        gout, gh = fluid.layers.rnn(fluid.layers.GRUCell(6), x)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, hv, cv, go, ghv = exe.run(
            main, feed={"x": np.random.RandomState(0).rand(2, 4, 3).astype("f")},
            fetch_list=[out, h, c, gout, gh])
    assert np.asarray(hv).shape == (2, 6)
    assert np.asarray(cv).shape == (2, 6)
    # final h equals the last output step
    np.testing.assert_allclose(np.asarray(hv), np.asarray(o)[:, -1], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ghv), np.asarray(go)[:, -1],
                               rtol=1e-6)
    # LSTM cell state differs from hidden (c != h)
    assert not np.allclose(np.asarray(cv), np.asarray(hv))


# -- batch 3: control-flow mux / ctc decode / chunk eval / detection comp ----


def test_ctc_greedy_decoder():
    # ids over time: blank=0
    logits = np.zeros((2, 6, 4), "f")
    seq = [[1, 1, 0, 2, 2, 0], [0, 3, 0, 3, 1, 1]]
    for b in range(2):
        for t, c in enumerate(seq[b]):
            logits[b, t, c] = 5.0
    out = run_op("ctc_align", jnp.asarray(logits), blank=0)
    o = np.asarray(out)
    np.testing.assert_array_equal(o[0][:2], [1, 2])
    assert (o[0][2:] == -1).all()
    np.testing.assert_array_equal(o[1][:3], [3, 3, 1])


def test_chunk_eval_iob():
    # IOB with 1 type: B=0, I=1, O=2
    lab = np.array([[0, 1, 2, 0, 1, -1]], "int64")   # 2 chunks
    inf = np.array([[0, 1, 2, 0, 2, -1]], "int64")   # 1st exact, 2nd short
    p, r, f1, ni, nl, nc = run_op("chunk_eval", jnp.asarray(inf),
                                  jnp.asarray(lab), num_chunk_types=1)
    assert int(ni) == 2 and int(nl) == 2 and int(nc) == 1
    np.testing.assert_allclose(float(p), 0.5)
    np.testing.assert_allclose(float(r), 0.5)


def test_hash_deterministic_in_range():
    x = np.array([[1], [2], [1]], "int64")
    out = run_op("hash", jnp.asarray(x), mod_by=100, num_hash=2)
    o = np.asarray(out)
    assert o.shape == (3, 2)
    assert (o >= 0).all() and (o < 100).all()
    np.testing.assert_array_equal(o[0], o[2])  # same input, same hash
    assert not np.array_equal(o[0], o[1])


def test_im2sequence_and_seq_slice():
    x = np.arange(16, dtype="f").reshape(1, 1, 4, 4)
    out = run_op("im2sequence", jnp.asarray(x), kernels=[2, 2],
                 strides=[2, 2], paddings=[0, 0])
    assert out.shape == (1, 4, 4)
    np.testing.assert_array_equal(np.asarray(out)[0, 0], [0, 1, 4, 5])

    s = np.arange(12, dtype="f").reshape(2, 6)
    sl = run_op("sequence_slice_dense", jnp.asarray(s),
                jnp.asarray(np.array([1, 2], "int64")),
                jnp.asarray(np.array([3, 2], "int64")))
    np.testing.assert_array_equal(np.asarray(sl)[0][:3], [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(sl)[1][:2], [8, 9])
    assert np.asarray(sl)[1][2] == 0


def test_case_switch_case():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1])
        import paddle_tpu.layers.tensor as T

        two = T.fill_constant([1], "float32", 2.0)

        def b1():
            return x * 10.0

        def b2():
            return x + 100.0

        pred = fluid.layers.reduce_sum(x) > fluid.layers.reduce_sum(two)
        out = fluid.layers.case([(pred, b1)], default=b2)
        idx = T.fill_constant([1], "int64", 1)
        sout = fluid.layers.switch_case(idx, {0: b1, 1: b2})
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, so = exe.run(main, feed={"x": np.array([[5.0]], "f")},
                        fetch_list=[out, sout])
    assert float(np.asarray(o).ravel()[0]) == 50.0     # pred true -> b1
    assert float(np.asarray(so).ravel()[0]) == 105.0   # branch 1 -> +100


def test_detection_output_and_ssd_loss():
    rng = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        loc = fluid.layers.data("loc", shape=[8, 4])
        conf = fluid.layers.data("conf", shape=[8, 3])
        pb = fluid.layers.data("pb", shape=[4])      # [P,4] no batch? use -1
        pb2 = fluid.layers.data("pb2", shape=[4])
        gt = fluid.layers.data("gt", shape=[4])
        gl = fluid.layers.data("gl", shape=[1], dtype="int64")
        nms = fluid.layers.detection_output(
            loc, fluid.layers.softmax(conf), pb, [0.1, 0.1, 0.2, 0.2],
            keep_top_k=4, nms_top_k=8, score_threshold=0.01)
        loss = fluid.layers.ssd_loss(
            loc, conf, gt, gl, pb, prior_box_var=[0.1, 0.1, 0.2, 0.2])
    exe = fluid.Executor(fluid.CPUPlace())
    P = 8
    priors = np.stack([np.linspace(0, 0.8, P), np.linspace(0, 0.8, P),
                       np.linspace(0.2, 1.0, P), np.linspace(0.2, 1.0, P)],
                      1).astype("f")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        o, l = exe.run(main, feed={
            "loc": rng.randn(1, P, 4).astype("f") * 0.1,
            "conf": rng.randn(1, P, 3).astype("f"),
            "pb": priors, "pb2": priors,
            "gt": np.array([[0.1, 0.1, 0.4, 0.4]], "f"),
            "gl": np.array([[1]], "int64"),
        }, fetch_list=[nms, loss])
    assert np.asarray(o).shape == (1, 4, 6)
    assert np.isfinite(float(np.asarray(l).ravel()[0]))


def test_chunk_eval_exact_span_and_exclusion():
    # inference chunk extends past the label chunk end -> NOT correct
    lab = np.array([[0, 2]], "int64")      # B, O  (1 chunk, len 1)
    inf = np.array([[0, 1]], "int64")      # B, I  (1 chunk, len 2)
    p, r, f1, ni, nl, nc = run_op("chunk_eval", jnp.asarray(inf),
                                  jnp.asarray(lab), num_chunk_types=1)
    assert int(nc) == 0 and float(p) == 0.0

    # excluded chunk type drops from all counts
    lab2 = np.array([[0, 1, 2]], "int64")
    inf2 = np.array([[0, 1, 2]], "int64")
    _, _, _, ni2, nl2, nc2 = run_op(
        "chunk_eval", jnp.asarray(inf2), jnp.asarray(lab2),
        num_chunk_types=1, excluded_chunk_types=[0])
    assert int(ni2) == 0 and int(nl2) == 0 and int(nc2) == 0


def test_trilinear_align_corners():
    x = np.arange(4, dtype="f").reshape(1, 1, 1, 1, 4)
    out = run_op("trilinear_interp", jnp.asarray(x), out_shape=[1, 1, 7],
                 align_corners=True)
    o = np.asarray(out).ravel()
    np.testing.assert_allclose(o, np.linspace(0, 3, 7), rtol=1e-5)


def test_beam_search_decoder_greedy_consistency():
    """Analytic check: with state-independent constant logits, every step's
    best continuation is the same argmax token, so the backtracked best
    beam must be that token repeated; greedy (K=1) must agree."""
    import paddle_tpu.layers.tensor as T
    from paddle_tpu.initializer import Constant

    V, H, B, Tmax = 6, 8, 2, 4
    bias_vals = np.array([0.1, 0.4, 0.2, 3.0, 0.3, 0.25], "f")  # argmax = 3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        init_h = fluid.layers.data("h0", shape=[H])
        cell = fluid.layers.GRUCell(H)

        def embed(ids):
            return fluid.layers.embedding(
                ids, (V, H), param_attr=fluid.ParamAttr(name="bsd_emb"))

        def out_fn(h):
            # zero weight + fixed per-class bias -> constant logits
            z = fluid.layers.fc(
                h, V, param_attr=fluid.ParamAttr(initializer=Constant(0.0),
                                                 name="bsd_zero_w"),
                bias_attr=False)
            bias_row = T.assign(bias_vals.reshape(1, V))
            return fluid.layers.elementwise_add(z, bias_row)

        def make(K):
            bsd = fluid.layers.BeamSearchDecoder(
                cell, start_token=1, end_token=0, beam_size=K,
                embedding_fn=embed, output_fn=out_fn)
            outs, st = fluid.layers.dynamic_decode(bsd, inits=init_h,
                                                   max_step_num=Tmax)
            return bsd.finalize(outs), st[-2]  # [..., logp, last_tok]

        seqs1, _ = make(1)
        seqs3, score3 = make(3)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        s1, s3, sc3 = exe.run(main, feed={"h0": rng.randn(B, H).astype("f")},
                              fetch_list=[seqs1, seqs3, score3])
    s1, s3 = np.asarray(s1), np.asarray(s3)
    sc3 = np.asarray(sc3).reshape(B, 3)
    assert s1.shape == (Tmax, B, 1) and s3.shape == (Tmax, B, 3)
    # greedy and beam-best must both be the argmax token (3) every step
    np.testing.assert_array_equal(s1[:, :, 0], np.full((Tmax, B), 3))
    np.testing.assert_array_equal(s3[:, :, 0], np.full((Tmax, B), 3))
    # best-beam score == Tmax * log_softmax(bias)[3]
    expect = Tmax * (bias_vals[3] - np.log(np.exp(bias_vals).sum()))
    np.testing.assert_allclose(sc3[:, 0], expect, rtol=1e-4)


def test_beam_search_decoder_finished_beam_semantics():
    """A beam that emits end_token must keep its score FROZEN and keep
    emitting end_token (the beam_search op's finished handling)."""
    import paddle_tpu.layers.tensor as T
    from paddle_tpu.initializer import Constant

    V, H, B, Tmax = 5, 4, 1, 4
    # argmax token IS the end token -> best beam finishes at step 1
    bias_vals = np.array([0.1, 5.0, 0.2, 0.3, 0.15], "f")  # argmax = 1
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        init_h = fluid.layers.data("h0", shape=[H])
        cell = fluid.layers.GRUCell(H)

        def embed(ids):
            return fluid.layers.embedding(
                ids, (V, H), param_attr=fluid.ParamAttr(name="fb_emb"))

        def out_fn(h):
            z = fluid.layers.fc(
                h, V, param_attr=fluid.ParamAttr(initializer=Constant(0.0),
                                                 name="fb_zero_w"),
                bias_attr=False)
            return fluid.layers.elementwise_add(z, T.assign(
                bias_vals.reshape(1, V)))

        bsd = fluid.layers.BeamSearchDecoder(
            cell, start_token=2, end_token=1, beam_size=2,
            embedding_fn=embed, output_fn=out_fn)
        outs, st = fluid.layers.dynamic_decode(bsd, inits=init_h,
                                               max_step_num=Tmax)
        seqs = bsd.finalize(outs)
        scores = st[-2]
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        s, sc = exe.run(main, feed={"h0": np.zeros((B, H), "f")},
                        fetch_list=[seqs, scores])
    s = np.asarray(s)          # [T, B, K]
    sc = np.asarray(sc).reshape(B, 2)
    logp = bias_vals - np.log(np.exp(bias_vals).sum())
    # best beam: end at step 0 with score logp[1], FROZEN thereafter
    assert s[0, 0, 0] == 1
    np.testing.assert_allclose(sc[0, 0], logp[1], rtol=1e-4)
    # after finishing, the beam emits only end_token
    assert (s[1:, 0, 0] == 1).all()
