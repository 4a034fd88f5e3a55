"""Mesh pipeline parallelism: GPipe over a named mesh axis.

TPU-native replacement for the reference's multi-device pipeline
(framework/pipeline_trainer.cc:24 places sections on distinct devices;
section_worker.cc:141 passes scopes stage->stage through queues).  Here the
"queue" is the ICI: parameters are stage-sharded over a `pp` mesh axis
(stage i's weights live only on pipe-rank-i devices), every device runs the
same SPMD program under shard_map, and activations move stage->stage with
`lax.ppermute` on the classic skewed microbatch schedule:

    tick t:  stage 0 ingests microbatch t; stage s computes the activation
             it received at tick t-1; the last stage emits microbatch
             t-(S-1); then every activation rotates one hop.

The backward pass is NOT hand-scheduled: `jax.grad` through the scan
transposes each ppermute into the reverse rotation, which IS the GPipe
backward schedule (all-forward then all-backward, activations stashed by
the scan) — the compiler owns the bubble, matching how XLA owns collective
scheduling everywhere else in this framework.

Contract: all inter-stage activations share one shape [mb, ...] (the
transformer-block case); embedding/head stay outside the loop via
`embed_fn`/`loss_fn`.  Parameters are passed STACKED with a leading stage
axis sharded over `axis` — `stack_stage_params` builds that layout.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage, mesh=None, axis="pp"):
    """[{name: array} per stage] -> {name: [S, ...] array}, placed so the
    stage axis is sharded over the mesh `axis` (each pipe rank holds only
    its own stage's weights)."""
    names = per_stage[0].keys()
    for p in per_stage[1:]:
        if p.keys() != names:
            raise ValueError("stages must share a parameter structure")
    stacked = {n: jnp.stack([jnp.asarray(p[n]) for p in per_stage])
               for n in names}
    if mesh is not None:
        stacked = {
            n: jax.device_put(
                v, NamedSharding(mesh, P(axis, *([None] * (v.ndim - 1)))))
            for n, v in stacked.items()}
    return stacked


def _unstack_local(params):
    """Inside shard_map each pipe rank sees leading stage dim 1."""
    return jax.tree_util.tree_map(lambda v: v[0], params)


def gpipe_spmd(stage_fn, n_stages, n_micro, axis="pp"):
    """Build the SPMD pipeline body (to run under shard_map over `axis`).

    stage_fn(params, h) -> h' applies ONE stage; params is the rank-local
    (unstacked) parameter pytree.  Returns f(params_local, x_micro) ->
    [n_micro, ...] outputs, valid on the LAST pipe rank (garbage
    elsewhere — mask or psum what you consume)."""
    if n_micro < 1:
        raise ValueError("n_micro must be >= 1")

    def forward(params_local, x_micro):
        p = _unstack_local(params_local)
        stage = lax.axis_index(axis)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        buf = jnp.zeros_like(x_micro[0])
        outs = jnp.zeros_like(x_micro)

        def tick(carry, t):
            buf, outs = carry
            idx = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(stage == 0, x_micro[idx], buf)
            y = stage_fn(p, inp)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = (t >= n_stages - 1) & (stage == n_stages - 1)
            outs = outs.at[out_idx].set(
                jnp.where(write, y, outs[out_idx]))
            buf = lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = lax.scan(
            tick, (buf, outs), jnp.arange(n_micro + n_stages - 1))
        return outs

    return forward


def make_pipeline_step(stage_fn, loss_fn, mesh, n_micro, axis="pp",
                       optimizer=None, embed_fn=None, n_chunks=1,
                       data_axis=None, reduce_grad_axes=()):
    """Jitted stage-sharded GPipe train step.

    stage_fn(params, h) -> h'      one stage (params = that stage's slice)
    loss_fn(outs, labels) -> scalar   computed on last-stage outputs
    embed_fn(x) -> h               optional replicated pre-pipeline embed
    optimizer(p, g) -> p'          optional sgd-style update per leaf
    data_axis                      optional SECOND mesh axis for composed
        data x pipeline parallelism (a real pod job's topology): the
        microbatch dim is sharded over it, params stay replicated across
        it, and gradients/loss are pmean'd over it — loss_fn must be a
        mean over its microbatch outputs so shard means average exactly.

    n_chunks > 1 bounds activation memory: the n_micro microbatches run
    as n_chunks sequential GPipe passes of n_micro/n_chunks each, with
    gradients accumulated between passes (lax.scan) — the jax.grad stash
    holds one CHUNK's activations instead of the whole batch's, at the
    cost of (n_chunks-1) extra pipeline fills.  loss_fn must be a MEAN
    over its microbatch outputs (chunk means are averaged).

    Returns step(params_stacked, x, labels) -> (loss, params_or_grads):
    x [B, ...] is split into n_micro microbatches; loss is replicated; the
    second output is updated params when `optimizer` is given, else grads
    (stage-sharded like the input params).
    """
    n_stages = mesh.shape[axis]
    if n_chunks < 1:
        raise ValueError("n_chunks must be >= 1, got %d" % n_chunks)
    if n_micro % n_chunks:
        raise ValueError("n_micro %d not divisible by n_chunks %d"
                         % (n_micro, n_chunks))
    micro_per_chunk = n_micro // n_chunks
    fwd = gpipe_spmd(stage_fn, n_stages, micro_per_chunk, axis)

    def loss_spmd(params_local, x_micro, labels_micro):
        outs = fwd(params_local, x_micro)
        stage = lax.axis_index(axis)
        raw = loss_fn(outs, labels_micro)
        # LOCAL masked loss (real only on the last pipe rank).  No psum
        # here: under shard_map(check_vma=False) psum transposes to psum,
        # which would scale every cotangent seed by n_stages.  Cross-rank
        # gradient flow still happens through the ppermute transposes —
        # rank s's grads answer d(last rank's loss)/d(stage-s params).
        return jnp.where(stage == n_stages - 1, raw, 0.0)

    def spmd_body(params_local, x_micro, labels_micro):
        if n_chunks == 1:
            loss_local, grads = jax.value_and_grad(loss_spmd)(
                params_local, x_micro, labels_micro)
        else:
            xc = x_micro.reshape((n_chunks, micro_per_chunk)
                                 + x_micro.shape[1:])
            yc = labels_micro.reshape((n_chunks, micro_per_chunk)
                                      + labels_micro.shape[1:])

            def chunk(carry, xy):
                l_acc, g_acc = carry
                l, g = jax.value_and_grad(loss_spmd)(
                    params_local, xy[0], xy[1])
                return (l_acc + l, jax.tree_util.tree_map(
                    jnp.add, g_acc, g)), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params_local)
            # derive the accumulator dtype from the actual loss (a
            # hardcoded f32 init would break the scan carry contract
            # under x64 / f64 losses)
            loss_shape = jax.eval_shape(
                loss_spmd, params_local, xc[0], yc[0])
            (loss_sum, grads_sum), _ = lax.scan(
                chunk, (jnp.zeros((), loss_shape.dtype), zeros), (xc, yc))
            # loss_fn is a mean per chunk: average the chunk means/grads
            loss_local = loss_sum / n_chunks
            grads = jax.tree_util.tree_map(lambda g: g / n_chunks,
                                           grads_sum)
        # replicate the loss for reporting OUTSIDE the differentiated path
        loss = lax.psum(lax.stop_gradient(loss_local), axis)
        if data_axis is not None:
            # composed dp: every data shard ran the full pipeline on its
            # slice of each microbatch; average across the data axis
            # (outside the differentiated path, like the loss psum above)
            grads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, data_axis), grads)
            loss = lax.pmean(loss, data_axis)
        for ax in reduce_grad_axes:
            # composed tp inside a stage (3-axis dp x tp x pp): each
            # model rank holds its shard's scatter of the param grads,
            # and because the stage's activations/cotangents are
            # replicated over the axis, every covered element carries an
            # extra axis-size factor (the collective's transpose sums
            # identical per-rank cotangents).  pmean both combines the
            # disjoint shards and cancels that factor — EXACT for stage
            # fns whose params are all consumed in sliced form BEFORE the
            # output collective (column-parallel w AND b, like
            # tests/test_composed_parallelism.py test_three_axis_mesh)
            grads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, ax), grads)
        if optimizer is not None:
            new_params = jax.tree_util.tree_map(optimizer, params_local,
                                                grads)
            return loss, new_params
        return loss, grads

    def step(params_stacked, x, labels):
        for path, v in jax.tree_util.tree_flatten_with_path(
                params_stacked)[0]:
            if v.shape[0] != n_stages:
                # a mismatch would not error downstream: shard_map hands
                # each rank a multi-stage slice and _unstack_local keeps
                # only slice 0, silently training a smaller model
                raise ValueError(
                    "stacked param %s has %d stages but mesh axis %r has "
                    "%d devices" % (jax.tree_util.keystr(path), v.shape[0],
                                    axis, n_stages))
        B = x.shape[0]
        if B % n_micro:
            raise ValueError("batch %d not divisible by n_micro %d"
                             % (B, n_micro))
        mb = B // n_micro
        if data_axis is not None and mb % mesh.shape[data_axis]:
            raise ValueError(
                "microbatch size %d not divisible by data axis %r size %d"
                % (mb, data_axis, mesh.shape[data_axis]))
        x_micro = x.reshape((n_micro, mb) + x.shape[1:])
        if embed_fn is not None:
            x_micro = jax.vmap(embed_fn)(x_micro)
        labels_micro = labels.reshape((n_micro, mb) + labels.shape[1:])
        pspec = jax.tree_util.tree_map(
            lambda v: P(axis, *([None] * (v.ndim - 1))), params_stacked)
        # composed dp x pp: shard the within-microbatch dim over data_axis
        xspec = P(None, data_axis) if data_axis is not None else P()
        body = jax.shard_map(
            spmd_body, mesh=mesh,
            in_specs=(pspec, xspec, xspec),
            out_specs=(P(), pspec), check_vma=False)
        return body(params_stacked, x_micro, labels_micro)

    return jax.jit(step)


def reference_step(stage_fn, loss_fn, per_stage_params, x, labels,
                   n_micro=1, embed_fn=None):
    """Single-device sequential semantics of the same pipeline (parity
    oracle for tests): run stages back-to-back per microbatch."""
    B = x.shape[0]
    mb = B // n_micro
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])
    if embed_fn is not None:
        x_micro = jax.vmap(embed_fn)(x_micro)
    labels_micro = labels.reshape((n_micro, mb) + labels.shape[1:])

    def full(per_stage):
        outs = []
        for m in range(n_micro):
            h = x_micro[m]
            for p in per_stage:
                h = stage_fn(p, h)
            outs.append(h)
        return loss_fn(jnp.stack(outs), labels_micro)

    loss, grads = jax.value_and_grad(full)(list(per_stage_params))
    return loss, grads
