"""The check a refactor of ``paddle_tpu/serving/`` or ``paddle_tpu/models/``
proves itself by: that every decoder family's step is, as text, the step it
was.  Run once in a ``git archive`` of the parent and once in the change,
from the root of each tree, and compare the two outputs (JSON on stdout, a
case a line):

    python tools/step_text_hash.py > /root/scratch/change.json
    (cd <parent's tree> && python tools/step_text_hash.py) > parent.json
    diff parent.json change.json          # empty: no program moved

For every row and configuration of ``tests/decoder_families.py``:

- ``steps``: the sha256 head of ``jax.jit(make_packed_step(...)).lower(...)
  .as_text()`` at the tests' sizes (block 4: on the CPU every kind gathers).
  ``as_text()`` carries no source locations, so two trees whose code moved
  and whose program did not give the same head.
- ``steps_interpreted``: the same at block 16 under
  ``PADDLE_PALLAS_INTERPRET=1``, where a row's kernels engage as far as its
  tiny widths let them.
- ``key_parts``: the row's ``StepAccount.key_parts`` as sorted JSON: what a
  compiled step is keyed by beside its configuration, so that a restored
  executable is still found.

``--tpu-kernels`` adds ``tpu_kernels``: the heads of the attention kernels'
Mosaic modules, lowered for a described v5e at the served cells' shapes (it
needs ``jax.experimental.topologies`` and loads the TPU's compiler, which
one process at a time may do)."""

import argparse
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LANES, BLOCKS = 2, 12


def head(text):
    return hashlib.sha256(text.encode()).hexdigest()[:8]


def step_and_key(cfg, params, block):
    """-> (the lowered packed step's head, the account's key parts) of one
    configuration at ``LANES`` lanes over ``BLOCKS`` blocks of ``block``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving.kv_cache import PagedKVCache

    shapes = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    kv = dm.cache_config(cfg, block, BLOCKS, state_slots=LANES + 2)
    carry = jax.eval_shape(lambda: PagedKVCache(kv).carry())
    held = jax.eval_shape(lambda p: dm.laid_out(cfg, p), shapes(
        {k: jnp.asarray(v) for k, v in params.items()}))
    width = dm.lane_columns(kv, -(-cfg.max_seq // block))[1]
    text = jax.jit(dm.make_packed_step(cfg, kv, LANES), donate_argnums=(0,)
                   ).lower(carry, held,
                           jax.ShapeDtypeStruct((LANES,), jnp.int32),
                           jax.ShapeDtypeStruct((LANES, width), jnp.int32)
                           ).as_text()
    account = dm.StepAccount(cfg, kv, held, (LANES,),
                             laid=[k for k in held if k not in params])
    return head(text), account.key_parts


def rows(block):
    import decoder_families as fam

    steps, keys = {}, {}
    for row in fam.ROWS.values():
        for key, (cfg, params) in row.configs.items():
            case = "%s-%s" % (row.arch, key)
            steps[case], keys[case] = step_and_key(cfg, params, block)
    return steps, keys


# name -> (heads, head_dim, a pool row's width, the pool's dtype, blocks,
# table slots, window) of the cells' K/V attention layers, and name -> heads
# of their latent ones (rows of 640 bfloat16, 12,832 blocks, 802 slots)
KV_CELLS = {"gpt2": (16, 64, 1024, "float32", 1024, 64, None),
            "olmoe": (16, 128, 2048, "bfloat16", 2048, 128, None),
            "granite": (40, 64, 512, "bfloat16", 2048, 128, None),
            "lfm2": (32, 64, 512, "bfloat16", 2048, 128, None),
            "exaone_global": (64, 128, 1024, "bfloat16", 12832, 401, None),
            "exaone_window": (64, 128, 1024, "bfloat16", 400, 9, 128),
            "nemotron": (32, 128, 256, "bfloat16", 2048, 128, None),
            "smallthinker_global": (28, 128, 512, "bfloat16", 12832, 1026,
                                    None),
            "smallthinker_window": (28, 128, 512, "bfloat16", 9000, 257,
                                    4096)}
LATENT_CELLS = {"kimi_latent": 32, "dots_latent": 128}


def tpu_kernels():
    """The attention kernels, and the kernel of a mixing's maps, lowered FOR
    THE TPU: the head of Mosaic's
    module in each custom call, printed without source locations (the call
    holds it as bytecode with the files' paths and lines, which differ
    between two trees)."""
    import jax
    import jax.numpy as jnp
    from jax._src.pallas.mosaic import pallas_call_registration as reg
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.pallas_kernels import paged_attention as pa

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)
    modules = []
    lower = reg.mosaic.lower_module_to_custom_call

    def noted(*a, module, **kw):
        modules.append(module.operation.get_asm(enable_debug_info=False))
        return lower(*a, module=module, **kw)

    def kernel_head(fn, *args):
        text = jax.jit(fn).lower(*args).as_text()
        if "tpu_custom_call" not in text or len(modules) != 1:
            raise RuntimeError("no one kernel in the lowered call")
        return head(modules.pop())

    # the kernels ask the backend, which here is the CPU: answer as the
    # chip would for as long as they are lowered
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    reg.mosaic.lower_module_to_custom_call = noted
    f32, i32 = jnp.float32, jnp.int32
    out = {}
    try:
        for name, (h, d, w, dt, blocks, maxb, window) in KV_CELLS.items():
            out[name] = kernel_head(
                lambda q, k, v, t, l, window=window: pa.paged_attention(
                    q, k, v, t, l, window=window),
                spec((32, h, d), f32), spec((blocks, 16, w), dt),
                spec((blocks, 16, w), dt), spec((32, maxb), i32),
                spec((32,), i32))
        for name, h in LATENT_CELLS.items():
            out[name] = kernel_head(
                lambda q, p, t, l: pa.latent_attention(q, p, t, l, 0.1, 512),
                spec((32, h, 640), f32), spec((12832, 16, 640), jnp.bfloat16),
                spec((32, 802), i32), spec((32,), i32))
        # a mixing's maps at Xing4.0's cell: 4 streams of 3,584, 32 lanes
        import types

        from paddle_tpu.models import hyper_connections as hc

        mixing = types.SimpleNamespace(
            hc_mult=4, hidden=3584, hc_sinkhorn_iters=20, hc_eps=1e-6,
            norm_eps=1e-6, hc_clamp=(-30.0, 30.0))
        out["xing_hc_maps"] = kernel_head(
            lambda phi, b, a, x: hc.maps(mixing, phi, b, a, x),
            spec((4 * 3584, 24), f32), spec((24,), f32), spec((3,), f32),
            spec((32, 4, 3584), f32))
    finally:
        jax.default_backend = backend
        reg.mosaic.lower_module_to_custom_call = lower
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tpu-kernels", action="store_true",
                        help="also the attention kernels' Mosaic modules, "
                        "lowered for a described v5e")
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the tree this file lies in, whatever is installed
    sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]
    out = {}
    out["steps"], out["key_parts"] = rows(4)
    os.environ["PADDLE_PALLAS_INTERPRET"] = "1"
    try:
        out["steps_interpreted"], out["key_parts_interpreted"] = rows(16)
    finally:
        del os.environ["PADDLE_PALLAS_INTERPRET"]
    if args.tpu_kernels:
        out["tpu_kernels"] = tpu_kernels()
    # a case a line, so that ``diff`` names the case that moved
    print("{\n%s\n}" % ",\n".join(
        ' "%s": {\n%s\n }' % (section, ",\n".join(
            "  %s: %s" % (json.dumps(case), json.dumps(said, sort_keys=True))
            for case, said in sorted(cases.items())))
        for section, cases in sorted(out.items())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
