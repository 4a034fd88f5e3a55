"""The decode step writes its K and V into the paged pool in place
(serving/decode_model.py, serving/kv_cache.py): no instruction of the
lowered step produces a copy or a transpose as large as a layer's pool, nor
a select as large as the gathered history, the compiled step aliases every pool onto its argument, and what it
needs beside its arguments stays far under the pool.  A small decoder with
a pool far larger than its activations, on the CPU tier: this guards the
property on the structure XLA is handed; the chip run proves what XLA:TPU
makes of it (PERF.md section 6, PR 26; tests/test_tpu_compile.py compiles
the real widths for the chip)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.serving import decode_model as dm
from paddle_tpu.serving.kv_cache import KVCacheConfig, PagedKVCache

CFG = dm.DecoderConfig(vocab=31, layers=2, heads=2, head_dim=8, max_seq=32)
BS, BLOCKS, LANES = 4, 512, 4
MAXB = CFG.max_seq // BS


def _step_and_feeds(kind, kv):
    lane = np.zeros(LANES, np.int32)
    wide = np.zeros((LANES, 2), np.int32)
    tables = np.full((LANES, MAXB), -1, np.int32)
    if kind == "step":
        return dm.make_paged_step(CFG, kv), (lane, lane, tables, lane)
    if kind == "multi":
        return (dm.make_paged_step_multi(CFG, kv, 2),
                (wide, wide, tables, wide))
    return (dm.make_draft_rollout(CFG, kv, 2),
            (lane, lane, tables, lane, lane))


def _largest_tensor(line):
    """Elements of the largest tensor an instruction's text names, in
    StableHLO (``tensor<512x4x16xf32>``) or HLO (``f32[512,4,16]``)."""
    shapes = re.findall(r"tensor<((?:\d+x)+)", line) \
        + re.findall(r"[a-z]\d+\[([\d,]+)\]", line)
    return max((int(np.prod([int(d) for d in re.findall(r"\d+", dims)]))
                for dims in shapes), default=0)


@pytest.mark.parametrize("dtype", ["f32", "int8"])
@pytest.mark.parametrize("kind", ["step", "multi", "rollout"])
def test_step_updates_the_pool_in_place(kind, dtype):
    kv = KVCacheConfig(CFG.layers, CFG.heads, CFG.head_dim, BS, BLOCKS,
                       dtype)
    cache = PagedKVCache(kv)
    params = {k: jnp.asarray(v)
              for k, v in dm.init_decoder_params(CFG).items()}
    fn, feeds = _step_and_feeds(kind, kv)
    lowered = jax.jit(fn, donate_argnums=(0,)).lower(
        cache.carry(), params, *feeds)
    compiled = lowered.compile()
    pool_elems = BLOCKS * BS * CFG.hidden
    history_elems = LANES * CFG.max_seq * CFG.hidden
    # the gathered history is far smaller than a pool, so a copy or a
    # transpose this large is a pass over a whole pool; a select as large
    # as the history is the gather's out-of-range fill come back
    assert history_elems * 4 <= pool_elems
    too_large = {"copy": pool_elems, "transpose": pool_elems,
                 "select": history_elems}
    for text, opcode in ((lowered.as_text(), r"stablehlo\.(\w+)"),
                         (compiled.as_text(),
                          r" = \S+ ([\w\-]+)\(")):
        for line in text.splitlines():
            op = re.search(opcode, line)
            if op and op.group(1) in too_large:
                assert _largest_tensor(line) < too_large[op.group(1)], \
                    line.strip()[:200]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= cache.nbytes
    assert memory.temp_size_in_bytes < cache.nbytes / 4
