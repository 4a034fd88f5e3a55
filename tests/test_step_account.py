"""``decode_model.StepAccount``: what a model's decode step takes and reads,
by kind of layer, beside the step and with no engine.  Every row of
``tests/decoder_families.py`` gives the key parts, the ``serving_prewarm``
attributes, the step span's read counts and the counters and gauges that
ride with them that the engine of PR 57 gave for it (``step_account_pins
.json``, written once by running that engine: at the rows' tiny sizes, where
every kind gathers, and at each family's published widths under the
interpreter, where the kernels engage), and ``engine.py`` names no kind of
layer."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.serving import decode_model as dm

BS = fam.BS
with open(os.path.join(os.path.dirname(__file__),
                       "step_account_pins.json")) as _fp:
    PINS = json.load(_fp)


def _plain(value):
    """As JSON holds it: tuples are lists, a bucket is its digits."""
    return json.loads(json.dumps(value))


def _account(cfg, params, block, blocks, buckets, laid=()):
    held = cfg.recurrent_layers or cfg.window_layers
    kv = dm.cache_config(cfg, block, blocks, cfg.kv_dtype or "f32",
                         state_slots=max(buckets) + 1 if held else 0)
    return dm.StepAccount(cfg, kv, params, buckets, model="m", laid=laid)


def _tiny(row, key, buckets):
    cfg, params = row.configs[key]
    held = dm.laid_out(cfg, params)
    return cfg, _account(cfg, held, BS, 40, buckets,
                         laid=[k for k in held if k not in params])


def _published(row, buckets):
    """The family's benchmark configuration at its cell's pool, the weights
    as shapes alone."""
    name, blocks, _chunk = row.chunk
    with open(fam.config_file(name)) as fp:
        config = json.load(fp)
    config.pop("tiny", None)
    cfg = fam.load("benchmark", "models", config["model"] + ".py") \
        .decoder_config(config)
    shapes = getattr(dm._model(cfg.arch), "param_shapes", lambda cfg: {})(cfg)
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[cfg.dtype]
    return cfg, _account(cfg, {k: jax.ShapeDtypeStruct(shape, dtype)
                               for k, (shape, _kind) in shapes.items()},
                         16, blocks, buckets)


def _lens(cfg, lanes):
    """Two steps' context lengths, idle lanes (0) among them: contexts
    under 300 positions, and contexts up to the model's longest."""
    if lanes == 4:
        return {"a": [5, 0, 12, 1], "b": [cfg.max_seq - 8, 9, 33, 17]}
    rng = np.random.RandomState(11)
    short, long = rng.randint(1, 300, 32), rng.randint(1, cfg.max_seq, 32)
    short[[3, 17, 30]] = 0
    long[5], long[6] = 0, cfg.max_seq
    return {"a": short, "b": long}


def _extras(cfg):
    """A routed step's counts as it returns them: tokens an output of the
    router a layer (an expert, or behind the experts an identity expert),
    every third without one, and the lanes that kept each group or, of a
    router with identity experts, the lanes that chose so many real
    experts."""
    rng = np.random.RandomState(5)
    counts = rng.randint(0, 4, (len(cfg.routed_layers), cfg.router_width))
    counts[:, ::3] = 0
    more = [rng.randint(0, 5, (len(cfg.routed_layers), cfg.n_group))
            .astype(np.int32)] if cfg.n_group > 1 else []
    if cfg.zero_experts:
        real = rng.randint(0, 3, (len(cfg.routed_layers),
                                  cfg.experts_per_token + 1))
        real[:, 0] = 0
        more.append(real.astype(np.int32))
    return [counts.astype(np.int32)] + more


def _telemetry(prefixes):
    snap = _tm.snapshot()
    return {kind: {k: v for k, v in snap[kind].items()
                   if k.startswith(prefixes)}
            for kind in ("counters", "gauges")}


def _check_against_the_parent(cfg, account, pins, buckets):
    lanes = max(buckets)
    lens = {name: np.asarray(v, np.int32)
            for name, v in _lens(cfg, lanes).items()}
    assert _plain(account.key_parts) == pins["key_parts"]
    assert account.pool_bytes() == pins["pool_bytes"]
    assert {str(b): _plain(account.prewarm_attrs(b)) for b in buckets} \
        == pins["prewarm"]
    steps = {name + ("%d" % b if lanes == 32 else ""):
             account.step_attrs(b, v[:b])
             for name, v in lens.items()
             for b in (buckets if lanes == 32 else [lanes])}
    assert steps == pins["step"]
    if cfg.window_layers:
        _tm.reset()
        assert {name: account.window_attrs(v, 2, *pins["window_in_use"])
                for name, v in lens.items()} == pins["window"]
        assert _telemetry(("kv_window", "kv_pool_blocks")) \
            == pins["window_telemetry"]
    if cfg.routed_layers:
        _tm.reset()
        assert {str(b): account.moe_attrs(b, _extras(cfg))
                for b in buckets} == pins["moe"]
        assert _telemetry(("moe_",)) == pins["moe_telemetry"]
    assert set(pins) <= {"key_parts", "pool_bytes", "prewarm", "step",
                         "window", "window_in_use", "window_telemetry",
                         "moe", "moe_telemetry"}


@pytest.mark.parametrize("row,key", fam.cases())
def test_the_account_says_what_the_engine_said(row, key, cache_dir,
                                               telemetry_on):
    """The account of a row's configuration, built with no engine: (a) every
    one of its key parts is in the key of the step an engine of the same row
    holds, and the engine's entry shows the account's paths; (b) key parts,
    pool gauges, prewarm attributes at two buckets, the span's reads at two
    steps' lengths, the window layers' and the routed layers' attributes and
    what they count are the parent's; (c) a kind the model has no layer of
    adds no key."""
    cfg, account = _tiny(row, key, (2, 4))
    _check_against_the_parent(cfg, account, PINS["%s-%s" % (row.arch, key)],
                              (2, 4))
    e = fam.engine(*row.configs[key], 40, start=False)
    m = e._models["m"]
    _cfg, alone = _tiny(row, key, e.buckets)
    assert alone.kv_config.num_blocks == m.kv_config.num_blocks
    held = m.stepfn._key_parts
    assert alone.key_parts and all(
        held[part] == path for part, path in alone.key_parts.items())
    assert set(held) == set(alone.key_parts) | {"kind", "model", "cfg", "kv"}
    assert (m.attn_path, m.window_path, m.experts_path, m.state_path) \
        == (alone.attn_path, alone.window_path, alone.experts_path,
            alone.state_path)
    said = set(account.prewarm_attrs(4)) \
        | set(account.step_attrs(4, np.asarray([3, 0, 9, 1], np.int32)))
    absent = {"window": not cfg.window_layers,
              "experts": not cfg.routed_layers,
              "state": not cfg.recurrent_layers,
              "window_update": not cfg.recurrent_layers,
              "latent": not cfg.latent_layers,
              "index": not cfg.index_topk, "sparse": not cfg.index_topk,
              "layers": len(set(cfg.layer_types)) == 1}
    # (``window_update`` is a recurrent layer's convolution window, not a
    # window layer's ring)
    for word in (w for w, gone in absent.items() if gone):
        assert not [k for k in said | set(account.key_parts) if word in k
                    and (word, k) != ("window", "window_update")], word
    if not cfg.routed_layers:
        assert account.experts_path == {} \
            and account.moe_attrs(4, None) == {} == account.moe_attrs(4, [])
    if not cfg.window_layers:
        assert account.window_path is None


@pytest.mark.parametrize("row", [pytest.param(r, id=r.arch)
                                 for r in fam.ROWS.values()])
def test_the_account_at_published_widths_where_the_kernels_engage(
        row, interpreted, telemetry_on):
    """The same at the family's benchmark configuration, 8 and 32 lanes over
    its cell's pool, told that the backend takes the kernels: every kind's
    ``"pallas"`` form, a selecting model's masked walk, the chunk spans, the
    expert and state-update kernels' chunks, a share of a router's experts
    and a router with groups."""
    cfg, account = _published(row, (8, 32))
    pins = PINS["%s-published" % row.arch]
    assert "pallas" in json.dumps(pins["key_parts"])
    _check_against_the_parent(cfg, account, pins, (8, 32))


def test_the_engine_names_no_kind_of_layer():
    """``engine.py`` keeps lanes, blocks, rings and slots: which path a kind
    of layer takes and what a step read of it are the account's.  The nine
    calls and ten configuration fields it held before PR 58 stay out, so the
    next family lands without a line there."""
    with open(os.path.join(fam.ROOT, "paddle_tpu", "serving",
                           "engine.py")) as fp:
        source = fp.read()
    calls = ["_dm.attention_path", "_dm.experts_path",
             "_dm.state_update_path", "_dm.chunk_positions",
             "_dm.experts_chunk", "_dm.experts_gate",
             "_dm.state_update_columns", "_pa.blocks_read", "_pa.chunks_read"]
    fields = ["latent_layers", "index_topk", "routed_layers", "state_layers",
              "layer_types", "window", "held_experts", "n_group", "experts",
              "experts_per_token"]
    assert [c for c in calls if c in source] == []
    assert [f for f in fields
            if re.search(r"cfg\.%s\b" % f, source)] == []
    assert "pallas_kernels" not in source
