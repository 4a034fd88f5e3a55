"""A mixing's maps as one kernel (paddle_tpu/pallas_kernels/hc_maps.py, chosen
by ``hyper_connections.maps``): the kernel under the interpreter against the
jnp form over lanes, streams and iterations, the clamp reached and an idle
lane of zeros among them; ``H_res`` doubly stochastic to what its iterations
leave; a step of one lane or two; the call traced once a
set of shapes and keyed by what a test swaps; what ``StepAccount``, the
prewarm event and the step's span say of the path; the paged step on this
kernel against the step on the jnp form.  The shape rule's reasons are cases
of tests/test_kernel_rule_and_block_ops.py; the whole step compiled for a
described v5e is tests/test_tpu_compile.py's."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

import decoder_families as fam
from paddle_tpu.core import telemetry as _tm
from paddle_tpu.core import tracing as _trc
from paddle_tpu.models import hyper_connections as hc
from paddle_tpu.models import xing4 as xg
from paddle_tpu.pallas_kernels import adoption, hc_maps
from paddle_tpu.serving import decode_model as dm

HIDDEN = 128


def _mixing(lanes, n, iters, seed=0, hidden=HIDDEN):
    """A seeded mixing at ``n`` streams of ``hidden``: (cfg, phi as
    published, b with one ``b_res`` entry at 100 and one at -100, a, X with
    lane 1 idle: all zeros)."""
    cfg = types.SimpleNamespace(
        hc_mult=n, hidden=hidden, hc_sinkhorn_iters=iters, hc_eps=1e-6,
        norm_eps=1e-6, hc_clamp=(-30.0, 30.0))
    r = np.random.RandomState([seed, lanes, n, iters])
    width = hc.width(n)
    phi = (r.standard_normal((n * hidden, width)) * 0.1).astype(np.float32)
    b = xg.hc_draw(r, n, (width,), "hc_b", 0.1)
    b[2 * n + 1], b[-2] = 100.0, -100.0
    a = np.asarray(xg.A_INIT, np.float32)
    X = r.standard_normal((lanes, n, hidden)).astype(np.float32)
    X[1] = 0.0
    return cfg, jnp.asarray(phi), jnp.asarray(b), jnp.asarray(a), \
        jnp.asarray(X)


@pytest.mark.parametrize("iters", [1, 20])
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("lanes", [8, 32, 64])
def test_the_kernel_makes_the_jnp_forms_maps(interpreted, lanes, n, iters):
    """The three maps of the kernel are ``maps_reference``'s within float32
    rounding, a ``b_res`` entry of 100 clipped before the exponential and an
    idle lane of zeros finite (``norm_eps`` under the root, ``hc_eps`` in
    every sum); seeded plainly, ``H_res``'s columns sum to 1 to ``hc_eps``
    and its rows to what the iterations leave."""
    cfg, phi, b, a, X = _mixing(lanes, n, iters)
    assert hc.maps_path(cfg, lanes) == "pallas"
    want = hc.maps_reference(cfg, phi, b, a, X)
    got = hc.maps(cfg, phi, b, a, X)
    assert adoption.active_kernels() == ["hc_maps"]
    assert [g.shape for g in got] == [(lanes, n), (lanes, n), (lanes, n, n)]
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, atol=2e-6, rtol=2e-5)
    # seeded plainly (no entry at the clamp), the residual map is doubly
    # stochastic as tests/test_xing4.py asks of the jnp form's: columns to
    # ``hc_eps``, rows to what the iterations leave
    res = np.asarray(hc.maps(cfg, phi, b.at[2 * n + 1].set(0.3)
                             .at[-2].set(-0.2), a, X)[2])
    assert (res >= 0).all()
    assert np.abs(res.sum(axis=1) - 1).max() < 1e-5
    if iters == 20:
        assert np.abs(res.sum(axis=2) - 1).max() < 5e-3
    else:
        assert np.abs(res.sum(axis=2) - 1).max() > 0.01


@pytest.mark.parametrize("lanes", [1, 2])
def test_the_kernel_serves_a_step_of_one_or_two_lanes(interpreted, lanes):
    """The smallest buckets an engine compiles (a lane, a pair): the same
    maps as the jnp form's, no lane padded in by the caller."""
    cfg, phi, b, a, X = _mixing(4, 4, 20, seed=3)
    X = X[2:2 + lanes]
    assert hc.maps_path(cfg, lanes) == "pallas"
    got = hc.maps(cfg, phi, b, a, X)
    assert adoption.active_kernels() == ["hc_maps"]
    for g, w in zip(got, hc.maps_reference(cfg, phi, b, a, X)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=2e-6, rtol=2e-5)


def test_the_call_is_traced_once_and_keyed_by_what_a_test_swaps(
        interpreted, monkeypatch):
    """A model's mixings call one traced kernel a set of shapes
    (``_maps_call``, as ``paged_attention._latent_call``); the kernel and
    its normalisation are in the key, so one swapped in the module is
    another call and not a stale one."""
    hc_maps._maps_call.cache_clear()
    cfg, phi, b, a, X = _mixing(8, 4, 20, seed=5)
    first = hc.maps(cfg, phi, b, a, X)
    for _ in range(3):
        hc.maps(cfg, phi * 2.0, b, a, X)
    info = hc_maps._maps_call.cache_info()
    assert (info.misses, info.hits) == (1, 3)
    monkeypatch.setattr(hc_maps, "_sinkhorn", lambda rows, iters, eps: rows)
    raw = hc.maps(cfg, phi, b, a, X)
    assert hc_maps._maps_call.cache_info().misses == 2
    np.testing.assert_array_equal(raw[0], first[0])
    assert np.abs(np.asarray(raw[2]).sum(axis=1) - 1).max() > 0.5
    hc_maps._maps_call.cache_clear()


# what serves below: 4 streams of 128 round 4 query heads of 128 + 32 rotated
# over 96 latent values and experts of width 128, as tests/test_xing4.py's
# step on two kernels
def _served_cfg():
    return dm.DecoderConfig(
        arch="xing4", vocab=61, layers=3, heads=4, head_dim=128,
        hidden_size=HIDDEN, max_seq=64, layer_types=("latent",) * 3,
        latent_rank=128, latent_rope=32, q_rank=64, rope_scaling=fam.YARN,
        dense_layers=1, dense_ffn=64, ffn=128, shared_ffn=64, experts=16,
        experts_held=8, experts_per_token=3, routed_scaling=2.0,
        norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp=(-30, 30))


def test_the_paged_step_on_the_maps_kernel_gives_the_jnp_forms_tokens(
        interpreted, monkeypatch):
    """The whole step with its mixings' maps on the kernel against the same
    step with ``hyper_connections.maps`` swapped for the jnp form (the
    probe's ``--hc-maps xla``), the other two kernels interpreted in both:
    the tokens and logits agree."""
    cfg = _served_cfg()
    params = xg.init_params(cfg, seed=5, std=0.1, bias_std=0.05)
    assert hc.maps_path(cfg, 2) == "pallas"

    def run():
        ((fed, logits), _idle), _routed = fam.run_paged(
            cfg, params, [([7], 20), ([], 0)], blocks=12, block_size=16)
        return fed, logits

    on_kernel = run()
    assert "hc_maps" in adoption.active_kernels()
    adoption.reset()
    monkeypatch.setattr(hc, "maps", hc.maps_reference)
    plain = run()
    assert set(adoption.active_kernels()) == {"latent_attention",
                                              "moe_experts"}
    assert on_kernel[0] == plain[0]
    np.testing.assert_allclose(on_kernel[1], plain[1], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_the_account_the_prewarm_event_and_the_span_name_the_path(
        cache_dir, telemetry_on, tmp_path, monkeypatch, path):
    """``StepAccount`` says how the mixings' maps are made a bucket: in the
    compiled step's key (an executable compiled for one path is never
    restored for the other), on the ``serving_prewarm`` event and on every
    step's span (``hc_maps_path``: the counter that says the mechanism
    engaged), ``phi`` held as published on both; a family of one stream says
    none of it."""
    if path == "pallas":
        monkeypatch.setenv("PADDLE_PALLAS_INTERPRET", "1")
        adoption.reset()
    cfg = _served_cfg()
    params = xg.init_params(cfg, seed=5, std=0.1, bias_std=0.05)
    with fam.flags(tracing=True, telemetry_dir=str(tmp_path)):
        e = fam.engine(cfg, params, 12, buckets="2", name="xg")
        try:
            e.prewarm()
            r = e.generate("xg", [1, 2, 3], max_new_tokens=6,
                           deadline_ms=60000.0)
            assert r.status == "ok"
            entry = e._models["xg"]
            held = entry.stepfn._key_parts
        finally:
            e.stop()
        _trc.flush()
        _tm.flush()
    assert entry.account.hc_maps_path == {2: path}
    assert held["hc_maps"] == [(2, path)]
    # ``phi`` is held as published on either path (the kernel's call turns
    # it, a bitcast on the chip): ``wkvb`` alone is laid out at load
    assert held["weights_laid_out"] == sorted(
        "l%d_wkvb_%s" % (l, kv) for l in range(3) for kv in "kv")
    assert all("l%d_hc_%s_phi" % (l, sub) in entry.params
               for l in range(3) for sub in xg.SUBLAYERS)
    warm = fam.prewarm_events(tmp_path)
    assert warm and all(ev["hc_maps_path"] == path for ev in warm)
    steps = fam.step_spans(tmp_path, "xg")
    assert len(steps) >= 6 and all(s["hc_maps_path"] == path for s in steps)
    assert ("hc_maps" in adoption.active_kernels()) == (path == "pallas")
    other = fam.ROWS["dots_vlm"].f32
    account = dm.StepAccount(other[0], dm.cache_config(other[0], fam.BS, 24),
                             dm.laid_out(*other), (2,))
    assert account.hc_maps_path == {} and "hc_maps" not in account.key_parts
    assert hc.maps_path(other[0], 2) is None


def test_the_probe_lists_one_layers_mixings_an_operation_at_a_time():
    """``decode_step_probe.hc_ops``: the operations under one layer's
    ``hc`` scopes by the profile's seconds, dearest first in microseconds an
    execution, the others past ``top`` counted and summed; another layer's
    and another scope's operations stay out."""
    probe = fam.load("tools", "decode_step_probe.py")
    index = {"hc_maps.3": ("custom-call", "f32[24,32]{1,0}",
                           "jit(step)/layer2/hc/attn_maps/hc_maps"),
             "fusion.7": ("fusion", "f32[32,4]{0,1}",
                          "jit(step)/layer2/hc/attn_maps/slice"),
             "fusion.8": ("fusion", "f32[32,4,3584]{2,0,1}",
                          "jit(step)/layer2/hc/mlp_merge/add"),
             "fusion.9": ("fusion", "f32[32,4,3584]{2,0,1}",
                          "jit(step)/layer3/hc/mlp_merge/add"),
             "fusion.1": ("fusion", "f32[32,3584]{1,0}",
                          "jit(step)/layer2/latent/out/dot_general")}
    seconds = {"%hc_maps.3": 100e-6, "%fusion.7": 2e-6, "%fusion.8": 60e-6,
               "%fusion.9": 61e-6, "%fusion.1": 90e-6}
    short = lambda name: name.lstrip("%")
    assert probe.hc_ops(seconds, index, short, 2, steps=20, top=2) == [
        ["attn_maps/hc_maps", 5.0, "custom-call f32[24,32]{1,0}"],
        ["mlp_merge/add", 3.0, "fusion f32[32,4,3584]{2,0,1}"],
        ["others: 1", 0.1, ""]]
