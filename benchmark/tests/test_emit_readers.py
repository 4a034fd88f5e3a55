"""The readers of the server's publish metrics, each on a hand-made ``obs``:
what they compute, and that a program whose spans lack the attributes (the
parent of the PR that added ``published``) gives nothing and does not
raise."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_module  # noqa: E402


def reader(name):
    return load_module("layer_metrics", name).read


def step(**attrs):
    return {"t": "span", "name": "serving.decode_step", "ts": 0, "dur": 1000,
            "attrs": dict({"lanes": 2, "generated": 2}, **attrs)}


def test_emit_and_publish_readers():
    obs = {"kind": "serve", "decode_spans": [
        step(published=29, phases={"serving.emit": 1200,
                                   "serving.dispatch": 2800}),
        step(published=0, phases={"serving.emit": 300}),
        step(published=31, phases={"serving.emit": 900}),
        # a span closed on an error path: phases, no emit, nothing published
        step(phases={"serving.plan": 50})]}
    assert reader("emit_ms_per_step.serve")(obs) == 0.9
    assert reader("stream_chunks_per_publish.serve")(obs) == 20.0
    # the parent's spans: the phases since PR 25, no ``published``
    parent = {"kind": "serve", "decode_spans": [
        step(phases={"serving.emit": 5100}),
        step(phases={"serving.emit": 4900})]}
    assert reader("emit_ms_per_step.serve")(parent) == 5.0
    assert reader("stream_chunks_per_publish.serve")(parent) is None


@pytest.mark.parametrize("name", ["emit_ms_per_step.serve",
                                  "stream_chunks_per_publish.serve"])
@pytest.mark.parametrize("obs", [
    # spans older than the phases and than ``published``
    {"kind": "serve", "decode_spans": [step(), step()]},
    {"kind": "serve"},
    {"kind": "serve", "decode_spans": []},
    {"kind": "train"}])
def test_nothing_to_read_gives_none(name, obs):
    assert reader(name)(obs) is None
