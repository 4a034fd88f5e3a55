"""Bytes and operations a Xing4.0 decode step needs, from the source's own
keys: the numerators of ``xing_stream_floor_share.serve``,
``xing_hc_roofline_share.serve``,
``xing_latent_attention_roofline_share.serve`` and
``xing_experts_roofline_share.serve``, and the reduction of the trace
viewer's events to the share of the device's time under the residual
streams' scopes (``xing_hc_step_share.serve``).  Kept with the benchmark (beside
``dots_cost.py`` and the others) so no PR that claims a gain can change it.

Why ``dots_cost`` does not fit this source though the mixer and the
feed-forward are the same: a token's residual stream is ``hc_mult`` vectors,
mixed round each of a layer's two sublayers by maps made from the streams
themselves (``phi [hc_mult x hidden_size, 2 hc_mult + hc_mult^2]`` float32 a
sublayer), which is both weights to read and streams to move 80 times a
step.

Only what must move is counted: each weight once, the experts *hit* and not
the experts held, latent rows as many blocks as the attention fetched and
the values of a row (576, not the 640 its pool holds it in), a live lane's
streams three times a mixing (read for the maps and the sublayer's input,
read for the merge, written), this step's rows of the embedding, nothing of
activations, the norms or the selection biases, and nothing twice.  The
attention's operations are the absorbed form's.  So a share of a peak
computed from these cannot pass 100%.
"""

import functools
import os

from benchmark import solar_cost

HC_BYTES_PER_VALUE = 4          # the streams and their mixing are float32
HC_SCOPE = "/hc/"               # layer<i>/hc/<sublayer>_<maps|read|merge>
HC_ENDS = ("hc/start", "hc/sum")


def layers(config):
    return config["num_hidden_layers"]


def routed_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def mixings(config):
    """The sublayers whose residual path is a mixing: two a layer."""
    return 2 * layers(config)


def hc_width(config):
    n = config["hc_mult"]
    return 2 * n + n * n


def hc_param_bytes(config):
    """Every mixing's ``phi``, ``b`` and three scalars, float32."""
    return mixings(config) * (
        config["hc_mult"] * config["hidden_size"] * hc_width(config)
        + hc_width(config) + 3) * HC_BYTES_PER_VALUE


def hc_stream_bytes_per_step(config, live_lanes):
    """What the step's mixings move of the live lanes' streams: a lane's
    ``[hc_mult, hidden_size]`` float32 three times a mixing."""
    return 3 * mixings(config) * float(live_lanes) * config["hc_mult"] \
        * config["hidden_size"] * HC_BYTES_PER_VALUE


def hc_floor_bytes_per_step(config, live_lanes):
    """Everything the mixings must move: their parameters and the
    streams."""
    return hc_param_bytes(config) \
        + hc_stream_bytes_per_step(config, live_lanes)


def latent_weight_bytes(config, bytes_per_value=2):
    """One MLA mixer: ``q_a``, its norm, ``q_b``, ``kv_a``, its norm,
    ``kv_b``, ``o_proj``."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, dv, rank = config["qk_nope_head_dim"], \
        config["qk_rope_head_dim"], config["v_head_dim"], \
        config["kv_lora_rank"]
    qr = config["q_lora_rank"]
    return (h * qr + qr + qr * heads * (nope + rope) + h * (rank + rope)
            + rank + rank * heads * (nope + dv) + heads * dv * h) \
        * bytes_per_value


def expert_bytes(config, bytes_per_value=2):
    """One routed expert: gate, up and down of ``hidden_size x
    moe_intermediate_size`` each."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * bytes_per_value


def routed_layer_fixed_bytes(config, bytes_per_value=2):
    """What a routed layer reads whatever was hit: the router over all the
    published experts and the shared expert."""
    h = config["hidden_size"]
    return (h * config["num_experts_published"]
            + config["n_shared_experts"] * 3 * h
            * config["moe_intermediate_size"]) * bytes_per_value


def dense_layer_bytes(config, bytes_per_value=2):
    return 3 * config["hidden_size"] * config["intermediate_size"] \
        * bytes_per_value


def experts_hit_bytes_per_step(config, experts_hit_per_layer,
                               bytes_per_value=2):
    """The routed experts a step must read: in each routed layer every held
    expert that at least one token was routed to (``experts_hit_per_layer``:
    the mean over those layers), once, whole."""
    return routed_layers(config) * float(experts_hit_per_layer) \
        * expert_bytes(config, bytes_per_value)


def latent_block_bytes(config, block_size, bytes_per_value=2):
    """The values of one block of one latent layer: a row a token."""
    return block_size * (config["kv_lora_rank"]
                         + config["qk_rope_head_dim"]) * bytes_per_value


def latent_floor_bytes_per_step(config, blocks_a_layer, block_size,
                                bytes_per_value=2):
    """The rows the step's latent attention fetched: ``blocks_a_layer`` (the
    span's ``latent_blocks_read``) in each layer."""
    return layers(config) * float(blocks_a_layer) \
        * latent_block_bytes(config, block_size, bytes_per_value)


def latent_flops_per_step(config, blocks_a_layer, block_size):
    """The operations of the absorbed attention over those rows: every head
    against every position fetched, a multiply and an add a value of its
    score (``rank + rope`` values) and of its output (``rank``).  A lane's
    last block counts whole though its context may end inside it (the time
    measured covers the whole block too: the kernel multiplies it)."""
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return layers(config) * float(blocks_a_layer) * block_size \
        * config["num_attention_heads"] * 2 * (2 * rank + rope)


def weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                bytes_per_value=2):
    """Weights one decode step must read: every mixer and mixing, the dense
    lead, every router and shared expert, the held experts hit, the head, a
    row of the embedding a lane."""
    h = config["hidden_size"]
    return layers(config) * latent_weight_bytes(config, bytes_per_value) \
        + hc_param_bytes(config) \
        + config["first_k_dense_replace"] \
        * dense_layer_bytes(config, bytes_per_value) \
        + routed_layers(config) \
        * routed_layer_fixed_bytes(config, bytes_per_value) \
        + experts_hit_bytes_per_step(config, experts_hit_per_layer,
                                     bytes_per_value) \
        + h * config["vocab_size"] * bytes_per_value \
        + lanes * h * bytes_per_value


def stream_floor_bytes_per_step(config, experts_hit_per_layer, lanes,
                                blocks_a_layer, block_size, hc_stream_bytes):
    """Everything one decode step must move: the weights, the latent rows
    fetched and the live lanes' streams (``hc_stream_bytes``: the span's
    attribute, which ``hc_stream_bytes_per_step`` counts another way)."""
    return weight_floor_bytes_per_step(config, experts_hit_per_layer, lanes) \
        + latent_floor_bytes_per_step(config, blocks_a_layer, block_size) \
        + float(hc_stream_bytes)


# -- what a reader asks of an observation ----------------------------------------

# the keys that tell this source's configuration from every other cell's
KEYS = ("hc_mult", "hc_sinkhorn_iters", "kv_lora_rank",
        "num_experts_published")


def profiled(obs):
    """Is ``obs`` a traced serving run of this source's configuration with a
    device profile to divide by (not another cell, the parent of the PR that
    added this, or a CPU rehearsal)?"""
    config = obs.get("config") or {}
    return bool(obs.get("kind") == "serve" and obs.get("profile")
                and obs.get("peaks") and obs.get("traced_steps")
                and all(key in config for key in KEYS))


# the profile's seconds under a kernel's name, and the attributes of the
# window's last two seconds of spans (the steps nearest the profiled ones):
# as Solar-Open2's cost file has them
kernel_seconds = solar_cost.kernel_seconds
late_attrs = solar_cost.late_attrs


def is_hc_scope(op_name):
    """Does an operation's ``op_name`` (the ``jax.named_scope`` path XLA
    keeps in its metadata) lie under the residual streams' scopes?"""
    return HC_SCOPE in op_name or any(
        ("/" + end + "/") in ("/" + op_name + "/") for end in HC_ENDS)


def trace_file(trace_dir):
    """The trace viewer's file that ``jax.profiler`` writes beside the
    ``.xplane.pb`` under ``trace_dir`` (``*.trace.json.gz``, the newest), or
    None."""
    import glob

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")),
        key=os.path.getmtime) if trace_dir else []
    return found[-1] if found else None


def trace_events(path):
    """The events of a trace viewer's file: unlike the planes' events as
    ``ProfileData`` gives them, its device events carry their operation's
    metadata, ``args.tf_op`` the ``jax.named_scope`` path among it
    (``jit(step)/.../layer3/hc/attn_maps/div:``).  None where there are
    none."""
    import gzip
    import json

    with gzip.open(path, "rb") as fp:
        return json.load(fp).get("traceEvents") or None


def scoped_share(events, wanted=is_hc_scope):
    """The share of the chips' operation time (self time on the ``XLA Ops``
    lines of the ``/device:TPU:`` processes, inside the ``bench.window``
    annotation: ``trace_reduce``'s own rule for ``op_seconds``) that lies
    under the scopes ``wanted`` accepts, from the trace viewer's events.  A
    share and not seconds: the file may hold fewer events than the profile
    (its writer caps them), and a share of what it holds stands for the
    whole where the steps are alike.  None where no device operation says
    its scope (then nothing can be said, which is not 0)."""
    from benchmark import trace_reduce as tr

    names = {"process_name": {}, "thread_name": {}}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") in names:
            names[ev["name"]][ev.get("pid"), ev.get("tid")] \
                = (ev.get("args") or {}).get("name", "")
    chips = {pid for (pid, _tid), name in names["process_name"].items()
             if name.startswith(tr.DEVICE_PLANE_PREFIX)}
    lines = {key for key, name in names["thread_name"].items()
             if key[0] in chips and name == tr.OPS_LINE}
    window = next(((ev["ts"], ev["ts"] + ev["dur"]) for ev in events
                   if ev.get("ph") == "X"
                   and ev.get("name") == tr.WINDOW_ANNOTATION), None)
    by_line, scopes = {}, {}
    for ev in events:
        key = (ev.get("pid"), ev.get("tid"))
        if ev.get("ph") != "X" or key not in lines:
            continue
        # an event apiece: one operation's executions differ in nothing
        # but their time, and ``_self_times`` sums by name
        name = ev.get("name", "")
        scopes.setdefault(name, (ev.get("args") or {}).get("tf_op", ""))
        by_line.setdefault(key, []).append(
            (float(ev["ts"]), float(ev["ts"]) + float(ev["dur"]), name))
    if not any(scopes.values()):
        return None
    under = total = 0.0
    for evs in by_line.values():
        evs.sort(key=lambda e: (e[0], -e[1]))
        if window:
            evs = tr._clip(evs, *window)
        for name, span in tr._self_times(evs).items():
            total += span
            if wanted(scopes[name]):
                under += span
    return under / total if total else None


def profile_dir():
    """Where the run's profile lies: ``run.py`` traces into ``profile``
    under the program's telemetry directory."""
    import paddle_tpu as fluid

    root = fluid.get_flags(["FLAGS_telemetry_dir"]).get(
        "FLAGS_telemetry_dir")
    return os.path.join(root, "profile") if root else None


@functools.lru_cache(maxsize=2)
def _hc_share_of(path, _modified):
    """``scoped_share`` of one trace file, read once a run (two metrics ask,
    and a window's file is a million events)."""
    events = trace_events(path)
    return scoped_share(events) if events else None


def hc_share(obs):
    """The share of the profiled operations' time under the residual
    streams' scopes; None where that cannot be read."""
    path = trace_file(profile_dir()) if profiled(obs) else None
    return _hc_share_of(path, os.path.getmtime(path)) if path else None


def hc_seconds_per_step(obs):
    """Device seconds a profiled step spends under the residual streams'
    scopes: their share of the operations' time in the trace viewer's file
    times the profile's busy time a step; None where that cannot be read."""
    share = hc_share(obs)
    return None if share is None \
        else share * obs["profile"]["busy_s"] / obs["traced_steps"]
