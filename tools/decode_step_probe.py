"""Probe of the decode step alone, on the chip: no server, no wire, no
scheduler.  One serving configuration of the benchmark (``--config``: a
name under ``benchmark/configs/`` or a path; GPT-2-medium by default) at
its own widths, weight dtype and KV residency, at one lane bucket and one
pool size, through the same ``CarriedStepFn`` the engine uses.  Prints, and
writes under ``chiprun_out/``, one JSON object:

* ``memory``: the compiled step's ``temp_bytes`` / ``alias_bytes`` beside the
  pool's bytes, and the pool-sized instructions left in its HLO; ``attention``
  and ``pallas_kernel_counters``: the path the step's attention took, as the
  rule names it and as each layer's lowering counted it;
* ``parameter_fed_copies``: ``[instruction, parameter, bytes]`` for every
  weight the compiled step copies into another layout each time it runs.
  The step is built from the weights as the engine holds them
  (``decode_model.laid_out``), so a family that lays a weight out at load
  shows none for it; a name here is a layout to choose at load;
* ``step_ms``: host clock over steps that end in ``block_until_ready``;
* ``scope_ms_per_step``: device time per step by ``jax.named_scope``
  (``layer<i>`` folded to ``layerN``), from a profile of ``--steps`` steps
  reduced by ``benchmark/trace_reduce.py`` and keyed through the HLO's
  ``op_name`` metadata (``device_ops_per_step``: how many instructions the
  profile saw run a step); ``top_ops`` names the dearest single instructions
  and ``unscoped_ops`` the dearest of those under no scope (``other``).

    chiprun -- python tools/decode_step_probe.py --blocks 1024 --bucket 32
    chiprun -- python tools/decode_step_probe.py --config olmoe-1b-7b-serve \
        --blocks 2048

For a model of several residual streams (``--config xing4.0-29b-a4b-serve
--blocks 3616 --contexts 200-1700``) the scopes gain ``layerN/hc/attn_maps``,
``attn_read``, ``attn_merge`` and ``mlp_*`` alike round ``latent/*``, ``mlp``
and ``moe/*``, and the result gives ``residual_streams``: the mixings' ms a
step by scope beside the kernels', their share of the busy time, the bytes
they must move (``benchmark/xing_cost.py``) and bytes/s, how many
fusions the compiled step runs under them and one middle layer's mixings an
operation at a time (``hc_ops``); ``hc_maps_path`` says how a mixing's maps
were made, and ``--hc-maps xla`` swaps the kernel (one operation a mixing)
for the jnp form the step takes off the TPU: the comparison the kernel was
adopted by.

For a configuration with state-space layers (``--config
granite-4.0-h-micro-serve --blocks 2048``) every lane holds a state slot,
the scopes gain ``layerN/ssm/in_proj``, ``conv``, ``state_update`` and
``out_proj``, and the result gives ``ssm/state_update``'s achieved bytes/s
(``benchmark/ssm_cost.py``'s state traffic over the scope's device time),
beside it the ``ssm_state_update`` kernels' own ms a step and bytes/s over
the same bytes (what ``ssm_update_roofline_share.serve`` divides, so the two
can be laid side by side) and ``state_update_columns``, the columns of a
slot one transfer of the kernel moves;
``--ssm-update xla`` swaps the kernel for the gather, update and scatter the
step makes of it off the TPU: the comparison the kernel was adopted by.
For ``--config lfm2-24b-a2b-serve --blocks 2048`` every lane holds a window
slot and the scopes gain ``layerN/conv/in_proj``, ``window`` and
``out_proj``.  For ``--config k-exaone-236b-a23b-serve --blocks 12832`` every
lane holds a full ring in the window layers' pools beside its blocks of the
global layer's, ``layerN/attn/kv_read`` is the kernel over a ring or over the
whole context by the layer's kind, and the routed layers gain
``layerN/moe/shared``.  For a routed-expert configuration the result also gives
``moe/experts``' achieved bytes/s (``benchmark/moe_cost.py``, or
``benchmark/lfm2_cost.py`` for a source with its keys, over the scope's
device time) and ``--experts`` names the forms of the routed layer to run,
one after another in this one process over the same weights, a result line
each: ``block`` (what the program picks: ``moe_experts.routed_experts``),
``dense`` (the einsums over all experts, the fallback) and ``kernel`` (the
Pallas kernel that reads the hit experts alone, whatever the shape rule
says).  Each line has ``moe/experts``' ms a step, the expert bytes that form
reads a step (``dense`` all of them, the others the experts this step's
tokens hit, from the step's own routed counts) and bytes/s: the comparison
the block's choice was made by, not an option of the program.  For
``--config nemotron-3-nano-30b-a3b-serve --blocks 2048`` the step is all 52
blocks of one sublayer each (``--layers 6`` keeps ``M E M E M *``): the
``ssm`` scopes with B and C in 8 groups, ``attn/kv_read`` for 32 query heads
over 2, ``moe/router``, ``moe/experts`` and ``moe/shared`` on the layers that
are experts alone; the bytes are ``benchmark/nemotron_cost.py``'s, and
``--experts block,dense,kernel`` compares the two-matrix forms
(``moe_experts.relu2_experts``).  For ``--config kimi-linear-48b-a3b-serve
--blocks 12832`` the step is all 27 layers (``--layers 5`` keeps ``kda kda
kda latent kda``): the scopes ``layerN/kda/conv``, ``kda/state`` and
``kda/out``, ``layerN/latent/absorb``, ``latent/kv_write``,
``latent/kv_read`` and ``latent/out``, ``mlp`` on the dense lead and the
``moe`` scopes elsewhere; the bytes are ``benchmark/kimi_cost.py``'s, and the
result gives the ``kda_state_update`` and ``latent_attention`` kernels' own
ms a step and bytes/s (what ``kimi_kda_state_roofline_share.serve`` and
``kimi_latent_attention_roofline_share.serve`` divide).  For ``--config
dots-vlm1-inst-serve --blocks 12832`` the step is the dense lead and five
routed layers, every one latent: the scopes ``layerN/latent/q_compress``,
``latent/absorb``, ``latent/rope``, ``latent/kv_write``, ``latent/kv_read``
and ``latent/out``, ``mlp``, ``moe/router``, ``moe/experts``,
``moe/shared`` and ``lm_head``; the bytes and the attention's operations are
``benchmark/dots_cost.py``'s.  For ``--config glm-5-serve --blocks 25120
--contexts lo-hi`` the latent layers select: the scopes gain
``layerN/latent/index`` (the indexer's projections and rotation, the index
key's write and the ``index_scores`` kernel) and ``latent/select`` (the
choice: the chosen set made from the scores as the walk's mask by an exact
threshold, 46 counting passes and no sort:
``paged_attention.chosen_by_chunk``) before ``latent/kv_read`` (the latent
kernel over a lane's live blocks under that mask); the index pools are
filled with seeded keys, so that the chosen rows lie scattered as a served
sequence's do; the result gives
``selection``: the index kernel's own ms a step and bytes/s over the live
blocks' keys, the rows chosen of those in context
(``benchmark/glm_cost.py``), the form the selected read took
(``selected_read``), how the choice was made (``select_path``:
``threshold``, the set as a mask; ``listed``, ``top_k``'s list), the
choice's own ms a step, the blocks a layer's masked walk fetched and what
laying a list out as the mask cost (``mask_ms_per_step``, the ``latent/mask``
scope: nothing where the set comes as a mask).  ``--latent-read`` and
``--select`` name controls, not options of the program: ``--latent-read
gathered`` stands the row form in, which a table wider than the rule's
serves (``latent/kv_gather``, the chosen rows' gather, then the kernel over
them), ``dense`` reads every live block under no mask (nothing reads the
index and the choice there, and the compiler drops both), and ``--select
listed`` serves the walk as it was before PR 60: a choice that can give no
mask, so the exact ``top_k`` (a sort) and the one-hot contraction that turns
its list back into the mask.  ``layerN/staged`` (any configuration) is what
XLA puts in itself around a layer's weights and names nothing: a weight
relaid for the product that reads it, or fetched ahead of it.

    chiprun -- python tools/decode_step_probe.py --config \
        lfm2-24b-a2b-serve --blocks 2048 --experts dense,kernel

Every result names ``attention_kernels``: by kind of layer that takes the
attention kernel, the positions a chunk spans (the kernel sizes it by the
bytes a position costs), the chunks a step walks at the profiled contexts,
the kernels' own ms a step, microseconds a chunk, and bytes/s over the live
blocks' bytes (``attention`` with the window layers' rings counted in, whose
kernels carry the same name), and for the K/V kernel ``straight_chunk_share``:
of its chunks, those that ran the straight-line body, by the function the
step's span counts them with.  ``--contexts lo-hi`` draws the lanes'
contexts from that range (a cell's window: ``1100-3100`` for Kimi-Linear's)
where the default is a third to the whole of a lane's share of the pool.

``--check`` leaves the model out and compares the step's attention alone,
at the configuration's shapes, on one layer's random pools and the same
tables: the kernel (``pallas_kernels/paged_attention.py``; its latent form
over one pool of rows for a model with latent layers) against the
gather path, largest absolute and rms error, beside a control whose
products are rounded to bfloat16 (an f32 pool's kernel has to sit orders
below it) and, for a bf16 pool, beside the gather path's own distance from
float32 mathematics on the stored values; and the time of each a call, the
kernel's also by the chunk.

It needs the TPU for a time; ``--compile-only`` stops after ``memory`` (it
then says what the local backend's compiler made, which is not the chip's).
"""

import argparse
import functools
import importlib
import json
import math
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

WARM_STEPS = 5

# --experts: what stands in for moe_experts.routed_experts
EXPERT_FORMS = ("block", "dense", "kernel")


# an instruction of a compiled module's text: its name, its result (one shape,
# or a kernel's or an async start's tuple of them), its opcode, and the rest
_NAMED = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                    r"([\w\-]+)\((.*)")


def hlo_index(text):
    """instruction name -> (opcode, shape, op_name scope) of a compiled
    module's entry computation and fusions."""
    out = {}
    lines = text.splitlines()
    starts = {m.group(1): line for line in lines
              for m in [_NAMED.match(line)]
              if m and m.group(3) in ("copy-start", "slice-start")}
    for line in lines:
        m = _NAMED.match(line)
        if not m:
            continue
        scope = re.search(r'op_name="([^"]*)"', line)
        scope = scope.group(1) if scope else ""
        # what XLA puts in itself around a weight names no scope: a copy
        # that relays it for the product that reads it carries the
        # parameter's own name (``params['l3_wkvb']``), a fetch ahead of its
        # product (copy-start or slice-start, and the -done that names it)
        # carries none.  Both go to the weight's layer, as ``staged``
        param = re.match(r"params\[\\?'(?:l(\d+)_)?", scope)
        if not scope:
            done = re.search(r"%((?:copy|slice)-start[\w.\-]*)\)", line)
            param = re.search(r"%params__(?:l(\d+)_)?", starts.get(
                done.group(1), "") if done else line)
        if param:
            scope = ("layer%s/" % param.group(1) if param.group(1)
                     else "") + "staged"
        out[m.group(1)] = (m.group(3), m.group(2), scope)
    return out


# opcodes that move a value and compute nothing: a fetch ahead of its use (the
# -start and -done of a slice or a copy, which a module restored from the
# compile cache prints as ``async-start`` / ``async-done`` of a computation
# that holds the slice), a view, the pieces put together again
_MOVES = ("slice-start", "slice-done", "copy-start", "copy-done",
          "async-start", "async-done", "bitcast", "get-tuple-element",
          "ConcatBitcast")
# ... and those that lay it out anew
_RELAYS = ("copy", "transpose")
_ITEMSIZE = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2}


def parameter_fed_copies(text):
    """What the compiled step copies into another layout of its own
    parameters (``params[...]``, the weights: constants, so each is work a
    layout chosen at load would save) -> [[instruction, parameter, bytes of
    the result], ...] over the entry computation: a ``copy`` or a
    ``transpose``, or a fusion of nothing else, whose operand is the
    parameter or what only moved it (``_MOVES``).  A fetch ahead of a
    product is no copy in this sense: the product reads what it fetched."""
    bodies, entry, inside = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            inside = entry if head.group(1) else bodies.setdefault(
                head.group(2), {})
            continue
        m = _NAMED.match(line)
        if m and inside is not None:
            name, shape, op, rest = m.groups()
            target = re.search(r'custom_call_target="(\w+)"', rest)
            calls = re.search(r"calls=%?([\w.\-]+)", rest)
            inside[name] = (
                target.group(1) if target else op, shape,
                re.findall(r"%([\w.\-]+)", rest.split("), ")[0]),
                calls.group(1) if calls else None)

    def relays(name):
        op, _shape, _operands, calls = entry[name]
        if op == "fusion":
            inner = {o for o, *_ in bodies.get(calls, {}).values()}
            return bool(inner & set(_RELAYS)) and inner <= set(
                _RELAYS + ("parameter", "bitcast"))
        return op in _RELAYS

    def parameter(name):
        """The step parameter ``name`` is, or only moves."""
        op, _shape, operands, calls = entry.get(name, ("", "", [], None))
        if op == "parameter":
            return name if name.startswith("params__") else None
        inner = {o for o, *_ in bodies.get(calls, {}).values()}
        if op in _MOVES and inner <= {"parameter", "slice", "copy"}:
            return next(filter(None, map(parameter, operands)), None)
        return None

    found = []
    for name, (_op, shape, operands, _calls) in entry.items():
        source = relays(name) and next(
            filter(None, map(parameter, operands)), None)
        if source:
            dtype, dims = re.match(r"\(?(\w+)\[([\d,]*)\]", shape).groups()
            found.append([
                name, re.sub(r"^params__|__(\.\d+)?$", "", source),
                _ITEMSIZE.get(dtype, 1) * math.prod(
                    map(int, filter(None, dims.split(","))))])
    return found


def scope_of(op_name):
    """``jit(step)/layer3/attn/kv_write/scatter`` -> ``layerN/attn/kv_write``
    (a gated softmax mixer's ``layerN/attention/`` + ``qkv``, ``kv_write``,
    ``kv_read``, ``gate``, ``out`` among them).  A recurrent layer's window
    moves under ``window`` inside its mixer's scope, ``kda/conv/window`` and
    ``ssm/conv/window``; a ``conv`` layer's mixer names its own scope so,
    and the two are one: ``conv/window``.  A model of several residual
    streams mixes them under ``layerN/hc/`` + ``attn_maps``, ``attn_read``,
    ``attn_merge`` and ``mlp_*`` alike (``hc``: ``hc/start`` and
    ``hc/sum``)."""
    parts = [p for p in op_name.split("/") if not p.startswith("jit(")]
    parts = [re.sub(r"^layer\d+$", "layerN", p) for p in parts]
    keep = [p for p in parts
            if p in ("layerN", "attn", "mlp", "moe", "router", "experts",
                     "lm_head", "kv_write", "kv_read", "kv_gather",
                     "ssm", "in_proj", "conv", "state_update", "out_proj",
                     "window", "kda", "state", "out", "latent", "absorb",
                     "shared", "q_compress", "rope", "staged", "index",
                     "select", "mask", "zero", "attention", "qkv", "gate",
                     # a model of several residual streams: a sublayer's
                     # mixing (``hc`` alone: the two ends of the streams)
                     "hc", "attn_maps", "attn_read", "attn_merge",
                     "mlp_maps", "mlp_read", "mlp_merge")]
    keep = [p for i, p in enumerate(keep) if not i or p != keep[i - 1]]
    return "/".join(keep) or "other"


def hc_ops(op_seconds, index, short, layer, steps, top=24):
    """The operations of layer ``layer``'s two mixings by the profile:
    [[scope under the layer, us an execution, opcode and shape], ...] the
    dearest ``top`` of them, then how many the others are and their sum."""
    under = "layer%d/hc/" % layer
    found = []
    for name, secs in op_seconds.items():
        op, shape, scope = index.get(short(name), ("", "", ""))
        if under in scope:
            found.append([scope.split(under)[1], round(secs * 1e6 / steps, 3),
                          (op + " " + shape)[:72]])
    found.sort(key=lambda row: -row[1])
    rest = found[top:]
    return found[:top] + [["others: %d" % len(rest),
                           round(sum(row[1] for row in rest), 3), ""]]


def pool_sized(index, pool_elems):
    """Instructions whose result is at least one layer pool, by opcode."""
    found = {}
    for _name, (op, shape, _scope) in index.items():
        if op in ("parameter", "get-tuple-element", "tuple", "bitcast"):
            continue
        dims = re.findall(r"\[([\d,]+)\]", shape)
        elems = max((math.prod(map(int, d.split(","))) for d in dims),
                    default=0)
        if elems >= pool_elems:
            found[op] = found.get(op, 0) + 1
    return found


def full_rings(kv, lanes):
    """Window tables [lanes, ring] for lanes past their first window: a
    full ring of its own blocks each (ring 0 holds the scratch block)."""
    import numpy as np

    ring = kv.window_ring
    return (ring + np.arange(lanes * ring, dtype=np.int32)).reshape(lanes,
                                                                    ring)


def check_attention(cfg, kv, tables, lens, seed, repeat=24, window=None):
    """The kernel against the gather path on one layer's pools (see the
    module docstring).  ``scale`` is the rms of the exact output.  A path is
    timed as ``repeat`` calls chained inside one program, each call's query
    the last one's output: one call alone costs less than its dispatch from
    the host (0.2 ms).  ``window`` given, the pools are a window layer's and
    ``tables`` the lanes' rings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.pallas_kernels import paged_attention as pa
    from paddle_tpu.serving import kv_cache as kvc

    dtype = kvc._PAYLOAD[kv.dtype][0]
    latent = bool(cfg.latent_layers)
    width = kv.latent_row if latent else kv.heads * kv.head_dim
    shape = (kv.window_blocks if window else kv.num_blocks, kv.block_size,
             width)
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    q = jax.random.normal(
        kq, (len(lens), cfg.heads, width if latent else cfg.head_dim),
        jnp.float32)
    k_pool = jax.random.normal(kk, shape, jnp.float32).astype(dtype)
    v_pool = jax.random.normal(kv_, shape, jnp.float32).astype(dtype)
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)

    scale = cfg.latent_scale if latent else cfg.attention_multiplier
    if latent:
        # one pool of rows, the value a row's first ``latent_rank`` columns
        # (``v`` rides along unread)
        rank = cfg.latent_rank
        gather = lambda q, k, _v, t, n, to=None: \
            pa.latent_attention_reference(
                q, k.astype(to or k.dtype), t, n, scale, rank)
        kernel = lambda q, k, _v, t, n: pa._latent_pallas(q, k, t, n, scale,
                                                          rank)
        span = pa.latent_chunk_positions(q.shape, shape, dtype, rank,
                                         tables.shape[1])
    else:
        gather = lambda q, k, v, t, n, to=None: pa.paged_attention_reference(
            q, k.astype(to or k.dtype), v.astype(to or v.dtype), t, n, scale,
            window)
        kernel = lambda q, k, v, t, n: pa._paged_pallas(
            q, k, v, t, n, scale, window=window)
        span = pa.chunk_positions(q.shape, shape, dtype, tables.shape[1],
                                  ring=tables.shape[1] if window else 0)
    paths = {
        "kernel": kernel,
        "gather": gather,
        # float32 mathematics on the values as stored
        "exact": functools.partial(gather, to=jnp.float32),
        # every product of bfloat16 operands
        "bf16_products": functools.partial(gather, to=jnp.bfloat16),
    }
    rest = (k_pool, v_pool, tables, lens)
    out, ms = {}, {}
    for name, fn in paths.items():
        out[name] = np.asarray(jax.jit(fn)(q, *rest), np.float64)
        # the latent form's output is narrower than its query
        again = lambda q, *rest, _fn=fn: jnp.pad(_fn(q, *rest), (
            (0, 0), (0, 0), (0, q.shape[2] - out[name].shape[2])))
        chained = jax.jit(lambda q, *rest, _fn=again: jax.lax.fori_loop(
            0, repeat, lambda _i, q: _fn(q, *rest), q))
        chained(q, *rest).block_until_ready()
        t0 = time.perf_counter()
        chained(q, *rest).block_until_ready()
        ms[name] = (time.perf_counter() - t0) * 1e3 / repeat

    def err(a, b):
        d = out[a] - out[b]
        return {"max_abs": float(np.abs(d).max()),
                "rms": float(np.sqrt((d ** 2).mean()))}

    finite = bool(np.isfinite(out["kernel"]).all())
    kernel, gathered = err("kernel", "exact"), err("gather", "exact")
    coarse = err("bf16_products", "exact")
    if dtype == jnp.float32:
        # the gather is the exact path here: the kernel orders under a
        # path whose products are bfloat16
        passed = finite and kernel["max_abs"] <= 1e-2 * coarse["max_abs"]
    else:
        # both round the query and the probabilities to the pool's dtype
        passed = finite and kernel["rms"] <= 1.5 * gathered["rms"] \
            and kernel["max_abs"] <= 3 * gathered["max_abs"]
    # the chunks a call walks (a live lane's ring is one where the ring is
    # a chunk; a longer ring's slots are walked as a context's blocks are)
    chunks = pa.chunks_read(np.asarray(lens), kv.block_size,
                            tables.shape[1], span)[0] if window \
        else int((-(-np.asarray(lens) // span)).sum())
    return {"scale": float(np.sqrt((out["exact"] ** 2).mean())),
            "finite": finite, "passed": bool(passed),
            "kernel_vs_gather": err("kernel", "gather"),
            "kernel_vs_exact": kernel,
            "gather_vs_exact": gathered,
            "bf16_products_vs_exact": coarse,
            "ms_per_call": ms,
            # the kernel's chunk, how many a call walks, and what one costs
            "chunk_positions": span, "chunks": chunks,
            "kernel_us_per_chunk": ms["kernel"] * 1e3 / chunks,
            "live_blocks": int((-(-np.asarray(lens) // kv.block_size)).sum()),
            "blocks_read": pa.blocks_read(np.asarray(lens), kv.block_size,
                                          tables.shape[1], "pallas",
                                          ring=bool(window)),
            "table_slots": int(tables.size)}


def probe_step(args, form, reads_all, config, cfg, kv, cache, device, feed):
    """Compile the step as ``moe_experts.routed_experts`` stands now, and on
    a TPU run, time and profile it -> the result line of ``form``, which
    reads every expert (``reads_all``) or the hit ones."""
    import jax
    import numpy as np

    from benchmark import dots_cost, exaone_cost, glm_cost, kimi_cost, \
        lfm2_cost, longcat_cost, moe_cost, nemotron_cost, smallthinker_cost, \
        solar_cost, ssm_cost, trace_reduce
    from paddle_tpu.core import telemetry
    from paddle_tpu.core.executor import CarriedStepFn
    from paddle_tpu.pallas_kernels import kda_update, paged_attention, \
        ssm_update
    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    b = args.bucket
    stepfn = CarriedStepFn(dm.make_paged_step(cfg, kv), donate_argnums=(0,),
                           name="probe")
    warm = stepfn.warmup(b, *feed(0))
    compiled = stepfn.executable(b)
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    if args.hlo_out:
        os.makedirs(os.path.dirname(args.hlo_out) or ".", exist_ok=True)
        with open(args.hlo_out, "w") as fp:
            fp.write(text)
    index = hlo_index(text)
    said = dm.StepAccount(cfg, kv, feed(0)[1], (b,)).prewarm_attrs(b)
    # one layer's K (or V) pool, or a recurrent layer's state slots,
    # whichever is smaller: a copy of either is what the search is for
    pool_elems = args.blocks * args.block_size * kv.heads * kv.head_dim
    if cfg.latent_layers:
        pool_elems = args.blocks * args.block_size * kv.latent_row
    if cfg.ssm_layers or cfg.kda_layers:
        pool_elems = min(pool_elems, kv.state_slots * int(np.prod(
            kv.state_shapes[1][0])))
    result = {
        "label": args.label, "config": config["name"],
        "experts": form, "ssm_update": args.ssm_update,
        "device": device.device_kind,
        "platform": device.platform, "blocks": args.blocks,
        "bucket": b, "dtype": args.dtype, "layers": cfg.layers,
        "memory": {"temp_bytes": int(memory.temp_size_in_bytes),
                   "alias_bytes": int(memory.alias_size_in_bytes),
                   "pool_bytes": cache.kv_nbytes,
                   "state_bytes": kvc.state_bytes(kv),
                   "compile_ms": round(warm["compile_ms"], 1),
                   "pool_sized_instructions": pool_sized(index, pool_elems)},
        # the weights the step lays out anew every time it runs (a family's
        # ``laid_out`` is there to empty this list)
        "parameter_fed_copies": parameter_fed_copies(text),
        # the paths as the engine's ``serving_prewarm`` names them, the
        # columns of a slot one transfer of the state-update kernel moves
        # among them
        "attention": said["attention"],
        "state_update_columns": said.get("state_update_columns")
        if args.ssm_update == "step" else None,
        "window_attention": said.get("window_attention"),
        # how a mixing's maps are made: the rule's word, the control's
        "hc_maps_path": said.get("hc_maps_path")
        if args.hc_maps == "step" else args.hc_maps,
        "pallas_kernel_counters": {
            key: value for key, value in telemetry.snapshot()["counters"].items()
            if key.startswith("pallas_kernel_")},
    }
    if args.compile_only:
        return result
    if device.platform != "tpu":
        print("decode_step_probe: no TPU, so no time (use --compile-only)",
              file=sys.stderr)
        return result

    routed = []

    def run(n0, n):
        for i in range(n0, n0 + n):
            carry, nxt, _logits, *extras = stepfn(b, *feed(i))
            cache.replace_carry(carry)
            nxt.block_until_ready()
            routed.extend(extras[:1])

    run(0, WARM_STEPS)
    t0 = time.perf_counter()
    run(WARM_STEPS, args.steps)
    result["step_ms"] = (time.perf_counter() - t0) * 1e3 / args.steps
    trace_dir = tempfile.mkdtemp(prefix="decode_step_probe_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
        run(WARM_STEPS + args.steps, args.steps)
    jax.profiler.stop_trace()
    prof = trace_reduce.reduce_dir(trace_dir, top=12)
    scopes = {}
    short = lambda name: trace_reduce._short(name).lstrip("%_")
    unscoped = {}
    for name, secs in prof["op_seconds"].items():
        key = scope_of(index.get(short(name), ("", "", ""))[2])
        scopes[key] = scopes.get(key, 0.0) + secs * 1e3 / args.steps
        if key == "other":
            unscoped[name] = secs * 1e3 / args.steps
    result["busy_ms_per_step"] = prof["busy_s"] * 1e3 / args.steps
    # the operations the device runs a step: each instruction runs once
    result["device_ops_per_step"] = len(prof["op_seconds"])
    result["scope_ms_per_step"] = dict(
        sorted(scopes.items(), key=lambda kv: -kv[1]))
    # what lies under no scope, dearest first: [name, ms a step, opcode and
    # shape], so that a share of the step nobody named can be found
    result["unscoped_ops"] = [
        [n, round(ms, 4), " ".join(index.get(short(n), ("", "", ""))[:2])[:96]]
        for n, ms in sorted(unscoped.items(), key=lambda kv: -kv[1])[:12]]
    result["top_ops"] = [
        [n, round(s * 1e3 / args.steps, 4),
         "/".join(index.get(short(n), ("", "", ""))[::2])[:120]]
        for n, s in prof["device_ops"]]
    hc_ms = {k: v for k, v in scopes.items() if "hc" in k.split("/")}
    if cfg.mixings and hc_ms:
        # the residual streams' mixings beside the kernels': their time by
        # scope, what they must move (``benchmark/xing_cost.py``: every
        # mixing's parameters and the lanes' streams three times a mixing)
        # and how near its floor that runs
        from benchmark import xing_cost

        moved = xing_cost.hc_floor_bytes_per_step(config, b)
        total = sum(hc_ms.values())
        fusions = sum(op == "fusion" and "/hc/" in scope
                      for op, _shape, scope in index.values())
        result["residual_streams"] = {
            "streams": cfg.hc_mult, "mixings": cfg.mixings,
            "sinkhorn_iters": cfg.hc_sinkhorn_iters,
            "hc_ms_per_step": total, "hc_ms_by_scope": hc_ms,
            "hc_share_of_busy": total / result["busy_ms_per_step"],
            "hc_bytes_per_step": moved,
            "hc_bytes_per_s": moved / (total / 1e3),
            # the fusions the compiled step runs under the scopes (maps,
            # read and merge), and a mixing
            "hc_fusions": fusions,
            "hc_fusions_a_mixing": fusions / cfg.mixings,
            # one middle layer's mixings an operation at a time, dearest
            # first: [scope, us an execution, opcode and shape]
            "hc_ops": hc_ops(prof["op_seconds"], index, short,
                             cfg.layers // 2, args.steps)}
    moe_ms = sum(v for k, v in scopes.items() if k.endswith("experts"))
    if cfg.routed_layers and moe_ms:
        # the experts this form reads in a routed layer, once a step:
        # every one for the einsums (hit or not), the profiled steps' hit
        # ones for the others; over the scope's time.  The cost file is the
        # one that reads this source's keys
        hit = float(np.mean([
            (np.asarray(r)[:, cfg.held_experts] > 0).sum(axis=1).mean()
            for r in routed[-args.steps:]]))
        held = "num_experts"
        if "hybrid_override_pattern" in config:
            # two-matrix experts in the layers the pattern names, a share
            bytes_of = nemotron_cost.experts_hit_bytes_per_step
            held = "n_routed_experts"
        elif "gqa_layers" in config:
            # three-matrix experts in every layer, a share
            bytes_of = solar_cost.experts_hit_bytes_per_step
            held = "n_routed_experts"
        elif "linear_attn_config" in config:
            # three-matrix experts behind a dense lead, a share
            bytes_of = kimi_cost.experts_hit_bytes_per_step
        elif "zero_expert_num" in config:
            # one routed part a pair of sublayers, a share; an identity
            # expert has no bytes
            bytes_of = longcat_cost.experts_hit_bytes_per_step
        elif "n_group" in config and "kv_lora_rank" in config:
            # the same behind latent attention in every layer, a share
            bytes_of = dots_cost.experts_hit_bytes_per_step
        elif "moe_num_primary_experts" in config:
            # ReLU-gated experts in every layer, all of them held
            bytes_of = smallthinker_cost.experts_hit_bytes_per_step
            held = "moe_num_primary_experts"
        elif "mlp_layer_types" in config:
            # a share: the held experts of each sparse layer
            bytes_of = lambda _c, n: exaone_cost.sparse_layers(config) * n \
                * exaone_cost.expert_bytes(config)
        elif "moe_intermediate_size" in config:
            bytes_of = lfm2_cost.routed_stream_floor_bytes_per_step
        else:
            bytes_of = moe_cost.expert_stream_bytes_per_step
        moved = bytes_of(config, config[held] if reads_all else hit)
        result["moe_experts_hit_per_layer"] = hit
        result["moe_experts_ms_per_step"] = moe_ms
        result["moe_experts_bytes_per_step"] = moved
        result["moe_experts_bytes_per_s"] = moved / (moe_ms / 1e3)
    ssm_ms = sum(v for k, v in scopes.items()
                 if k.endswith("ssm/state_update"))
    if cfg.ssm_layers and ssm_ms:
        # every lane's state in every mamba layer, read and written
        # once a step, over the scope's time
        cost = nemotron_cost if "hybrid_override_pattern" in config \
            else ssm_cost
        moved = cost.state_traffic_bytes_per_step(config, b)
        result["ssm_state_bytes_per_step"] = moved
        result["ssm_state_update_bytes_per_s"] = moved / (ssm_ms / 1e3)
        # the kernel's executions alone, by the name they carry in the
        # trace: what ``ssm_update_roofline_share.serve`` divides (the
        # scope above also holds the fusions that make its operands)
        kernel_s = sum(s for name, s in prof["op_seconds"].items()
                       if name.lstrip("%").startswith(ssm_update.KERNEL_NAME))
        if kernel_s:
            result["ssm_state_update_kernel_ms_per_step"] = \
                kernel_s * 1e3 / args.steps
            result["ssm_state_update_kernel_bytes_per_s"] = \
                moved / (kernel_s / args.steps)
    kernel_ms = lambda kernel: sum(
        s for name, s in prof["op_seconds"].items()
        if name.lstrip("%").startswith(kernel)) * 1e3 / args.steps
    if cfg.kda_layers:
        # every lane's state in every KDA layer, read and written once a
        # step, over the kernel's own executions
        moved = (solar_cost if "gqa_layers" in config else kimi_cost) \
            .state_traffic_bytes_per_step(config, b)
        result["kda_state_bytes_per_step"] = moved
        ms = kernel_ms(kda_update.KERNEL_NAME)
        if ms:
            result["kda_state_update_kernel_ms_per_step"] = ms
            result["kda_state_update_kernel_bytes_per_s"] = moved / (ms / 1e3)
    # each kind of attention kernel at the profiled steps' contexts: its
    # chunk, the chunks a step walks over the kind's layers, its own time by
    # the step and by the chunk, and the rate over the live blocks' bytes
    # (a window layer's kernels carry the global layers' name in a trace:
    # ``attention`` counts the rings' chunks and bytes beside the tables'
    # where a model has both), and of the K/V kernel's chunks the share that
    # ran its straight-line body (``paged_attention.straight_chunks_read``,
    # what the step's span counts too)
    now = np.asarray(feed(WARM_STEPS + args.steps)[5])
    row = kvc._PAYLOAD[kv.dtype][0].dtype.itemsize * args.block_size
    spans = dm.chunk_positions(cfg, kv, b)
    result["attention_kernels"] = {}
    for kind, span in spans.items():
        if kind == "window":
            continue
        layers = cfg.latent_layers if kind == "latent" else cfg.attn_layers
        ms = kernel_ms(paged_attention.LATENT_KERNEL_NAME if kind == "latent"
                       else paged_attention.KERNEL_NAME)
        # (layers, table slots, positions a chunk, a ring?)
        walks = [(len(layers), cfg.max_seq // args.block_size, span, False)]
        if kind == "attention" and "window" in spans:
            walks.append((len(cfg.window_layers), kv.window_ring,
                          spans["window"], True))
        chunks = sum(n * paged_attention.chunks_read(
            now, args.block_size, slots, at)[0] for n, slots, at, _ in walks)
        moved = row * sum(n * paged_attention.blocks_read(
            now, args.block_size, slots, "pallas", ring=ring)
            for n, slots, _at, ring in walks) \
            * (kv.latent_row if kind == "latent"
               else 2 * kv.heads * kv.head_dim)
        result["attention_kernels"][kind] = {
            "chunk_positions": span, "chunks_per_step": chunks,
            "kernel_ms_per_step": ms,
            "us_per_chunk": ms * 1e3 / chunks if chunks else None,
            "bytes_per_step": moved,
            "bytes_per_s": moved / (ms / 1e3) if ms else None,
            "with_window_kernels": len(walks) > 1}
        if kind == "attention" and chunks:
            result["attention_kernels"][kind]["straight_chunk_share"] = \
                100.0 * sum(n * paged_attention.straight_chunks_read(
                    now, args.block_size, slots, at)
                    for n, slots, at, _ in walks) / chunks
    # the form a selecting model's read took (None: no selection, or the
    # dense control's none)
    form = dm.attention_path(cfg, kv, b, "selected") \
        if args.latent_read != "dense" else None
    if cfg.latent_layers:
        # the rows the latent layers fetched at the profiled steps' contexts
        # (the values of a row, not the width its pool holds it in; the row
        # form of a selected read fetches the chosen rows alone)
        read = paged_attention.blocks_read(
            np.minimum(now, cfg.index_topk) if form == "pallas" else now,
            args.block_size,
            cfg.max_seq // args.block_size, result["attention"])
        cost = kimi_cost if "linear_attn_config" in config \
            else longcat_cost if "zero_expert_num" in config else dots_cost
        moved = cost.latent_floor_bytes_per_step(config, read,
                                                 args.block_size)
        result["latent_blocks_read"] = read
        result["latent_bytes_per_step"] = moved
        ms = kernel_ms(paged_attention.LATENT_KERNEL_NAME)
        if ms:
            result["latent_attention_kernel_ms_per_step"] = ms
            result["latent_attention_kernel_bytes_per_s"] = moved / (ms / 1e3)
            if cost is not kimi_cost:
                # 128 heads over a row put the kernel at the chip's ridge
                # (64, at half of it): its operations beside its bytes
                result["latent_attention_kernel_flops_per_s"] = \
                    cost.latent_flops_per_step(
                        config, read, args.block_size) / (ms / 1e3)
    if cfg.index_topk:
        walked = paged_attention.blocks_read(
            now, args.block_size, cfg.max_seq // args.block_size,
            dm.attention_path(cfg, kv, b, "index"))
        moved = glm_cost.index_floor_bytes_per_step(config, walked,
                                                    args.block_size)
        ms = kernel_ms(paged_attention.INDEX_KERNEL_NAME)
        result["selection"] = {
            "latent_read": args.latent_read, "selected_read": form,
            # how the choice was made (the walk alone reads a mask), and
            # its scope's ms
            "select": args.select,
            "select_path": "threshold" if args.select == "served"
            and form == "pallas_masked" else "listed",
            "select_ms_per_step": sum(v for k, v in scopes.items()
                                      if k.endswith("latent/select")),
            # what a layer's masked walk fetched, and what laying the
            # chosen positions out as its mask cost
            "latent_blocks_walked": result["latent_blocks_read"]
            if form == "pallas_masked" else None,
            "mask_ms_per_step": sum(v for k, v in scopes.items()
                                    if k.endswith("latent/mask")),
            "index_path": dm.attention_path(cfg, kv, b, "index"),
            "index_blocks_read": walked, "index_bytes_per_step": moved,
            "index_kernel_ms_per_step": ms,
            "index_kernel_bytes_per_s": moved / (ms / 1e3) if ms else None,
            "rows_selected": int(np.minimum(now, cfg.index_topk).sum()),
            "rows_in_context": int(now.sum()),
            "sparse_lanes": int((now > cfg.index_topk).sum())}
    stats = device.memory_stats() or {}
    result["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    result["peak_bytes_reserved"] = stats.get("peak_bytes_reserved")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2-medium-serve",
                    help="a serving configuration of the benchmark: a name "
                    "under benchmark/configs/ or a path to such a file")
    ap.add_argument("--experts", default="block",
                    help="forms of the routed layer to run, comma separated: "
                    + ", ".join(EXPERT_FORMS))
    ap.add_argument("--ssm-update", default="step", choices=("step", "xla"),
                    help="xla: the state update as gather, update, scatter "
                    "(what the step does off the TPU) in place of the kernel")
    ap.add_argument("--hc-maps", default="step", choices=("step", "xla"),
                    help="xla: a mixing's maps in jnp (what the step does off "
                    "the TPU: 81 fusions a mixing at 20 Sinkhorn iterations) "
                    "in place of the kernel")
    ap.add_argument("--latent-read", default="served",
                    choices=("served", "gathered", "dense"),
                    help="a control of a selecting model's read: gathered, "
                    "the chosen rows gathered and the latent kernel over "
                    "them (the row form); dense, every live block under no "
                    "mask")
    ap.add_argument("--select", default="served",
                    choices=("served", "listed"),
                    help="a control of a selecting model's choice: listed, "
                    "top_k's list and (where the read walks under a mask) "
                    "the one-hot contraction that lays it out, in place of "
                    "the threshold's mask")
    ap.add_argument("--blocks", type=int, default=1024)
    ap.add_argument("--bucket", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--dtype", default=None,
                    help="KV residency (default: the model's own, else f32)")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--contexts", default=None, metavar="LO-HI",
                    help="draw the lanes' contexts from this range (a "
                    "cell's window) in place of a third to the whole of a "
                    "lane's share of the pool")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="the attention kernel against the gather path at "
                    "this configuration's shapes, and nothing else")
    ap.add_argument("--hlo-out", default=None)
    ap.add_argument("--label", default="probe")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from benchmark.run import load_module
    import paddle_tpu as fluid
    from paddle_tpu.core import telemetry
    from paddle_tpu.pallas_kernels import moe_experts, paged_attention
    from paddle_tpu.serving import decode_model as dm
    from paddle_tpu.serving import kv_cache as kvc

    path = args.config if os.path.exists(args.config) else os.path.join(
        ROOT, "benchmark", "configs", args.config + ".json")
    with open(path) as fp:
        config = json.load(fp)
    config.pop("tiny", None)
    if args.layers:
        # (``num_layers``: a source whose layers are pairs of sublayers)
        config["n_layer" if "n_layer" in config else "num_layers"
               if "num_layers" in config
               else "num_hidden_layers"] = args.layers
        for key in ("layer_types", "mlp_layer_types", "sliding_windows",
                    "hybrid_override_pattern", "rope_layout",
                    "sliding_window_layout"):
            if key in config:
                config[key] = config[key][:args.layers]
        if "linear_attn_config" in config:
            # the source names its layers in two lists, from 1
            config["linear_attn_config"] = dict(
                config["linear_attn_config"], **{
                    key: [l for l in config["linear_attn_config"][key]
                          if l <= args.layers]
                    for key in ("kda_layers", "full_attn_layers")
                    if key in config["linear_attn_config"]})
    device = jax.devices()[0]
    model = load_module("models", config["model"])
    cfg = model.decoder_config(config)
    forms = args.experts.split(",")
    if set(forms) - set(EXPERT_FORMS):
        ap.error("--experts takes " + ", ".join(EXPERT_FORMS))
    if args.ssm_update == "xla":
        from paddle_tpu.pallas_kernels import kda_update, ssm_update

        ssm_update.state_update = ssm_update.state_update_reference
        kda_update.state_update = kda_update.state_update_reference
    if args.hc_maps == "xla":
        from paddle_tpu.models import hyper_connections

        hyper_connections.maps = hyper_connections.maps_reference
    args.dtype = args.dtype or cfg.kv_dtype or "f32"
    # a state slot a lane and the scratch, for a model with recurrent layers
    kv = dm.cache_config(cfg, args.block_size, args.blocks, args.dtype,
                         state_slots=args.bucket + 1)
    cache = kvc.PagedKVCache(kv)
    b, maxb = args.bucket, cfg.max_seq // args.block_size
    if cfg.index_topk:
        # seeded index keys, so that the chosen rows lie scattered
        carry = list(cache.carry())
        for i in kv.index_places:
            carry[i] = jax.random.normal(
                jax.random.PRNGKey(i), carry[i].shape, carry[i].dtype)
        cache.replace_carry(carry)
        if args.latent_read == "gathered":
            # no table is short enough for the walk: the rule's other side
            paged_attention._WALK_POSITIONS_PER_CHOSEN = 0
        if args.select == "listed":
            # a plain pair can give no mask: the list, laid out where walked
            dm.choose = lambda *a: tuple(paged_attention.choose(*a))
        if args.latent_read == "dense":
            dm.selected_latent_attention = (
                lambda q, pool, tables, lens, _positions, _count, scale,
                rank: dm.latent_attention(q, pool, tables, lens, scale,
                                          rank))

    # every lane mid-sequence, its blocks its own, as in the cell's window:
    # contexts from a third of what a lane's share of the pool holds up to
    # all of it, so the lanes hold 59-63% of the pool (the cells' windows
    # end at 52-71%) and a third of the table's slots are live
    rng = np.random.default_rng(args.seed)
    grow = WARM_STEPS + 2 * args.steps
    longest = min(cfg.max_seq - grow,
                  (args.blocks - 1) // b * args.block_size - grow)
    lo, hi = longest // 3, longest
    if args.contexts:
        lo, hi = map(int, args.contexts.split("-"))
        if not 0 < lo < hi <= longest:
            ap.error("--contexts: 0 < lo < hi <= %d at this pool, bucket "
                     "and model" % longest)
    lens = rng.integers(lo, hi, b).astype(np.int32)
    tables = np.full((b, maxb), -1, np.int32)
    free = iter(rng.permutation(np.arange(1, args.blocks)))
    for i in range(b):
        for j in range(-(-int(lens[i] + grow) // args.block_size)):
            tables[i, j] = next(free)
    tok = rng.integers(0, cfg.vocab, b).astype(np.int32)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    if args.check:
        if device.platform != "tpu":
            print("decode_step_probe: no TPU, so no kernel to check",
                  file=sys.stderr)
            return 2
        result = dict(
            check_attention(cfg, kv, tables, lens, args.seed),
            label=args.label, config=config["name"], dtype=args.dtype,
            device=device.device_kind, blocks=args.blocks, bucket=b)
        if cfg.window_layers:
            # the same of a window layer over every lane's ring
            result["window"] = check_attention(
                cfg, kv, full_rings(kv, b), lens, args.seed,
                window=cfg.window)
        with open(os.path.join(out_dir, "decode_step_check.jsonl"),
                  "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result))
        return 0 if result["passed"] \
            and result.get("window", result)["passed"] else 1

    # as the engine holds them: what is timed here is what a cell serves
    params = dm.laid_out(cfg, model.make_params(config, args.seed, device))
    # count which path each layer's lowering takes
    fluid.set_flags({"FLAGS_telemetry": True})
    slots = (np.arange(1, b + 1, dtype=np.int32),) \
        if cfg.recurrent_layers else ()
    if cfg.window_layers:
        slots += (full_rings(kv, b),)
    feed = lambda n: (cache.carry(), params, tok, lens + n - 1, tables,
                      lens + n) + slots
    # the model's form of the routed layer, as its family declares it: three
    # matrices with a gate, or two (relu^2, ``up`` and ``down`` both [E, F, H])
    two = importlib.import_module(
        "paddle_tpu.models." + cfg.arch).FAMILY.expert_matrices == 2
    entry = "relu2_experts" if two else "routed_experts"
    block_experts = getattr(moe_experts, entry)
    # form -> (what stands in for it, whether it reads every expert of a
    # layer or the hit ones)
    swapped = {
        "block": (block_experts,
                  dm.experts_path(cfg, params, b) != "pallas"),
        "dense": (lambda h2, gates, live, *w: (
            moe_experts.relu2_reference if two
            else moe_experts.experts_reference)(h2, gates, *w), True),
        "kernel": (moe_experts._relu2_pallas if two
                   else moe_experts._experts_pallas, False)}
    for form in forms:
        stand_in, reads_all = swapped[form]
        setattr(moe_experts, entry, stand_in)
        telemetry.reset()
        result = probe_step(args, form, reads_all, config, cfg, kv, cache,
                            device, feed)
        with open(os.path.join(out_dir, "decode_step_probe.jsonl"),
                  "a") as fp:
            fp.write(json.dumps(result) + "\n")
        print(json.dumps(result))
    setattr(moe_experts, entry, block_experts)
    return 0 if device.platform == "tpu" or args.compile_only else 2


if __name__ == "__main__":
    sys.exit(main())
