"""Program-level IR passes (parity: paddle/fluid/framework/ir/ —
ir::Pass + PassRegistry, ir/pass.h:38).

Most of the reference's ~75 passes dissolve into XLA (fusion, memory reuse,
placement).  What remains meaningful at the program level are
*graph-rewriting* optimizations whose benefit XLA cannot recover because
they change the parameter values themselves or delete stateful ops:

- conv_bn_fuse_pass (ir/conv_bn_fuse_pass.cc): fold an inference-mode
  batch_norm into the preceding conv2d's weights/bias.  Removes the BN op
  and its four parameter reads entirely.
- delete_dropout_pass (delete_dropout_op_pass): drop is_test dropout ops
  (identity at inference).

Passes run on (Program, Scope) pairs — the scope carries the parameter
values a folding pass rewrites, mirroring how the reference's passes read
the global scope for persistables."""

import numpy as np

__all__ = ["Pass", "register_pass", "get_pass", "apply_pass", "all_passes"]

_PASS_REGISTRY = {}


class Pass:
    """Base class (ir/pass.h:38 analog): override apply(program, scope).

    `protected` holds variable names a pass must keep PRODUCED (feed/fetch
    targets of a loaded inference model — fetch ops are stripped at load,
    io.py _strip_feed_fetch, so fetched vars have no op consumers and
    would otherwise look swallowable)."""

    name = None
    protected = frozenset()

    def apply(self, program, scope):
        raise NotImplementedError


def register_pass(name):
    def deco(cls):
        cls.name = name
        _PASS_REGISTRY[name] = cls
        return cls

    return deco


def get_pass(name):
    return _PASS_REGISTRY[name]()


def all_passes():
    return sorted(_PASS_REGISTRY)


def apply_pass(name, program, scope, protected=()):
    """Apply one registered pass in place; returns the program.
    `protected`: var names that must stay produced (fetch targets)."""
    p = get_pass(name)
    p.protected = frozenset(protected)
    p.apply(program, scope)
    return program


def _build_consumers(block):
    """name -> [ops reading it] (shared by the fusion passes)."""
    consumers = {}
    for op in block.ops:
        for n in op.input_arg_names:
            consumers.setdefault(n, []).append(op)
    return consumers


@register_pass("delete_dropout_pass")
class DeleteDropoutPass(Pass):
    """Replace is_test dropout ops with `assign` (identity).  Using assign
    instead of deleting + rewiring keeps every output var produced — fetch
    targets and chained dropouts stay valid — and XLA folds the copy."""

    def apply(self, program, scope):
        from .framework import Operator

        block = program.global_block()
        new_ops = []
        for op in block.ops:
            if (op.type == "dropout" and op.attrs.get("is_test")
                    and op.attrs.get("dropout_implementation")
                    == "upscale_in_train"):
                # upscale_in_train is identity at test time; downgrade
                # mode rescales, so only upscale is replaceable
                new_ops.append(Operator(
                    block, type="assign",
                    inputs={"X": [op.input("X")[0]]},
                    outputs={"Out": [op.output("Out")[0]]}, attrs={}))
            else:
                new_ops.append(op)
        block.ops = new_ops
        program._bump_version()


@register_pass("conv_bn_fuse_pass")
class ConvBNFusePass(Pass):
    """Fold inference batch_norm into the preceding conv2d
    (ir/conv_bn_fuse_pass.cc): W' = W * gamma/std (per out-channel),
    b' = beta - mean * gamma/std; the BN op is replaced by one
    elementwise_add of b'."""

    def apply(self, program, scope):
        block = program.global_block()
        # conv output name -> conv op, only when that output feeds exactly
        # one consumer (the BN)
        consumers = _build_consumers(block)
        filter_uses = {}
        for op in block.ops:
            if op.type == "conv2d":
                f = op.input("Filter")[0]
                filter_uses[f] = filter_uses.get(f, 0) + 1

        new_ops = []
        i = 0
        ops = block.ops
        while i < len(ops):
            op = ops[i]
            fused = False
            if op.type == "conv2d":
                out = op.output("Output")[0]
                cons = consumers.get(out, [])
                w_name = op.input("Filter")[0]
                # a filter shared by several convs (siamese nets) can't be
                # folded — scaling it would corrupt the other conv
                if (len(cons) == 1 and cons[0].type == "batch_norm"
                        and cons[0].attrs.get("is_test")
                        and filter_uses.get(w_name, 0) == 1):
                    bn = cons[0]
                    names = {s: bn.input(s)[0] for s in
                             ("Scale", "Bias", "Mean", "Variance")}
                    vals = {}
                    ok = True
                    for s, n in names.items():
                        v = scope.find_var(n)
                        if v is None or not v.get_tensor()._is_initialized():
                            ok = False
                            break
                        vals[s] = np.asarray(v.get_tensor().numpy())
                    wvar = scope.find_var(w_name)
                    if ok and wvar is not None and \
                            wvar.get_tensor()._is_initialized():
                        eps = float(bn.attrs.get("epsilon", 1e-5))
                        std = np.sqrt(vals["Variance"] + eps)
                        factor = vals["Scale"] / std          # [O]
                        W = np.asarray(wvar.get_tensor().numpy())
                        wvar.get_tensor().set(
                            (W * factor.reshape(-1, 1, 1, 1)).astype(W.dtype))
                        bias = vals["Bias"] - vals["Mean"] * factor
                        # keyed by the BN output: unique per fused pair
                        bias_name = bn.output("Y")[0] + "@bn_fused_bias"
                        bvar = block.create_var(
                            name=bias_name, shape=[len(bias)],
                            dtype="float32", persistable=True)
                        scope.var(bias_name).set(bias.astype("float32"))
                        bn_out = bn.output("Y")[0]
                        from .framework import Operator

                        add = Operator(
                            block, type="elementwise_add",
                            inputs={"X": [out], "Y": [bias_name]},
                            outputs={"Out": [bn_out]},
                            attrs={"axis": 1})
                        new_ops.append(op)
                        new_ops.append(add)
                        i += 1
                        # skip every op up to and including the BN (they
                        # are contiguous in topological emit order)
                        while ops[i] is not bn:
                            new_ops.append(ops[i])
                            i += 1
                        i += 1  # past the bn
                        fused = True
            if not fused:
                new_ops.append(op)
                i += 1
        block.ops = new_ops
        program._bump_version()


@register_pass("fc_fuse_pass")
class FCFusePass(Pass):
    """Fuse mul(X, W) + elementwise_add(., b) [+ relu] into one `fc` op
    (ir/fc_fuse_pass.cc).  Conditions mirror the reference pattern: the mul
    output feeds exactly the add, the bias is a 1-D persistable, and (for
    the act variant) the add output feeds exactly the relu."""

    def apply(self, program, scope):
        from .framework import Operator

        block = program.global_block()
        consumers = _build_consumers(block)

        def only_consumer(name, want_type):
            cons = consumers.get(name, [])
            if (len(cons) == 1 and cons[0].type == want_type
                    and name not in self.protected):
                return cons[0]
            return None

        skip = set()
        new_ops = []
        for op in block.ops:
            if id(op) in skip:
                continue
            if (op.type == "mul"
                    and int(op.attrs.get("y_num_col_dims", 1)) == 1):
                mul_out = op.output("Out")[0]
                add = only_consumer(mul_out, "elementwise_add")
                if add is not None:
                    b_name = add.input("Y")[0]
                    bvar = block._find_var_recursive(b_name)
                    # bias must broadcast along the LAST dim (fc semantics):
                    # for a 2-D mul output that is axis -1 or 1
                    axis_ok = int(add.attrs.get("axis", -1)) in (-1, 1)
                    if (bvar is not None and bvar.persistable
                            and bvar.shape is not None
                            and len(bvar.shape) == 1 and axis_ok
                            and add.input("X")[0] == mul_out):
                        act = ""
                        out_name = add.output("Out")[0]
                        relu = only_consumer(out_name, "relu")
                        tail_ops = [add]
                        if relu is not None:
                            act = "relu"
                            out_name = relu.output("Out")[0]
                            tail_ops.append(relu)
                        new_ops.append(Operator(
                            block, type="fc",
                            inputs={"Input": [op.input("X")[0]],
                                    "W": [op.input("Y")[0]],
                                    "Bias": [b_name]},
                            outputs={"Out": [out_name]},
                            attrs={"in_num_col_dims": int(op.attrs.get(
                                "x_num_col_dims", 1)),
                                "activation_type": act}))
                        skip.update(id(t) for t in tail_ops)
                        continue
            new_ops.append(op)
        block.ops = new_ops
        program._bump_version()


@register_pass("repeated_fc_relu_fuse_pass")
class RepeatedFCReluFusePass(Pass):
    """Fuse chains of relu-activated `fc` ops into one
    fusion_repeated_fc_relu op (ir/repeated_fc_relu_fuse_pass.cc).  The
    fused kernel applies fc+bias+relu to EVERY layer
    (fusion_repeated_fc_relu_op.cc:118-139), so only all-relu chains are
    eligible; a terminal plain fc stays unfused.  Run after fc_fuse_pass,
    which creates the fc ops this pass stitches."""

    MIN_CHAIN = 2

    def apply(self, program, scope):
        from .framework import Operator

        block = program.global_block()
        consumers = _build_consumers(block)
        producers = {}
        for op in block.ops:
            for n in op.output_arg_names:
                producers[n] = op

        def _eligible(o):
            # fusion_repeated_fc_relu does raw x @ w (no flattening) and
            # requires a Bias per fc: only fuse plain 2-D fcs with bias
            if int(o.attrs.get("in_num_col_dims", 1)) != 1:
                return False
            if not o.input("Bias"):
                return False
            v = block._find_var_recursive(o.input("Input")[0])
            return (v is not None and v.shape is not None
                    and len(v.shape) == 2)

        chains = []  # list of op lists
        used = set()
        for op in block.ops:
            if op.type != "fc" or id(op) in used:
                continue
            # only start a chain at a relu-activated fc whose producer
            # could NOT itself chain into it (true chain head): the skip
            # must mirror the extension conditions below, else a producer
            # with a multi-consumer/protected output blocks its consumer
            # from heading a valid chain
            if op.attrs.get("activation_type") != "relu":
                continue
            if not _eligible(op):
                continue
            in_name = op.input("Input")[0]
            prev = producers.get(in_name)
            if (prev is not None and prev.type == "fc"
                    and prev.attrs.get("activation_type") == "relu"
                    and _eligible(prev)
                    and len(consumers.get(in_name, [])) == 1
                    and in_name not in self.protected):
                continue
            chain = [op]
            cur = op
            while True:
                out_n = cur.output("Out")[0]
                nxt_cons = consumers.get(out_n, [])
                if (len(nxt_cons) != 1 or nxt_cons[0].type != "fc"
                        or out_n in self.protected
                        or not _eligible(nxt_cons[0])
                        or nxt_cons[0].attrs.get(
                            "activation_type") != "relu"):
                    break
                cur = nxt_cons[0]
                chain.append(cur)
            if len(chain) >= self.MIN_CHAIN:
                chains.append(chain)
                used.update(id(o) for o in chain)

        if not chains:
            return
        replaced = {}
        for chain in chains:
            head, tail = chain[0], chain[-1]
            relu_outs = [o.output("Out")[0] + "@fused_relu"
                         for o in chain[:-1]]
            for n in relu_outs:
                block.create_var(name=n)
            fused = Operator(
                block, type="fusion_repeated_fc_relu",
                inputs={"X": [head.input("Input")[0]],
                        "W": [o.input("W")[0] for o in chain],
                        "Bias": [o.input("Bias")[0] for o in chain]},
                outputs={"ReluOut": relu_outs,
                         "Out": [tail.output("Out")[0]]})
            replaced[id(head)] = fused
            for o in chain[1:]:
                replaced[id(o)] = None
        _commit_replacements(program, block, replaced)


def _sole_consumer(consumers, name, protected):
    """The single op reading `name`, or None if 0/many or protected."""
    cons = consumers.get(name, [])
    if len(cons) != 1 or name in protected:
        return None
    return cons[0]


def _commit_replacements(program, block, replaced):
    """Rewrite block.ops from a {id(op): new_op|None} map (None deletes;
    missing keeps) and bump the program version.  Shared epilogue of the
    fusion passes."""
    if not replaced:
        return
    block.ops = [replaced.get(id(op), op) for op in block.ops
                 if replaced.get(id(op), op) is not None]
    program._bump_version()


@register_pass("multihead_matmul_fuse_pass")
class MultiheadMatmulFusePass(Pass):
    """Rewrite composed scaled-dot-product attention into the fused
    `flash_attention` op (the TPU-native analog of
    ir/multihead_matmul_fuse_pass.cc constructing multihead_matmul_op.cu).

    Pattern (the repo's own layer emission, models/bert.py and
    nets.scaled_dot_product_attention):

        matmul(Q, K, transpose_Y=True[, alpha])
          -> [elementwise_add(scores, mask)]
          -> softmax
          -> [assign        # residue of delete_dropout_pass]
          -> matmul(probs, V)

    with Q/K/V rank-4 [B, H, S, D].  Replaced by one flash_attention op
    (Pallas blockwise kernel above the measured seq cutoff, XLA-fused jnp
    composition below it — either way >= the op-at-a-time composition).
    alpha becomes the kernel scale; alpha == 1.0 passes scale=1.0 ("already
    scaled", e.g. a separate upstream scale op) rather than the 1/sqrt(d)
    default that scale=0.0 selects."""

    def apply(self, program, scope):
        from .framework import Operator

        block = program.global_block()
        consumers = _build_consumers(block)

        def rank(name):
            v = block._find_var_recursive(name)
            return None if v is None or v.shape is None else len(v.shape)

        matches = []
        for op in block.ops:
            if op.type != "matmul":
                continue
            if not op.attrs.get("transpose_Y") or op.attrs.get(
                    "transpose_X"):
                continue
            q_name, k_name = op.input("X")[0], op.input("Y")[0]
            if rank(q_name) != 4 or rank(k_name) != 4:
                continue
            chain = [op]
            mask_name = None
            cur = _sole_consumer(consumers, op.output("Out")[0],
                                 self.protected)
            if cur is not None and cur.type == "elementwise_add":
                if cur.input("X")[0] != op.output("Out")[0]:
                    continue  # scores must be the X side
                if rank(cur.input("Y")[0]) != 4:
                    continue  # kernel bias contract: [B, 1|H, Sq, Sk]
                mask_name = cur.input("Y")[0]
                chain.append(cur)
                cur = _sole_consumer(consumers, cur.output("Out")[0],
                                     self.protected)
            if cur is None or cur.type != "softmax":
                continue
            ax = cur.attrs.get("axis", -1)
            if ax not in (-1, 3):
                continue
            chain.append(cur)
            cur = _sole_consumer(consumers, cur.output("Out")[0],
                                 self.protected)
            while cur is not None and cur.type == "assign":
                chain.append(cur)
                cur = _sole_consumer(consumers, cur.output("Out")[0],
                                     self.protected)
            if (cur is None or cur.type != "matmul"
                    or cur.attrs.get("transpose_X")
                    or cur.attrs.get("transpose_Y")
                    or float(cur.attrs.get("alpha", 1.0)) != 1.0
                    or cur.input("X")[0] != chain[-1].output("Out")[0]):
                continue
            v_name = cur.input("Y")[0]
            if rank(v_name) != 4:
                continue
            chain.append(cur)
            matches.append((chain, q_name, k_name, v_name, mask_name))

        if not matches:
            return
        replaced = {}
        for chain, q_name, k_name, v_name, mask_name in matches:
            alpha = float(chain[0].attrs.get("alpha", 1.0))
            inputs = {"Q": [q_name], "K": [k_name], "V": [v_name]}
            if mask_name is not None:
                inputs["BiasQK"] = [mask_name]
            fused = Operator(
                block, type="flash_attention", inputs=inputs,
                outputs={"Out": [chain[-1].output("Out")[0]]},
                attrs={"causal": False, "scale": alpha})
            replaced[id(chain[0])] = fused
            for o in chain[1:]:
                replaced[id(o)] = None
        _commit_replacements(program, block, replaced)


@register_pass("fuse_elewise_add_act_pass")
class FuseElewiseAddActPass(Pass):
    """elementwise_add -> {relu,tanh,sigmoid} becomes one
    fused_elemwise_activation op (ir/fuse_elewise_add_act_pass.cc)."""

    ACTS = ("relu", "tanh", "sigmoid")

    def apply(self, program, scope):
        from .framework import Operator

        block = program.global_block()
        consumers = _build_consumers(block)
        replaced = {}
        for op in block.ops:
            if op.type != "elementwise_add" or id(op) in replaced:
                continue
            nxt = _sole_consumer(consumers, op.output("Out")[0],
                                 self.protected)
            if nxt is None or nxt.type not in self.ACTS:
                continue
            if id(nxt) in replaced:
                continue
            fused = Operator(
                block, type="fused_elemwise_activation",
                inputs={"X": [op.input("X")[0]],
                        "Y": [op.input("Y")[0]]},
                outputs={"Out": [nxt.output("Out")[0]],
                         "IntermediateOut": [op.output("Out")[0]]},
                attrs={"functor_list": [nxt.type, "elementwise_add"],
                       "axis": int(op.attrs.get("axis", -1)),
                       "save_intermediate_out": True})
            replaced[id(op)] = fused
            replaced[id(nxt)] = None
        _commit_replacements(program, block, replaced)


@register_pass("seqpool_concat_fuse_pass")
class SeqPoolConcatFusePass(Pass):
    """N sequence_pool(pooltype) branches feeding one concat fuse into
    fusion_seqpool_concat (ir/seqpool_concat_fuse_pass.cc)."""

    POOLTYPES = ("SUM", "AVERAGE", "SQRT")

    def apply(self, program, scope):
        from .framework import Operator

        block = program.global_block()
        consumers = _build_consumers(block)
        producers = {}
        for op in block.ops:
            for n in op.output_arg_names:
                producers[n] = op
        replaced = {}
        for op in block.ops:
            if op.type != "concat" or id(op) in replaced:
                continue
            if int(op.attrs.get("axis", 0)) not in (1, -1):
                continue
            branches = []
            pooltype = None
            ok = True
            for n in op.input("X"):
                prod = producers.get(n)
                if (prod is None or prod.type != "sequence_pool"
                        or id(prod) in replaced
                        or prod.input("Length")
                        or _sole_consumer(consumers, n,
                                          self.protected) is not op):
                    ok = False
                    break
                # pooled output must be rank-2 (input [B, T, D]) so the
                # fused op's axis=-1 concat equals this concat's axis=1
                xv = block._find_var_recursive(prod.input("X")[0])
                if xv is None or xv.shape is None or len(xv.shape) != 3:
                    ok = False
                    break
                pt = prod.attrs.get("pooltype", "AVERAGE").upper()
                if pt not in self.POOLTYPES or (pooltype is not None
                                                and pt != pooltype):
                    ok = False
                    break
                pooltype = pt
                branches.append(prod)
            if not ok or len(branches) < 2:
                continue
            fused = Operator(
                block, type="fusion_seqpool_concat",
                inputs={"X": [b.input("X")[0] for b in branches]},
                outputs={"Out": [op.output("Out")[0]]},
                attrs={"pooltype": pooltype, "axis": 1})
            replaced[id(op)] = fused
            for b in branches:
                replaced[id(b)] = None
        _commit_replacements(program, block, replaced)


@register_pass("fuse_optimizer_ops_pass")
class FuseOptimizerOpsPass(Pass):
    """Coalesce per-parameter optimizer ops into one fused update
    (ir/fuse_optimizer_ops_pass.cc + coalesce_tensor: fuse_adam /
    fuse_sgd / fuse_momentum).  Groups ops of one type sharing the same
    hyperparameter attrs + LearningRate var + param dtype; each group
    becomes one fused_<type> op over duplicable input/output lists, placed
    at the LAST member's position.  A group is skipped when a non-member
    op between the first and last member reads or writes any of the
    group's state vars, or WRITES the shared LearningRate var (ordering
    hazards), or when adam uses per-op beta tensors.  Divergent adam
    beta-pow accumulators are safe: fused_adam applies each member's own
    bias correction."""

    MIN_GROUP = 4
    # Only params of rank <= this fuse: a vector (bias, LayerNorm or BN
    # scale) is linear-layout, so the fused lowering's concat into one flat
    # buffer is a plain copy of a few KB, and a launch of its own would
    # cost more than its bytes (round 3: 315 tiny per-weight updates took
    # ~46 ms of a 211 ms ResNet-50 step).  A matrix or a 4-D conv kernel
    # lies in (8, 128) tiles: flattening it relays it as well as copying
    # it, and its plain op is one elementwise fusion that XLA folds into
    # the matmul producing its gradient.  Measured, not a setting: round 3,
    # fuse-everything = 1786 img/s vs 2200 unfused on ResNet-50; PR 54,
    # BERT-base's 77 matrices in the flat buffers cost 22 ms of a 228 ms
    # step on the chip (PERF.md section 6).  core/world_analysis.py reads
    # the same constant for its prediction of the flat temps.
    MAX_FUSED_RANK = 1
    _STATE_SLOTS = {
        "sgd": ("Param", "Grad"),
        "momentum": ("Param", "Grad", "Velocity"),
        "adam": ("Param", "Grad", "Moment1", "Moment2", "Beta1Pow",
                 "Beta2Pow"),
    }
    _FUSED_ATTRS = {
        "sgd": (),
        "momentum": ("mu", "use_nesterov", "regularization_method",
                     "regularization_coeff"),
        "adam": ("beta1", "beta2", "epsilon"),
    }
    _META_ATTRS = frozenset({"op_role", "op_role_var", "op_namescope",
                             "op_callstack", "op_device"})

    def apply(self, program, scope):
        from .framework import Operator

        block = program.global_block()
        pos = {id(op): i for i, op in enumerate(block.ops)}
        groups = {}
        for op in block.ops:
            if op.type not in self._STATE_SLOTS:
                continue
            if op.type == "adam" and (op.input("Beta1Tensor")
                                      or op.input("Beta2Tensor")):
                continue
            pv = block._find_var_recursive(op.input("Param")[0])
            if (pv is None or pv.shape is None
                    or len(pv.shape) > self.MAX_FUSED_RANK):
                continue
            attrs_key = tuple(
                (k, tuple(v) if isinstance(v, list) else v)
                for k, v in sorted(op.attrs.items())
                if k not in self._META_ATTRS)
            key = (op.type, op.input("LearningRate")[0], pv.dtype,
                   attrs_key)
            groups.setdefault(key, []).append(op)

        replaced = {}
        for (op_type, lr_name, _dt, _ak), ops in groups.items():
            if len(ops) < self.MIN_GROUP:
                continue
            slots = self._STATE_SLOTS[op_type]
            state = set()
            for o in ops:
                for s in slots:
                    state.update(o.input(s))
                state.update(o.output_arg_names)
            if state & self.protected:
                continue
            member = set(id(o) for o in ops)
            lo = min(pos[id(o)] for o in ops)
            hi = max(pos[id(o)] for o in ops)
            hazard = False
            for other in block.ops[lo:hi + 1]:
                if id(other) in member:
                    continue
                touched = set(other.input_arg_names) | set(
                    other.output_arg_names)
                # a write to the shared LR between members would make the
                # single fused read diverge from the unfused sequence
                if (touched & state
                        or lr_name in other.output_arg_names):
                    hazard = True
                    break
            if hazard:
                continue
            inputs = {s: [o.input(s)[0] for o in ops] for s in slots}
            inputs["LearningRate"] = [lr_name]
            out_slot_map = {"sgd": ("ParamOut",),
                            "momentum": ("ParamOut", "VelocityOut"),
                            "adam": ("ParamOut", "Moment1Out",
                                     "Moment2Out", "Beta1PowOut",
                                     "Beta2PowOut")}[op_type]
            outputs = {s: [o.output(s)[0] for o in ops]
                       for s in out_slot_map}
            attrs = {k: ops[0].attrs.get(k)
                     for k in self._FUSED_ATTRS[op_type]
                     if k in ops[0].attrs}
            fused = Operator(block, type="fused_" + op_type,
                             inputs=inputs, outputs=outputs, attrs=attrs)
            last = max(ops, key=lambda o: pos[id(o)])
            for o in ops:
                replaced[id(o)] = fused if o is last else None
        _commit_replacements(program, block, replaced)
