"""Large-scale sparse parameter server — PSLib/Downpour analog.

Parity target (SURVEY.md §2.5 "Large-scale sparse PS"): the reference hosts
huge embedding tables on pserver-side sparse tables; DownpourWorker pulls the
rows its batch touches before the op loop and pushes per-row grads after
(framework/fleet/fleet_wrapper.h:55-150, downpour_worker.cc).  The dense
network never materializes the full table.

TPU-native shape of the same idea: the compiled XLA step stays pure — it
computes on a small [U, D] matrix of *pulled rows* fed like data, with batch
ids remapped to [0, U).  The runtime does pull (RPC gather) before the step
and push (per-row grad scatter + server-side SGD/Adagrad) after, over the
same native C++ tensor transport the dense PS uses
(native/csrc/tensor_rpc.cc).

Sharding: rows are routed to servers by ``id % num_servers`` (the
reference's RoundRobin ps_dispatcher over row sections).

Protocol (all vars namespaced by table name):
  client->server  SEND  "<tbl>.pull_ids@<client>#<seq>"   int64 [K]
  server->client  GET   "<tbl>.rows@<client>#<seq>"       float [K, D]
  client->server  SEND  "<tbl>.push_ids@<client>#<seq>" + ".push_grads@..."
COMPLETE shuts the server down (fleet.stop_worker analog).
"""

import collections
import threading

import numpy as np

from ..native.rpc import RpcClient, RpcServer, EV_COMPLETE, EV_SEND

__all__ = ["SparseTableServer", "SparseTableClient", "DistributedEmbedding"]


class SparseTableServer:
    """One shard of a sparse embedding table + its optimizer state.

    Rows are lazily initialized on first touch (uniform [-scale, scale]) —
    PSLib tables do the same so the full vocab never has to be allocated
    up front.  Supported optimizers: sgd, adagrad (DownpourSparseTable's
    default rule)."""

    def __init__(self, port, dim, optimizer="adagrad", lr=0.05,
                 init_scale=0.01, seed=0):
        self.server = RpcServer(port)
        self.port = self.server.port
        self.dim = dim
        self.lr = lr
        self.optimizer = optimizer
        self.init_scale = init_scale
        self.rows = {}            # global id -> np[D]
        self.g2sum = {}           # adagrad accumulator
        self.rng = np.random.RandomState(seed)
        self._thread = None

    # -- row access -----------------------------------------------------------

    def _row(self, gid):
        r = self.rows.get(gid)
        if r is None:
            r = self.rng.uniform(-self.init_scale, self.init_scale,
                                 self.dim).astype(np.float32)
            self.rows[gid] = r
        return r

    def _update(self, gid, grad):
        r = self._row(gid)
        if self.optimizer == "adagrad":
            acc = self.g2sum.get(gid, 0.0) + float(np.sum(grad * grad))
            self.g2sum[gid] = acc
            r -= self.lr / np.sqrt(acc + 1e-10) * grad
        else:  # sgd
            r -= self.lr * grad

    # -- event loop -----------------------------------------------------------

    def run(self):
        """Blocking poll loop; returns after COMPLETE or shutdown."""
        self.server.serve(True)
        pending_push = {}
        last_rows_var = {}   # client tag prefix -> last published var name
        while True:
            t, name, arr = self.server.poll()
            if t == 0 or t == EV_COMPLETE:
                return
            if t != EV_SEND:
                continue
            tbl, rest = name.split(".", 1)
            kind, tag = rest.split("@", 1)
            if kind == "pull_ids":
                ids = arr.astype(np.int64).reshape(-1)
                out = np.stack([self._row(int(g)) for g in ids]) \
                    if len(ids) else np.zeros((0, self.dim), np.float32)
                var = "%s.rows@%s" % (tbl, tag)
                self.server.set_var(var, out)
                # GC the previous pull's published rows for this client —
                # pulls are sequential per client, so seq-1 was consumed
                # before seq was requested (cf. dense PS version GC,
                # distributed/ps.py publish())
                client = tag.split("#", 1)[0]
                prev = last_rows_var.get((tbl, client))
                if prev is not None and prev != var:
                    self.server.del_var(prev)
                last_rows_var[(tbl, client)] = var
            elif kind == "push_ids":
                if len(pending_push) > 1024:
                    pending_push.pop(next(iter(pending_push)))  # orphan cap
                pending_push[tag] = arr.astype(np.int64).reshape(-1)
            elif kind == "push_grads":
                ids = pending_push.pop(tag, None)
                if ids is not None:
                    g = arr.reshape(len(ids), self.dim)
                    for i, gid in enumerate(ids):
                        self._update(int(gid), g[i])

    def start_thread(self):
        self._thread = threading.Thread(target=self.run, daemon=True)
        self._thread.start()
        return self._thread

    def shutdown(self):
        self.server.shutdown()


class SparseTableClient:
    """Trainer-side pull/push routing ids to shards by id % n_servers
    (FleetWrapper::PullSparseVarsSync / PushSparseVarsAsync analog)."""

    _instance_counter = __import__("itertools").count()

    def __init__(self, table, endpoints, client_id=None):
        import os

        self.table = table
        self.clients = [RpcClient(ep) for ep in endpoints]
        self.n = len(endpoints)
        # default id is unique across processes (pid) AND across instances
        # within one process (counter) so pull/push tags never collide
        if client_id is None:
            client_id = "%d-%d" % (os.getpid(),
                                   next(SparseTableClient._instance_counter))
        self.client_id = client_id
        self._seq = 0

    def pull(self, ids):
        """ids: int array of global row ids -> rows [len(ids), D] in order."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        self._seq += 1
        tag = "%s#%d" % (self.client_id, self._seq)
        per = [ids[ids % self.n == s] for s in range(self.n)]
        for s, cl in enumerate(self.clients):
            cl.send_var("%s.pull_ids@%s" % (self.table, tag), per[s])
        out = None
        for s, cl in enumerate(self.clients):
            rows = cl.get_var("%s.rows@%s" % (self.table, tag))
            if out is None:
                out = np.zeros((len(ids), rows.shape[1]), np.float32)
            pos = np.nonzero(ids % self.n == s)[0]
            out[pos] = rows
        return out

    def push(self, ids, grads):
        ids = np.asarray(ids, np.int64).reshape(-1)
        grads = np.asarray(grads, np.float32).reshape(len(ids), -1)
        self._seq += 1
        tag = "%s#%d" % (self.client_id, self._seq)
        for s, cl in enumerate(self.clients):
            m = ids % self.n == s
            cl.send_var("%s.push_ids@%s" % (self.table, tag), ids[m])
            cl.send_var("%s.push_grads@%s" % (self.table, tag), grads[m])

    def complete(self):
        for cl in self.clients:
            cl.complete()

    def close(self):
        for cl in self.clients:
            cl.close()


class DistributedEmbedding:
    """Program wiring for a PS-hosted embedding (DownpourWorker flow).

    Build phase (inside program_guard)::

        demb = DistributedEmbedding("emb_tbl", dim=16)
        out = demb.lookup(ids_var, batch_ids_max=64)   # [B, D] variable
        ... rest of the network; loss.minimize(...)

    Run phase, per step (ids = numpy [B] int64)::

        feed, info = demb.prepare_feed(ids)            # pulls rows via RPC
        outs = exe.run(main, feed={**data_feed, **feed},
                       fetch_list=[loss, demb.grad_var(main)])
        demb.push_grads(info, outs[-1])                # pushes row grads

    The step computes with the pulled [U, D] rows only; the full table
    lives on the sparse servers."""

    def __init__(self, table, dim, client=None):
        self.table = table
        self.dim = dim
        self.client = client
        self.rows_name = table + "@rows"
        self.local_ids_name = table + "@local_ids"
        self.max_rows = None

    def lookup(self, ids_var, batch_ids_max):
        """batch_ids_max: static upper bound on unique ids per batch (rows
        are zero-padded to it so the compiled step keeps one shape)."""
        import paddle_tpu as fluid

        self.max_rows = batch_ids_max
        rows = fluid.layers.data(self.rows_name,
                                 shape=[batch_ids_max, self.dim],
                                 append_batch_size=False,
                                 stop_gradient=False)
        local = fluid.layers.data(self.local_ids_name, shape=[],
                                  dtype="int64")  # [B] batch-sized
        out = fluid.layers.gather(rows, local)
        return out

    def lookup_bag(self, batch_size, bag_size, batch_ids_max):
        """Bagged (multi-hot) lookup: each sample carries up to `bag_size`
        feature ids; the step computes Out[b] = sum of that sample's rows —
        the recommender read pattern.  Emits ONE `embedding_bag` op over
        the pulled [batch_ids_max, D] rows with [B, K] local ids (-1 pads
        ragged bags), a masked take + sum.  Feed with
        prepare_feed_bags()."""
        import paddle_tpu as fluid
        from ..layer_helper import LayerHelper

        self.max_rows = batch_ids_max
        self.bag_size = bag_size
        rows = fluid.layers.data(self.rows_name,
                                 shape=[batch_ids_max, self.dim],
                                 append_batch_size=False,
                                 stop_gradient=False)
        local = fluid.layers.data(self.local_ids_name,
                                  shape=[batch_size, bag_size],
                                  dtype="int64", append_batch_size=False)
        helper = LayerHelper("embedding_bag", name=self.table + "_bag")
        out = helper.create_variable_for_type_inference(rows.dtype)
        helper.append_op(
            type="embedding_bag",
            inputs={"W": [rows], "Ids": [local]},
            outputs={"Out": [out]},
            attrs={"mode": "sum"},
        )
        return out

    def prepare_feed_bags(self, bags):
        """Pull rows for ragged per-sample id bags; returns
        (feed_dict, push_info).  `bags`: sequence of B id sequences (each
        at most bag_size long); shorter bags are -1-padded."""
        if self.max_rows is None or getattr(self, "bag_size", None) is None:
            raise RuntimeError("call lookup_bag() during program build first")
        flat = np.concatenate(
            [np.asarray(b, np.int64).reshape(-1) for b in bags]) \
            if len(bags) else np.zeros((0,), np.int64)
        uniq, inverse = np.unique(flat, return_inverse=True)
        U = len(uniq)
        if U > self.max_rows:
            raise ValueError(
                "batch touches %d unique rows > batch_ids_max=%d"
                % (U, self.max_rows))
        rows = self.client.pull(uniq)
        padded = np.zeros((self.max_rows, self.dim), np.float32)
        padded[:U] = rows
        local = np.full((len(bags), self.bag_size), -1, np.int64)
        off = 0
        for i, b in enumerate(bags):
            k = len(b)
            if k > self.bag_size:
                raise ValueError("bag %d has %d ids > bag_size=%d"
                                 % (i, k, self.bag_size))
            local[i, :k] = inverse[off:off + k]
            off += k
        return ({self.rows_name: padded,
                 self.local_ids_name: local},
                {"uniq": uniq, "n": U, "batch": len(bags)})

    def grad_var(self, program):
        name = self.rows_name + "@GRAD"
        return program.global_block().var(name)

    def prepare_feed(self, ids):
        """Pull touched rows; returns (feed_dict, push_info)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        uniq, inverse = np.unique(ids, return_inverse=True)
        U = len(uniq)
        if self.max_rows is None:
            raise RuntimeError("call lookup() during program build first")
        if U > self.max_rows:
            raise ValueError(
                "batch touches %d unique rows > batch_ids_max=%d"
                % (U, self.max_rows))
        rows = self.client.pull(uniq)
        # zero-pad to the static width so the compiled step keeps one shape
        padded = np.zeros((self.max_rows, self.dim), np.float32)
        padded[:U] = rows
        local = np.zeros((len(ids),), np.int64)
        local[:] = inverse
        # ids feed stays [B]; pad local ids width only if the consumer
        # declared the same static batch — here local ids length == batch
        return ({self.rows_name: padded,
                 self.local_ids_name: local},
                {"uniq": uniq, "n": U, "batch": len(ids)})

    def push_grads(self, info, rows_grad):
        g = np.asarray(rows_grad)[:info["n"]]
        self.client.push(info["uniq"], g)
