"""Parameter-server distributed training tests.

Mirrors the reference's localhost pattern (test_dist_base.py: N pservers +
N trainers on 127.0.0.1, loss/param parity vs local training, SURVEY.md
§4.6) — here pservers/trainers are threads sharing nothing but the C++ RPC
transport, and parity is exact: sync-PS SGD over 2 trainers with mean
aggregation must equal local SGD on the concatenated batch.
"""

import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.initializer import Constant


from dist_utils import free_ports as _free_ports  # noqa: E402


def _build(lr=0.1):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4])
        y = fluid.layers.data("y", shape=[1])
        pred = fluid.layers.fc(
            x, 1,
            param_attr=fluid.ParamAttr(initializer=Constant(0.1)),
            bias_attr=fluid.ParamAttr(initializer=Constant(0.0)))
        diff = fluid.layers.elementwise_sub(pred, y)
        loss = fluid.layers.reduce_mean(
            fluid.layers.elementwise_mul(diff, diff))
        fluid.optimizer.SGD(lr).minimize(loss)
    return main, startup, loss


def _make_data(steps, bs, seed):
    rng = np.random.RandomState(seed)
    w = np.array([[1.0], [-2.0], [0.5], [3.0]], "f")
    xs = rng.rand(steps, bs, 4).astype("f")
    ys = xs @ w + 0.1
    return xs, ys.astype("f")


def test_transpile_structure():
    main, startup, loss = _build()
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=0, program=main, startup_program=startup,
                pservers="127.0.0.1:7164,127.0.0.1:7165", trainers=2)
    tp = t.get_trainer_program()
    assert not any("sgd" == op.type for op in tp.global_block().ops)
    meta = tp._ps_trainer
    assert set(meta["param_to_ep"].values()) == {
        "127.0.0.1:7164", "127.0.0.1:7165"}  # 2 params spread over 2 servers
    for ep in ("127.0.0.1:7164", "127.0.0.1:7165"):
        sprog, sstart = t.get_pserver_programs(ep)
        assert sprog.global_block().ops[0].type == "listen_and_serv"
        opt_ops = sprog._ps_server["optimize_program"].global_block().ops
        assert any(op.type == "sgd" for op in opt_ops)
        assert len(sprog._ps_server["params"]) == 1
        assert len(sstart.global_block().ops) >= 1


def test_ps_training_matches_local():
    steps, bs = 8, 8
    eps = ["127.0.0.1:%d" % p for p in _free_ports(2)]
    xs, ys = _make_data(steps, 2 * bs, seed=7)

    # ---- local baseline on the full batch ---------------------------------
    main_l, startup_l, loss_l = _build()
    exe_l = fluid.Executor(fluid.CPUPlace())
    scope_l = fluid.Scope()
    with fluid.scope_guard(scope_l):
        exe_l.run(startup_l)
        for i in range(steps):
            exe_l.run(main_l, feed={"x": xs[i], "y": ys[i]},
                      fetch_list=[loss_l])
        params_local = {
            p.name: np.asarray(scope_l.find_var(p.name).get_tensor().numpy())
            for p in main_l.global_block().all_parameters()
        }

    # ---- distributed: 2 pservers + 2 trainers -----------------------------
    main, startup, loss = _build()
    pserver_threads = []
    pserver_errs = []

    def run_pserver(ep):
        try:
            t = fluid.DistributeTranspiler()
            t.transpile(trainer_id=0, program=main, startup_program=startup,
                        pservers=",".join(eps), trainers=2)
            prog, sprog = t.get_pserver_programs(ep)
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(sprog)
                exe.run(prog, scope=scope)
        except Exception as e:  # pragma: no cover
            pserver_errs.append(e)

    for ep in eps:
        th = threading.Thread(target=run_pserver, args=(ep,), daemon=True)
        th.start()
        pserver_threads.append(th)

    trainer_params = [None, None]
    trainer_errs = []

    def run_trainer(tid):
        try:
            t = fluid.DistributeTranspiler()
            t.transpile(trainer_id=tid, program=main,
                        startup_program=startup, pservers=",".join(eps),
                        trainers=2)
            tp = t.get_trainer_program()
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                half = slice(tid * bs, (tid + 1) * bs)
                for i in range(steps):
                    exe.run(tp, feed={"x": xs[i][half], "y": ys[i][half]},
                            fetch_list=[], scope=scope)
                trainer_params[tid] = {
                    p: np.asarray(scope.find_var(p).get_tensor().numpy())
                    for p in tp._ps_trainer["param_to_ep"]
                }
                scope._ps_comm.complete()
        except Exception as e:  # pragma: no cover
            trainer_errs.append(e)

    tthreads = [threading.Thread(target=run_trainer, args=(i,), daemon=True)
                for i in range(2)]
    for th in tthreads:
        th.start()
    for th in tthreads:
        th.join(timeout=120)
    for th in pserver_threads:
        th.join(timeout=30)
    assert not trainer_errs, trainer_errs
    assert not pserver_errs, pserver_errs
    assert trainer_params[0] is not None and trainer_params[1] is not None

    # both trainers hold identical params (sync PS), equal to local
    # training.  Param names differ between the two program builds (global
    # unique-name counter), so match positionally: sort by shape-then-name
    # (w is (4,1), b is (1,)).
    local_sorted = [params_local[k] for k in sorted(
        params_local, key=lambda n: (len(params_local[n].shape), n))]
    t0 = trainer_params[0]
    t0_sorted = [t0[k] for k in sorted(
        t0, key=lambda n: (len(t0[n].shape), n))]
    t1 = trainer_params[1]
    t1_sorted = [t1[k] for k in sorted(
        t1, key=lambda n: (len(t1[n].shape), n))]
    for a, b, c in zip(local_sorted, t0_sorted, t1_sorted):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(c, b, rtol=1e-6)


def test_async_ps_converges():
    """Async mode (no barriers, per-arrival updates): must converge on the
    linear task and shut down cleanly (reference AsyncCommunicator path)."""
    steps, bs = 40, 8
    eps = ["127.0.0.1:%d" % p for p in _free_ports(2)]
    xs, ys = _make_data(steps, 2 * bs, seed=11)
    main, startup, loss = _build(lr=0.02)
    errs = []

    def run_pserver(ep):
        try:
            t = fluid.DistributeTranspiler()
            t.transpile(trainer_id=0, program=main, startup_program=startup,
                        pservers=",".join(eps), trainers=2, sync_mode=False)
            prog, sprog = t.get_pserver_programs(ep)
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(sprog)
                exe.run(prog, scope=scope)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    for ep in eps:
        threading.Thread(target=run_pserver, args=(ep,), daemon=True).start()

    final = [None, None]

    def run_trainer(tid):
        try:
            t = fluid.DistributeTranspiler()
            t.transpile(trainer_id=tid, program=main,
                        startup_program=startup, pservers=",".join(eps),
                        trainers=2, sync_mode=False)
            tp = t.get_trainer_program()
            assert tp._ps_trainer["sync"] is False
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                # eval program: same forward, NO _ps_trainer metadata, so
                # an eval run neither sends grads nor trains on the batch
                eval_prog = tp.clone()
                if hasattr(eval_prog, "_ps_trainer"):
                    del eval_prog._ps_trainer

                def eval_loss():
                    lv = eval_prog.global_block().var(loss.name)
                    ev, = exe.run(eval_prog, feed={"x": xs[0][half],
                                                   "y": ys[0][half]},
                                  fetch_list=[lv], scope=scope)
                    return float(np.asarray(ev).ravel()[0])

                half = slice(tid * bs, (tid + 1) * bs)
                first = eval_loss()
                import time as _time

                for i in range(steps):
                    exe.run(tp, feed={"x": xs[i][half], "y": ys[i][half]},
                            fetch_list=[], scope=scope)
                    # async has no staleness bound: pace the trainer so the
                    # server's (jit-compiling) update loop can keep up —
                    # otherwise all 40 steps can finish against the initial
                    # params, which is legal async behavior but untestable
                    _time.sleep(0.02)
                final[tid] = (first, eval_loss())
                scope._ps_comm.complete()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=run_trainer, args=(i,), daemon=True)
          for i in range(2)]
    for th in ts:
        th.start()
    for th in ts:
        th.join(timeout=120)
    assert not errs, errs
    # eval loss on the fixed batch must drop well below its initial value
    for pair in final:
        assert pair is not None, final
        first, last = pair
        assert last < 0.75 * first, final


def test_async_eviction_reclaims_replay_state():
    """Async-mode eviction (ps.py run_async __evict__ handler): a trainer
    that stops heartbeating past FLAGS_worker_hb_timeout gets its replay-
    filter entry and liveness slot reclaimed, so a frame reusing its old
    (nonce, seq) tag is fresh again and applies.  A raw RpcClient plays the
    trainer so the dedupe tag is fully controlled."""
    import time

    from paddle_tpu.distributed import ps as ps_mod
    from paddle_tpu.native.rpc import RpcClient

    old_to = fluid.flags.flag("worker_hb_timeout")
    fluid.flags.set_flags({"FLAGS_worker_hb_timeout": 1.0})
    errs = []
    client = None
    try:
        ep = "127.0.0.1:%d" % _free_ports(1)[0]
        main, startup, loss = _build(lr=0.5)
        t = fluid.DistributeTranspiler()
        t.transpile(trainer_id=0, program=main, startup_program=startup,
                    pservers=ep, trainers=1, sync_mode=False)
        prog, sprog = t.get_pserver_programs(ep)
        grad_map = prog._ps_server["grad_map"]

        def run_pserver():
            try:
                exe = fluid.Executor(fluid.CPUPlace())
                scope = fluid.Scope()
                with fluid.scope_guard(scope):
                    exe.run(sprog)
                    exe.run(prog, scope=scope)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        th = threading.Thread(target=run_pserver, daemon=True)
        th.start()

        gname = next(iter(grad_map))
        pname = grad_map[gname]
        shape = tuple(main.global_block().var(pname).shape)
        g = np.ones(shape, "float32")
        pkey = ps_mod._vkey(pname, -1)
        client = RpcClient(ep)

        def wait_param(differs_from, timeout=20.0):
            deadline = time.time() + timeout
            while time.time() < deadline:
                cur = client.get_var(pkey)
                if not np.array_equal(cur, differs_from):
                    return cur
                time.sleep(0.05)
            return client.get_var(pkey)

        v0 = client.get_var(pkey)
        # heartbeat registers liveness, then one tagged grad applies
        hb = np.asarray([0], np.int64)
        client.send_var(ps_mod._HB_PREFIX + "0", hb)
        tag = "%s%s0:123:0" % (gname, ps_mod._SEQ_SEP)
        client.send_var(tag, g)
        v1 = wait_param(v0)
        assert not np.array_equal(v1, v0)

        # replayed frame (same tag, live trainer): at-most-once filter
        # drops it — the param must NOT move again
        client.send_var(tag, g)
        time.sleep(0.6)
        np.testing.assert_array_equal(client.get_var(pkey), v1)

        # go silent: no more heartbeats.  The checker thread evicts after
        # the 1s timeout, reclaiming the (tid 0) replay entry; from then
        # on the SAME tag is a fresh frame and applies.
        applied = False
        deadline = time.time() + 20.0
        v_prev = client.get_var(pkey)
        while time.time() < deadline:
            client.send_var(tag, g)
            time.sleep(0.4)
            cur = client.get_var(pkey)
            if not np.array_equal(cur, v_prev):
                applied = True
                break
        assert applied, "evicted trainer's tag never became fresh again"

        client.complete()
        th.join(timeout=30)
        assert not errs, errs
    finally:
        if client is not None:
            try:
                client.close()
            except Exception:
                pass
        fluid.flags.set_flags({"FLAGS_worker_hb_timeout": old_to})


@pytest.mark.parametrize("apply_delay_s", [0.0, 0.03])
def test_geo_sgd_converges(monkeypatch, apply_delay_s):
    """Geo-SGD: local training + periodic delta pushes; both trainers'
    params drift toward each other through the server merge and the task
    converges (reference geo_sgd_transpiler.py semantics).  With the
    server slow to apply what it has acknowledged (a loaded machine), a
    trainer still reads back params that hold its own delta: a pull that
    outran the apply made the next push add the same progress twice, and
    the task diverged."""
    if apply_delay_s:
        import time

        from paddle_tpu.native import rpc

        poll = rpc.RpcServer.poll

        def slow_poll(self):
            event = poll(self)
            time.sleep(apply_delay_s)
            return event

        monkeypatch.setattr(rpc.RpcServer, "poll", slow_poll)
    steps, bs, K = 24, 8, 4
    eps = ["127.0.0.1:%d" % p for p in _free_ports(1)]
    xs, ys = _make_data(steps, 2 * bs, seed=21)
    main, startup, loss = _build(lr=0.05)
    cfg = fluid.DistributeTranspilerConfig()
    cfg.geo_sgd_mode = True
    cfg.geo_sgd_need_push_nums = K
    errs = []

    def run_pserver(ep):
        try:
            t = fluid.DistributeTranspiler(config=cfg)
            t.transpile(trainer_id=0, program=main, startup_program=startup,
                        pservers=",".join(eps), trainers=2)
            prog, sprog = t.get_pserver_programs(ep)
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(sprog)
                exe.run(prog, scope=scope)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threading.Thread(target=run_pserver, args=(eps[0],), daemon=True).start()
    final = [None, None]

    def run_trainer(tid):
        try:
            t = fluid.DistributeTranspiler(config=cfg)
            t.transpile(trainer_id=tid, program=main,
                        startup_program=startup, pservers=",".join(eps),
                        trainers=2)
            tp = t.get_trainer_program()
            # geo keeps the optimizer in the trainer program
            assert any(op.type == "sgd"
                       for op in tp.global_block().ops)
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                half = slice(tid * bs, (tid + 1) * bs)
                # fixed-batch eval through a non-PS clone (no sends, no
                # local update) — per-batch losses are too noisy to gate on
                eval_prog = tp.clone(for_test=True)
                if hasattr(eval_prog, "_ps_trainer"):
                    del eval_prog._ps_trainer

                def eval_loss():
                    lv = eval_prog.global_block().var(loss.name)
                    ev, = exe.run(eval_prog, feed={"x": xs[0][half],
                                                   "y": ys[0][half]},
                                  fetch_list=[lv], scope=scope)
                    return float(np.asarray(ev).ravel()[0])

                first = eval_loss()
                for i in range(steps):
                    exe.run(tp, feed={"x": xs[i][half], "y": ys[i][half]},
                            fetch_list=[], scope=scope)
                final[tid] = (first, eval_loss())
                scope._ps_comm.complete()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=run_trainer, args=(i,), daemon=True)
          for i in range(2)]
    for th in ts:
        th.start()
    for th in ts:
        th.join(timeout=120)
    assert not errs, errs
    for pair in final:
        assert pair is not None, final
        first, last = pair
        assert last < 0.6 * first, final
