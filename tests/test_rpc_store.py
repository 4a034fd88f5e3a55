"""The native RPC store's contract (native/csrc/tensor_rpc.cc): a blocking
GET parks under the name it wants and is woken by a store of that name, by
the ``serve`` gate and by shutdown, and by nothing else; ``set_vars`` stores
and erases as one transaction; ``set_var`` / ``del_var`` are the transaction
of one.  Every user of the store rests on this: the parameter server's
gated GETs, the serving replies, and the per-token stream chunks that a
decode step publishes together.
"""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.native.rpc import RpcClient, RpcServer


@pytest.fixture()
def server():
    s = RpcServer(0)
    s.serve(True)
    yield s
    s.shutdown()


def _ep(server):
    return "127.0.0.1:%d" % server.port


class _Readers:
    """One thread and one connection a name, each in a blocking GET."""

    def __init__(self, server, names, deadline=20.0):
        self.got, self.errors = {}, {}
        self.threads = [threading.Thread(target=self._read,
                                         args=(_ep(server), i, n, deadline))
                        for i, n in enumerate(names)]
        for t in self.threads:
            t.start()

    def _read(self, ep, i, name, deadline):
        try:
            c = RpcClient(ep, rpc_deadline=deadline, retry_times=0)
            try:
                self.got[i] = c.get_var(name)
            finally:
                c.close()
        except ConnectionError as e:
            self.errors[i] = e

    def join(self, timeout=20.0):
        for t in self.threads:
            t.join(timeout)
        assert not any(t.is_alive() for t in self.threads), "a GET hung"


def _until_parked(server, n, timeout=20.0):
    end = time.time() + timeout
    while server.wait_stats()["parked"] != n:
        assert time.time() < end, "readers never parked: %r" % (
            server.wait_stats(),)
        time.sleep(0.005)


def _missing(server, name, wait=0.3):
    """True where a GET of ``name`` is still parked after ``wait``."""
    c = RpcClient(_ep(server), rpc_deadline=wait, retry_times=0)
    try:
        c.get_var(name)
        return False
    except ConnectionError:
        return True
    finally:
        c.close()


@pytest.mark.parametrize("n", [1, 8, 32])
def test_a_store_wakes_only_the_readers_of_what_it_wrote(server, n):
    readers = _Readers(server, ["k%d" % i for i in range(n)])
    _until_parked(server, n)
    # other names, stored alone, in a batch and erased: nobody looks up
    server.set_var("other", np.zeros(3, np.float32))
    server.set_vars([("o%d" % i, np.zeros(1, np.int64)) for i in range(n)],
                    delete=["other"])
    server.del_var("o0")
    time.sleep(0.05)
    assert server.wait_stats() == {"parked": n, "wakeups": 0}
    server.set_vars([("k%d" % i, np.full(4, i, np.int64))
                     for i in range(n)])
    readers.join()
    assert not readers.errors
    assert all((readers.got[i] == i).all() for i in range(n))
    # one wake a reader (n * n where every store woke every parked reader)
    stats = server.wait_stats()
    assert stats["parked"] == 0 and n <= stats["wakeups"] < 2 * n


def test_every_reader_of_one_name_is_woken(server):
    readers = _Readers(server, ["same"] * 5 + ["never"], deadline=1.5)
    _until_parked(server, 6)
    server.set_var("same", np.arange(3, dtype=np.float32))
    for t in readers.threads[:5]:
        t.join(10.0)
    assert sorted(readers.got) == list(range(5))
    assert all((readers.got[i] == [0, 1, 2]).all() for i in range(5))
    assert server.wait_stats()["parked"] == 1
    readers.join()
    assert list(readers.errors) == [5]


@pytest.mark.parametrize("parked_on", ["first", "last"])
def test_a_batch_is_visible_all_at_once(server, parked_on):
    """Whichever key of a batch a reader was woken by, every other key of
    the batch is already there, and what the batch erased is already gone."""
    for rnd in range(4):
        names = ["r%d:%d" % (rnd, i) for i in range(16)]
        wanted = names[0] if parked_on == "first" else names[-1]
        reader = RpcClient(_ep(server), rpc_deadline=20.0, retry_times=0)
        seen = {}

        def read():
            seen[wanted] = reader.get_var(wanted)
            # straight after the wake, on the same connection
            for name in names:
                seen[name] = reader.get_var(name)

        # a GET that timed out (``_missing``) leaves its handler parked
        parked = server.wait_stats()["parked"]
        t = threading.Thread(target=read)
        t.start()
        _until_parked(server, parked + 1)
        server.set_vars(
            [(name, np.full(2, i, np.int32)) for i, name in enumerate(names)],
            delete=["r%d:%d" % (rnd - 1, i) for i in range(16)] if rnd else ())
        t.join(10.0)
        assert not t.is_alive()
        reader.close()
        assert [int(seen[name][0]) for name in names] == list(range(16))
        if rnd:
            assert _missing(server, "r%d:0" % (rnd - 1))
            assert _missing(server, "r%d:15" % (rnd - 1))


def test_a_name_erased_and_stored_in_one_batch_ends_up_stored(server):
    server.set_var("x", np.asarray([1], np.int64))
    server.set_vars([("x", np.asarray([2], np.int64))], delete=["x"])
    c = RpcClient(_ep(server), rpc_deadline=5.0, retry_times=0)
    assert int(c.get_var("x")[0]) == 2
    c.close()


@pytest.mark.parametrize("stored", ["before", "while_closed"])
def test_the_serve_gate_holds_every_get_and_releases_all(stored):
    """The parameter server's round barrier: GETs wait for ``serve(True)``
    though the variable is there, and all leave when it comes."""
    s = RpcServer(0)
    try:
        names = ["w%d" % i for i in range(4)]
        if stored == "before":
            s.set_vars([(n, np.full(2, i, np.float32))
                        for i, n in enumerate(names)])
        readers = _Readers(s, names)
        _until_parked(s, 4)
        if stored == "while_closed":
            s.set_vars([(n, np.full(2, i, np.float32))
                        for i, n in enumerate(names)])
        time.sleep(0.05)
        assert s.wait_stats() == {"parked": 4, "wakeups": 0}
        s.serve(True)
        readers.join()
        assert not readers.errors
        assert all((readers.got[i] == i).all() for i in range(4))
        # closing the gate again parks the next GET of a variable that is there
        s.serve(False)
        assert _missing(s, "w0")
        s.serve(True)
        assert not _missing(s, "w0")
    finally:
        s.shutdown()


@pytest.mark.parametrize("serving", [True, False])
def test_shutdown_releases_every_parked_get(serving):
    s = RpcServer(0)
    s.serve(serving)
    readers = _Readers(s, ["a", "b", "b", "c"])
    _until_parked(s, 4)
    t0 = time.time()
    s.shutdown()
    readers.join()
    assert time.time() - t0 < 10.0
    assert sorted(readers.errors) == [0, 1, 2, 3] and not readers.got
    with pytest.raises(ConnectionError):
        s.set_vars([("a", np.zeros(1))])
    with pytest.raises(ConnectionError):
        s.wait_stats()


@pytest.mark.parametrize("case", ["set_then_get", "overwrite", "delete",
                                  "set_wakes_a_parked_get", "shapes"])
def test_set_var_and_del_var_are_the_batch_of_one(server, case):
    c = RpcClient(_ep(server), rpc_deadline=10.0, retry_times=0)
    try:
        if case == "set_then_get":
            server.set_var("v", np.arange(6, dtype=np.float32).reshape(2, 3))
            got = c.get_var("v")
            assert got.shape == (2, 3) and got.dtype == np.float32
            assert (got == np.arange(6).reshape(2, 3)).all()
        elif case == "overwrite":
            server.set_var("v", np.asarray([1, 2], np.int64))
            server.set_var("v", np.asarray([3], np.int32))
            got = c.get_var("v")
            assert got.dtype == np.int32 and got.tolist() == [3]
        elif case == "delete":
            server.set_var("v", np.zeros(2))
            server.del_var("v")
            server.del_var("was_never_there")
            assert _missing(server, "v")
        elif case == "set_wakes_a_parked_get":
            readers = _Readers(server, ["late"])
            _until_parked(server, 1)
            server.set_var("late", np.asarray([7], np.int64))
            readers.join()
            assert readers.got[0].tolist() == [7]
            assert server.wait_stats()["wakeups"] == 1
        else:
            # one batch of mixed ranks and dtypes, each as it was given
            batch = [("s", np.asarray([3.5], np.float64)),
                     ("m", np.arange(24, dtype=np.int32).reshape(2, 3, 4)),
                     ("e", np.zeros((0, 5), np.uint8)),
                     ("b", np.asarray([True, False]))]
            server.set_vars(batch)
            for name, want in batch:
                got = c.get_var(name)
                assert got.dtype == want.dtype and got.shape == want.shape
                assert (got == want).all()
    finally:
        c.close()
