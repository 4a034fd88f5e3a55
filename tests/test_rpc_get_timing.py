"""The RPC store's timed GETs (native/csrc/tensor_rpc.cc ``rpcs_time_gets``
/ ``rpcs_drain_gets``, ``RpcServer.time_gets`` / ``drain_gets``): off until
asked for; then, for every reply to a name under the prefix, on the clock of
``time.monotonic()`` and in microseconds: ``deliver`` from variable and
request both there to the reply written, ``late`` where the request came
after its variable, ``turnaround`` from the same connection's reply before
to this request.  From these the serving server tells a chunk that waited
for its reader from a reader that waited for the server
(serving/server.py ``_stream_delivery``).
"""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.native.rpc import GET_RING, RpcClient, RpcServer

PREFIX = "__timed__:"


@pytest.fixture()
def server():
    s = RpcServer(0)
    s.serve(True)
    yield s
    s.shutdown()


def _client(server):
    return RpcClient("127.0.0.1:%d" % server.port, rpc_deadline=20.0,
                     retry_times=0)


@pytest.fixture()
def client(server):
    c = _client(server)
    yield c
    c.close()


def _put(server, name, value=0):
    server.set_var(name, np.asarray([value], np.int64))


def _drain(server, n, timeout=20.0):
    """``(deliver, late, turnaround, dropped)`` of the next ``n`` replies: a
    reply can reach its reader before its record reaches the ring (the
    handler writes, then records), so a drain right behind a GET may come
    too soon."""
    got, end = [[], [], [], 0], time.time() + timeout
    while len(got[0]) < n:
        assert time.time() < end, "the records never came"
        deliver, late, turnaround, dropped = server.drain_gets()
        for have, new in zip(got, (deliver, late, turnaround)):
            assert new.dtype == np.int64
            have.extend(new.tolist())
        got[3] += dropped
        if len(got[0]) < n:
            time.sleep(0.001)
    assert len(got[0]) == n
    return got


def _none_comes(server, wait=0.05):
    time.sleep(wait)
    return [len(x) for x in server.drain_gets()[:3]] == [0, 0, 0] \
        and server.drain_gets()[3] == 0


def _parked_get(server, name):
    """A GET of ``name`` parked on a connection of its own: the thread to
    join once the name is stored."""
    def read():
        c = _client(server)
        try:
            c.get_var(name)
        finally:
            c.close()

    t = threading.Thread(target=read)
    t.start()
    end = time.time() + 20.0
    while server.wait_stats()["parked"] != 1:
        assert time.time() < end, "the GET never parked"
        time.sleep(0.002)
    return t


def test_off_by_default_and_off_again(server, client):
    _put(server, PREFIX + "a")
    client.get_var(PREFIX + "a")
    assert _none_comes(server)
    server.time_gets(PREFIX)
    client.get_var(PREFIX + "a")
    _drain(server, 1)
    server.time_gets(None)
    client.get_var(PREFIX + "a")
    assert _none_comes(server)


def test_a_get_parked_before_the_store_is_not_late_and_soon_served(server):
    server.time_gets(PREFIX)
    reader = _parked_get(server, PREFIX + "a")
    time.sleep(0.1)
    _put(server, PREFIX + "a")
    reader.join(20.0)
    assert not reader.is_alive()
    deliver, late, turnaround, dropped = _drain(server, 1)
    # it waited 100 ms for the variable, which is nobody's delivery time
    assert late == [] and 0 <= deliver[0] < 50_000
    assert turnaround == [] and dropped == 0


def test_a_get_that_comes_after_the_store_reads_how_late(server, client):
    server.time_gets(PREFIX)
    t0 = time.monotonic_ns()
    _put(server, PREFIX + "a")
    time.sleep(0.02)
    client.get_var(PREFIX + "a")
    t1 = time.monotonic_ns()
    deliver, late, turnaround, _ = _drain(server, 1)
    # microseconds of Python's monotonic clock; and the reader's lateness is
    # not the server's delivery (each value is rounded down by itself: 2 us)
    assert 20_000 <= late[0] <= (t1 - t0) // 1000
    assert 0 <= deliver[0] <= (t1 - t0) // 1000 - late[0] + 2
    assert turnaround == []     # the connection's first timed reply


def test_turnaround_is_each_connections_own(server, client):
    server.time_gets(PREFIX)
    fast = _client(server)
    try:
        for k in range(6):
            _put(server, PREFIX + "s:%d" % k, k)
            _put(server, PREFIX + "f:%d" % k, k)
        for k in range(6):
            client.get_var(PREFIX + "s:%d" % k)
            fast.get_var(PREFIX + "f:%d" % k)
            fast.get_var(PREFIX + "f:%d" % k)
            time.sleep(0.02)        # the slow reader's own time
    finally:
        fast.close()
    deliver, late, turnaround, _ = _drain(server, 18)
    # five of the slow connection's, eleven of the fast one's: six of those
    # follow a reply at once, and the sleep is in every other one
    assert len(turnaround) == 16 and len(late) == 18
    turn = np.asarray(turnaround)
    assert (turn >= 20_000).sum() >= 10 and turn.min() < 20_000


def test_names_outside_the_prefix_leave_no_record(server, client):
    server.time_gets(PREFIX)
    _put(server, "other")
    _put(server, PREFIX + "a")
    client.get_var(PREFIX + "a")
    client.get_var("other")
    client.get_var("other")
    client.get_var(PREFIX + "a")
    client.send_var("grad", np.zeros(2, np.float32))
    client.get_var(PREFIX + "a")
    deliver, late, turnaround, _ = _drain(server, 3)
    assert _none_comes(server)
    # the reply before was to another name, or an ACK: no turn-around
    assert turnaround == []
    client.get_var(PREFIX + "a")
    assert len(_drain(server, 1)[2]) == 1


def test_a_full_ring_counts_what_falls_out_and_keeps_the_newest(server,
                                                                client):
    server.time_gets(PREFIX)
    _put(server, PREFIX + "a")
    t_stored = time.monotonic_ns()
    extra = 37
    for _ in range(extra):
        client.get_var(PREFIX + "a")
    time.sleep(0.02)
    t_kept = time.monotonic_ns()
    for _ in range(GET_RING):
        client.get_var(PREFIX + "a")
    time.sleep(0.05)        # the last reply's record
    deliver, late, turnaround, dropped = server.drain_gets()
    assert (len(deliver), dropped) == (GET_RING, extra)
    assert len(late) == GET_RING and len(turnaround) == GET_RING
    # oldest first, and the oldest kept is the first request after the
    # pause: every request is later behind the one store than the one before
    assert (np.diff(late) >= 0).all()
    assert late[0] >= (t_kept - t_stored) // 1000
    assert _none_comes(server)


def test_a_drain_empties_the_ring(server, client):
    server.time_gets(PREFIX)
    _put(server, PREFIX + "a")
    for _ in range(5):
        client.get_var(PREFIX + "a")
    _drain(server, 5)
    assert _none_comes(server)
    client.get_var(PREFIX + "a")
    _drain(server, 1)
    assert _none_comes(server)


def test_a_get_read_under_one_setting_leaves_nothing_under_another(server):
    """Parked while the switch is on, answered after it went off and on
    again: its request was read in a stretch that was thrown away."""
    server.time_gets(PREFIX)
    reader = _parked_get(server, PREFIX + "a")
    server.time_gets(None)
    server.time_gets(PREFIX)
    _put(server, PREFIX + "a")
    reader.join(20.0)
    assert not reader.is_alive()
    assert _none_comes(server)


def test_the_switch_thrown_again_forgets_the_connections_last_reply(server,
                                                                    client):
    server.time_gets(PREFIX)
    _put(server, PREFIX + "a")
    client.get_var(PREFIX + "a")
    client.get_var(PREFIX + "a")
    assert len(_drain(server, 2)[2]) == 1
    server.time_gets(PREFIX)        # on to on: a new stretch all the same
    client.get_var(PREFIX + "a")
    client.get_var(PREFIX + "a")
    # the first reply of the new stretch has none before it to turn from
    assert len(_drain(server, 2)[2]) == 1


@pytest.mark.parametrize("parked", [False, True])
def test_shutdown_with_records_waiting(parked):
    s = RpcServer(0)
    s.serve(True)
    s.time_gets(PREFIX)
    _put(s, PREFIX + "a")
    c = _client(s)
    for _ in range(3):
        c.get_var(PREFIX + "a")
    errors = []

    def read():
        try:
            c.get_var(PREFIX + "never")
        except ConnectionError as e:
            errors.append(e)

    t = threading.Thread(target=read)
    if parked:
        t.start()
        while s.wait_stats()["parked"] != 1:
            time.sleep(0.002)
    done = threading.Thread(target=s.shutdown)
    done.start()
    done.join(20.0)
    assert not done.is_alive(), "shutdown hung"
    if parked:
        t.join(20.0)
        assert not t.is_alive() and len(errors) == 1
    with pytest.raises(ConnectionError):
        s.drain_gets()
    with pytest.raises(ConnectionError):
        s.time_gets(PREFIX)
    c.close()
