"""Socket transport suite: framing, flow control, and the TCP Fleet path.

Unit layers (no sockets / loopback socketpairs) run in tier-1; the
``tcp`` marker covers the real-network integration tests the CI
``tcp-mp`` lane re-runs, including the 16-process round and the
kill -9 fault drill.
"""
import multiprocessing as mp
import os
import socket
import ssl
import subprocess
import threading
import time

import numpy as np
import pytest

from repro.core.flowcontrol import CreditGate, CreditLedger
from repro.core.framing import (FT_BYE, FT_HELLO, FT_PING, FT_PONG, FT_REQ,
                                FT_RES, FT_WELCOME, FrameError, FrameReader,
                                control_frame, data_frame_parts, frame_nbytes,
                                pack_unary, parse_control, send_parts,
                                split_data, unpack_unary)
from repro.core.superlink import SuperLinkDriver, SuperNode
from repro.core.transport import (TcpFleetConnection, TcpSuperLink,
                                  run_supernode, start_off_chip)
from repro.fl import ClientApp, NumPyClient, ServerApp, ServerConfig
from repro.fl.strategy import make_strategy
from repro.runtime.reliable import RequestTimeout


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------
def test_control_frame_roundtrip_byte_at_a_time():
    frame = control_frame(FT_HELLO, {"node": "site-1", "proto": 1})
    r = FrameReader()
    got = []
    for i in range(len(frame)):
        got.extend(r.feed(frame[i:i + 1]))      # worst-case chunking
    assert len(got) == 1
    ftype, payload = got[0]
    assert ftype == FT_HELLO
    assert parse_control(payload) == {"node": "site-1", "proto": 1}


def test_many_frames_in_one_chunk():
    blob = b"".join(control_frame(FT_PING, {"t": float(i)})
                    for i in range(7))
    got = FrameReader().feed(blob)
    assert [parse_control(p)["t"] for _, p in got] == [float(i)
                                                       for i in range(7)]


def test_data_frame_zero_copy_body():
    body = np.arange(1024, dtype=np.float32).tobytes()
    parts = data_frame_parts(FT_REQ, {"i": "n:0", "m": "push_task_res"},
                             body)
    assert frame_nbytes(parts) == sum(len(p) for p in parts)
    r = FrameReader()
    (ftype, payload), = r.feed(b"".join(parts))
    assert ftype == FT_REQ
    header, view = split_data(payload)
    assert header == {"i": "n:0", "m": "push_task_res"}
    assert isinstance(view, memoryview) and view.readonly
    # the zero-copy decode the transport relies on: frombuffer straight
    # off the frame view, bitwise intact
    # repro: allow[alias-writeable] reason=view is readonly; write asserted to raise below
    arr = np.frombuffer(view, dtype=np.float32)
    assert arr.tobytes() == body
    with pytest.raises((TypeError, ValueError)):
        # repro: allow[alias-mutation] reason=asserting the frozen view rejects writes
        arr[0] = 1.0


def test_empty_body_data_frame():
    parts = data_frame_parts(FT_RES, {"i": "n:1"}, b"")
    assert len(parts) == 1                       # no zero-length send part
    (_, payload), = FrameReader().feed(b"".join(parts))
    header, view = split_data(payload)
    assert header == {"i": "n:1"} and view.nbytes == 0


def test_frame_length_limits():
    r = FrameReader(max_frame=64)
    with pytest.raises(FrameError):
        r.feed(b"\xff\xff\xff\xff")              # absurd length prefix
    r = FrameReader()
    with pytest.raises(FrameError):
        r.feed(b"\x00\x00\x00\x00")              # zero-length frame


def test_split_data_rejects_header_overrun():
    import struct
    payload = struct.pack("<I", 255)             # hlen=255, nothing follows
    frame = struct.pack("<I", 1 + len(payload)) + bytes((FT_REQ,)) + payload
    (_, view), = FrameReader().feed(frame)
    with pytest.raises(FrameError):
        split_data(view)


def test_unary_envelope_roundtrip():
    b = pack_unary("push_task_res", b"\x00\xf1payload")
    assert unpack_unary(b) == ("push_task_res", b"\x00\xf1payload")


def test_socketpair_partial_reads_and_short_writes():
    """A model-sized frame through deliberately tiny kernel buffers: the
    sender's short-write loop and the reader's incremental recv_into must
    reassemble it bitwise."""
    a, b = socket.socketpair()
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        body = os.urandom(1 << 20)
        parts = data_frame_parts(FT_REQ, {"i": "x:1", "m": "m"}, body)
        t = threading.Thread(target=send_parts, args=(a, *parts))
        t.start()
        r = FrameReader()
        frames = []
        while not frames:
            got = r.read_from(b)
            assert got is not None
            frames = got
        t.join()
        header, view = split_data(frames[0][1])
        assert header["i"] == "x:1" and bytes(view) == body
    finally:
        a.close()
        b.close()


def test_read_from_timeout_preserves_partial_frame():
    a, b = socket.socketpair()
    try:
        b.settimeout(0.05)
        frame = control_frame(FT_PONG, {"t": 1.0})
        a.sendall(frame[:3])                     # prefix cut short
        r = FrameReader()
        with pytest.raises(socket.timeout):
            while True:
                r.read_from(b)
        a.sendall(frame[3:])                     # resume the same frame
        frames = []
        while not frames:
            frames = r.read_from(b)
        assert frames[0][0] == FT_PONG
    finally:
        a.close()
        b.close()


def test_read_from_eof_mid_frame_raises():
    a, b = socket.socketpair()
    try:
        a.sendall(control_frame(FT_BYE, {"reason": "x"})[:6])
        a.close()
        r = FrameReader()
        with pytest.raises(ConnectionError):
            while True:
                if r.read_from(b) is None:
                    raise AssertionError("clean EOF despite partial frame")
    finally:
        b.close()


def test_read_from_clean_eof_returns_none():
    a, b = socket.socketpair()
    try:
        a.sendall(control_frame(FT_BYE, {"reason": "x"}))
        a.close()
        r = FrameReader()
        seen = []
        while True:
            got = r.read_from(b)
            if got is None:
                break
            seen.extend(got)
        assert [f[0] for f in seen] == [FT_BYE]
    finally:
        b.close()


# ---------------------------------------------------------------------------
# flow control
# ---------------------------------------------------------------------------
def test_credit_gate_blocks_until_grant():
    gate = CreditGate()
    gate.reset(100, 1000)
    assert gate.acquire(100, time.monotonic() + 1)
    done = threading.Event()

    def blocked():
        assert gate.acquire(50, time.monotonic() + 5)
        done.set()

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.05)
    assert not done.is_set()                     # sender is stalled
    gate.grant(50)
    t.join(timeout=5)
    assert done.is_set()


def test_credit_gate_deadline_and_close():
    gate = CreditGate()
    gate.reset(0, 100)
    assert not gate.acquire(10, time.monotonic() + 0.05)
    gate.close()
    with pytest.raises(ConnectionError):
        gate.acquire(10, time.monotonic() + 1)


def test_credit_gate_oversized_frame_overshoots_once():
    gate = CreditGate()
    gate.reset(100, 100)
    # a frame bigger than the whole window only needs the window: the
    # balance goes negative and the next acquire stalls until repaid
    assert gate.acquire(250, time.monotonic() + 1)
    assert gate.balance() == -150
    assert not gate.acquire(1, time.monotonic() + 0.05)
    gate.grant(10 ** 9)                          # capped at the limit
    assert gate.balance() == 100


def test_credit_ledger_accounting():
    led = CreditLedger(800)
    assert led.debit(700) and led.outstanding() == 700
    assert led.release(10) == 0                  # below limit//8 threshold
    assert led.release(95) == 105                # coalesced flush
    assert led.debit(800)                        # within 2x overshoot
    assert not led.debit(800)                    # protocol violation
    led2 = CreditLedger(1000)
    led2.debit(400)
    led2.release(50)                             # pending, unsent
    assert led2.snapshot_for_welcome() == 650    # pending folded, zeroed
    assert led2.release(75) == 0                 # 125 would double-count


# ---------------------------------------------------------------------------
# TCP integration (the CI tcp-mp lane re-runs these over real processes)
# ---------------------------------------------------------------------------
def _drain_frames(reader, sock, want=1, deadline=10.0):
    frames = []
    end = time.monotonic() + deadline
    while len(frames) < want and time.monotonic() < end:
        try:
            got = reader.read_from(sock)
        except socket.timeout:
            continue
        if got is None:
            break
        frames.extend(got)
    return frames


@pytest.mark.tcp
def test_tcp_register_pull_push_multiplexed():
    """Two peers interleave pulls and pushes over their multiplexed
    sockets; the driver sees every result exactly once."""
    with TcpSuperLink("127.0.0.1", 0, poll_wait=2.0) as link:
        host, port = link.address
        conns = {s: TcpFleetConnection(host, port, s)
                 for s in ("site-a", "site-b")}
        try:
            for s, c in conns.items():
                c.register(s)
            assert sorted(link.node_ids()) == ["site-a", "site-b"]
            tids = {}
            for s in conns:
                for k in range(3):
                    tids[link.push_task_ins(s, f"task-{s}-{k}".encode())] = s

            def worker(site):
                c = conns[site]
                while True:
                    tid, task = c.pull_task(site)
                    if not tid:
                        return
                    c.push_result(tid, b"done:" + bytes(task))

            ts = [threading.Thread(target=worker, args=(s,)) for s in conns]
            for t in ts:
                t.start()
            got = {}
            deadline = time.monotonic() + 20
            while len(got) < 6:
                item = link.pull_any(list(tids), deadline)
                assert item is not None, "round lost a result"
                got[item[0]] = bytes(item[1])
            for t in ts:
                t.join(timeout=10)
            assert set(got) == set(tids)
            for tid, site in tids.items():
                assert got[tid].startswith(b"done:task-" + site.encode())
        finally:
            for c in conns.values():
                c.close()


@pytest.mark.tcp
def test_tcp_credit_exhaustion_blocks_sender_not_server():
    """A pusher that outruns the server's consumption stalls client-side
    on the credit gate; an unrelated peer's traffic is unaffected, and
    consuming the buffered result un-stalls the pusher."""
    with TcpSuperLink("127.0.0.1", 0, credits_per_peer=8192,
                      poll_wait=0.1) as link:
        host, port = link.address
        fast = TcpFleetConnection(host, port, "fast", request_timeout=30.0)
        other = TcpFleetConnection(host, port, "other")
        try:
            fast.register("fast")
            other.register("other")
            payload = bytes(5000)
            fast.push_result("t-1", payload)     # fits the window
            stalled_done = threading.Event()

            def stalled_push():
                fast.push_result("t-2", payload)
                stalled_done.set()

            t = threading.Thread(target=stalled_push)
            t.start()
            time.sleep(0.3)
            # the second window's worth is stalled in the SENDER...
            assert not stalled_done.is_set()
            assert fast._gate.balance() < len(payload)
            # ...while the server keeps serving the other peer
            other.register("other")
            tid = link.push_task_ins("other", b"ping")
            assert other.pull_task("other") == (tid, b"ping")
            # consuming the buffered result releases credits -> un-stall
            got = link.pull_any(["t-1"], time.monotonic() + 5)
            assert got is not None and bytes(got[1]) == payload
            t.join(timeout=10)
            assert stalled_done.is_set()
            got = link.pull_any(["t-2"], time.monotonic() + 5)
            assert got is not None and bytes(got[1]) == payload
        finally:
            fast.close()
            other.close()


def _raw_hello(host, port, node):
    sock = socket.create_connection((host, port), timeout=5)
    sock.settimeout(0.2)
    send_parts(sock, control_frame(FT_HELLO, {"node": node, "proto": 1}))
    reader = FrameReader()
    (ftype, payload), = _drain_frames(reader, sock)
    assert ftype == FT_WELCOME
    return sock, reader, parse_control(payload)


@pytest.mark.tcp
def test_tcp_reconnect_resume_dedup():
    """A resent REQ (same msg_id, new connection) replays the cached
    response instead of re-executing: the resumed pull returns the SAME
    task even though the queue is now empty, and the duplicate's bytes
    are not double-held against the credit window."""
    with TcpSuperLink("127.0.0.1", 0, poll_wait=2.0) as link:
        host, port = link.address
        tid = link.push_task_ins("raw-1", b"the-one-task")
        sock, reader, welcome = _raw_hello(host, port, "raw-1")
        pull = b"".join(data_frame_parts(
            FT_REQ, {"i": "raw-1:0", "m": "pull_task_ins"}, b""))
        sock.sendall(pull)
        (ftype, payload), = _drain_frames(reader, sock)
        header, body = split_data(payload)
        assert ftype == FT_RES and header["id"] == tid
        assert bytes(body) == b"the-one-task"

        sock.close()                             # network blip
        sock2, reader2, welcome2 = _raw_hello(host, port, "raw-1")
        assert welcome2["credits"] == welcome["credits"]  # dup not held
        sock2.sendall(pull)                      # resume: same msg_id
        (_, payload), = _drain_frames(reader2, sock2)
        header, body = split_data(payload)
        assert header["id"] == tid               # replayed, not re-run
        assert bytes(body) == b"the-one-task"
        # a FRESH pull really does re-execute (and finds the queue empty)
        fresh = b"".join(data_frame_parts(
            FT_REQ, {"i": "raw-1:1", "m": "pull_task_ins"}, b""))
        sock2.sendall(fresh)
        (_, payload), = _drain_frames(reader2, sock2, deadline=15.0)
        header, _ = split_data(payload)
        assert header["i"] == "raw-1:1" and header["id"] == ""
        sock2.close()


@pytest.mark.tcp
def test_tcp_push_resend_does_not_double_apply():
    with TcpSuperLink("127.0.0.1", 0) as link:
        host, port = link.address
        sock, reader, _ = _raw_hello(host, port, "raw-2")
        push = b"".join(data_frame_parts(
            FT_REQ, {"i": "raw-2:0", "m": "push_task_res", "id": "tid-1"},
            b"result-bytes"))
        sock.sendall(push)
        (_, payload), = _drain_frames(reader, sock)
        assert split_data(payload)[0]["s"] == "OK"
        sock.sendall(push)                       # retry after a lost RES
        (_, payload), = _drain_frames(reader, sock)
        assert split_data(payload)[0]["s"] == "OK"   # replayed verdict
        got = link.pull_any(["tid-1"], time.monotonic() + 5)
        assert got is not None and bytes(got[1]) == b"result-bytes"
        assert link.stats["late_dropped"] == 0
        # consuming the single held copy returns the window to full
        deadline = time.monotonic() + 5
        while link._peers["raw-2"].ledger.outstanding() > 0:
            assert time.monotonic() < deadline, "credits never released"
            time.sleep(0.01)
        sock.close()


@pytest.mark.tcp
def test_tcp_heartbeat_expiry_drops_peer():
    with TcpSuperLink("127.0.0.1", 0, heartbeat_timeout=0.4) as link:
        host, port = link.address
        sock, _, _ = _raw_hello(host, port, "quiet")   # never PINGs
        assert "quiet" in link.node_ids()
        deadline = time.monotonic() + 5
        while "quiet" in link.node_ids():
            assert time.monotonic() < deadline, "reaper never fired"
            time.sleep(0.05)
        sock.close()


# --------------------------------------------------------- process fleet
class DeterministicClient(NumPyClient):
    """Pure-deterministic update: fit adds a site-derived constant, so
    tcp-vs-inproc aggregation can be compared bitwise."""

    def __init__(self, cid: str):
        self.cid = cid
        self.idx = int(cid.rsplit("-", 1)[-1])

    def fit(self, parameters, config):
        out = [np.asarray(p, dtype=np.float32) + np.float32(self.idx + 1)
               for p in parameters]
        return out, 10 + self.idx, {}

    def evaluate(self, parameters, config):
        loss = float(sum(np.abs(np.asarray(p)).sum() for p in parameters))
        return loss, 10 + self.idx, {}


class BlockingClient(NumPyClient):
    """Never answers: stands in for a client that will be SIGKILLed."""

    def fit(self, parameters, config):
        time.sleep(600)
        return parameters, 1, {}

    def evaluate(self, parameters, config):
        time.sleep(600)
        return 0.0, 1, {}


def _det_app(node_id: str) -> ClientApp:
    return ClientApp(lambda cid, n=node_id: DeterministicClient(n)
                     .to_client())


def _blocking_app(node_id: str) -> ClientApp:
    return ClientApp(lambda cid: BlockingClient().to_client())


def _det_server_app(rounds: int, timeout: float) -> ServerApp:
    initial = [np.linspace(-1.0, 1.0, 32, dtype=np.float32).reshape(8, 4),
               np.zeros(8, dtype=np.float32)]
    strat = make_strategy("fedavg", initial_parameters=initial)
    return ServerApp(ServerConfig(num_rounds=rounds, round_timeout=timeout),
                     strat)


N_PROCS = 16


@pytest.mark.tcp
@pytest.mark.slow
def test_tcp_16proc_round_bitwise_vs_inproc(tmp_path):
    """The acceptance bar: a 16-process quickstart-shaped round over real
    sockets lands bitwise-identical aggregates to the in-proc fold."""
    from repro.core.superlink import NativeConnection, SuperLink
    sites = [f"proc-{i}" for i in range(N_PROCS)]

    ref_link = SuperLink()
    ref_nodes = [SuperNode(s, _det_app(s), NativeConnection(ref_link))
                 for s in sites]
    for n in ref_nodes:
        n.start()
    try:
        drv = SuperLinkDriver(ref_link, expected_nodes=N_PROCS)
        h_ref = _det_server_app(2, 60.0).run(drv)
    finally:
        for n in ref_nodes:
            n.stop()

    ctx = mp.get_context("spawn")                # JAX threads do not fork
    with TcpSuperLink("127.0.0.1", 0, poll_wait=1.0,
                      heartbeat_timeout=60.0) as link:
        host, port = link.address
        procs = [ctx.Process(target=run_supernode,
                             args=(host, port, s, _det_app),
                             kwargs=dict(run_seconds=600.0,
                                         max_disconnected=10.0),
                             daemon=True)
                 for s in sites]
        start_off_chip(procs)
        try:
            deadline = time.monotonic() + 300
            while len(link.node_ids()) < N_PROCS:
                assert time.monotonic() < deadline, \
                    f"only {len(link.node_ids())}/{N_PROCS} joined"
                time.sleep(0.2)
            drv = SuperLinkDriver(link, expected_nodes=N_PROCS)
            h_tcp = _det_server_app(2, 120.0).run(drv)
        finally:
            link.close()                         # BYE -> children drain
            for p in procs:
                p.join(timeout=30)
                if p.is_alive():
                    p.kill()

    assert h_tcp.losses() == h_ref.losses()      # bitwise, not approx
    assert all(not r.failures for r in h_tcp.rounds)


@pytest.mark.tcp
def test_tcp_kill9_client_mid_round_records_timeout():
    """SIGKILL a SuperNode process mid-fit: the heartbeat reaper drops it
    from the roster and the round completes with the established
    ``(node, "timeout")`` failure record — the server never hangs."""
    ctx = mp.get_context("spawn")
    with TcpSuperLink("127.0.0.1", 0, poll_wait=0.2,
                      heartbeat_timeout=1.0) as link:
        host, port = link.address
        victim = ctx.Process(target=run_supernode,
                             args=(host, port, "victim", _blocking_app),
                             kwargs=dict(run_seconds=600.0,
                                         heartbeat_interval=0.2,
                                         max_disconnected=5.0),
                             daemon=True)
        start_off_chip([victim])
        good = SuperNode("good", _det_app("good-0"),
                         TcpFleetConnection(host, port, "good"))
        good.start()
        try:
            deadline = time.monotonic() + 120
            while len(link.node_ids()) < 2:
                assert time.monotonic() < deadline, "fleet never formed"
                time.sleep(0.1)

            killer = threading.Timer(1.0, victim.kill)
            killer.start()
            try:
                h = _det_server_app(1, 8.0).run(
                    SuperLinkDriver(link, expected_nodes=2))
            finally:
                killer.cancel()
            assert len(h.rounds) == 1
            assert ("victim", "timeout") in h.rounds[0].failures
            assert all(n == "victim" for n, _ in h.rounds[0].failures)
            assert np.isfinite(h.losses()[-1][1])
            assert "victim" not in link.node_ids()   # reaped from roster
        finally:
            good.stop()
            victim.join(timeout=10)
            if victim.is_alive():
                victim.kill()


# ------------------------------------------------------------------- TLS
@pytest.fixture(scope="module")
def tls_contexts(tmp_path_factory):
    d = tmp_path_factory.mktemp("tls")
    cert, key = d / "cert.pem", d / "key.pem"
    r = subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "1",
         "-subj", "/CN=127.0.0.1",
         "-addext", "subjectAltName=IP:127.0.0.1"],
        capture_output=True)
    if r.returncode != 0:
        pytest.skip(f"openssl unavailable: {r.stderr.decode()[:200]}")
    server = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    server.load_cert_chain(str(cert), str(key))
    client = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    client.load_verify_locations(str(cert))
    return server, client


@pytest.mark.tcp
def test_tcp_tls_loopback_roundtrip(tls_contexts):
    server_ctx, client_ctx = tls_contexts
    with TcpSuperLink("127.0.0.1", 0, ssl_context=server_ctx,
                      poll_wait=2.0) as link:
        host, port = link.address
        conn = TcpFleetConnection(host, port, "tls-1",
                                  ssl_context=client_ctx,
                                  server_hostname="127.0.0.1")
        try:
            conn.register("tls-1")
            tid = link.push_task_ins("tls-1", b"secure-task")
            assert conn.pull_task("tls-1") == (tid, b"secure-task")
            conn.push_result(tid, b"secure-res")
            got = link.pull_any([tid], time.monotonic() + 5)
            assert got is not None and bytes(got[1]) == b"secure-res"
        finally:
            conn.close()


# ------------------------------------------------- full-app equivalence
@pytest.mark.tcp
@pytest.mark.slow
def test_tcp_quickstart_scenario_bitwise_vs_inproc(monkeypatch):
    """The ServerApp/strategy stack is transport-agnostic: the quickstart
    scenario over sockets reproduces the in-proc run bit-for-bit."""
    import test_scenarios as ts
    monkeypatch.delenv("REPRO_TRANSPORT", raising=False)
    h_ref, _ = ts.run_scenario("flat", "fedavg", "none")
    monkeypatch.setenv("REPRO_TRANSPORT", "tcp")
    h_tcp, _ = ts.run_scenario("flat", "fedavg", "none")
    assert h_tcp.losses() == h_ref.losses()


@pytest.mark.tcp
def test_tcp_client_timeout_surfaces_as_request_timeout():
    conn = TcpFleetConnection("127.0.0.1", 1, "nobody",  # closed port
                              request_timeout=0.3, connect_timeout=0.2)
    try:
        with pytest.raises((RequestTimeout, ConnectionError)):
            conn.register("nobody")
    finally:
        conn.close()
