"""RPC layer tests (reference: common/tests/thrift_client_pool_test.cpp,
thrift_router_test.cpp — live local servers, role/AZ/quantity logic)."""

import asyncio
import json

import pytest

from rocksplicator_tpu.rpc import (
    ClusterLayout,
    IoLoop,
    Quantity,
    Role,
    RpcApplicationError,
    RpcClientPool,
    RpcConnectionError,
    RpcRouter,
    RpcServer,
    RpcTimeout,
)
from rocksplicator_tpu.rpc.serde import decode_message, encode_message


# ---------------------------------------------------------------------------
# serde
# ---------------------------------------------------------------------------


def test_serde_roundtrip_with_binary():
    msg = {
        "id": 1,
        "method": "replicate",
        "args": {
            "db_name": "seg00001",
            "updates": [
                {"seq_no": 5, "raw_data": b"\x00\x01binary\xff"},
                {"seq_no": 6, "raw_data": b"more"},
            ],
            "nested": {"blob": b"xyz", "n": 3.5, "flag": True, "none": None},
        },
    }
    header, chunks = encode_message(msg)
    payload = b"".join(chunks)
    out = decode_message(memoryview(header), memoryview(payload))
    assert out["id"] == 1
    assert bytes(out["args"]["updates"][0]["raw_data"]) == b"\x00\x01binary\xff"
    assert bytes(out["args"]["updates"][1]["raw_data"]) == b"more"
    assert bytes(out["args"]["nested"]["blob"]) == b"xyz"
    assert out["args"]["nested"]["n"] == 3.5
    assert out["args"]["nested"]["none"] is None
    # zero-copy: decoded binaries are views into the payload buffer
    assert isinstance(out["args"]["updates"][0]["raw_data"], memoryview)


def test_serde_rejects_reserved_key():
    with pytest.raises(ValueError):
        encode_message({"$bin": [0, 1]})


# ---------------------------------------------------------------------------
# server + client + pool over real TCP
# ---------------------------------------------------------------------------


class EchoHandler:
    async def handle_echo(self, text="", blob=b""):
        return {"text": text, "blob": bytes(blob) + b"!"}

    async def handle_fail(self, code="BOOM"):
        raise RpcApplicationError(code, "requested failure", {"k": 1})

    async def handle_slow(self, delay=1.0):
        await asyncio.sleep(delay)
        return {"done": True}

    async def handle_crash(self):
        raise RuntimeError("unexpected")


class ExtensionHandler:
    """Stacked handler — the 'service Counter extends Admin' pattern."""

    async def handle_extra(self):
        return {"extra": True}


@pytest.fixture()
def rpc_server():
    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    server.add_handler(ExtensionHandler())
    server.add_handler(EchoHandler())
    server.start()
    yield server, ioloop
    server.stop()


def test_rpc_echo_and_binary(rpc_server):
    server, ioloop = rpc_server

    async def go():
        pool = RpcClientPool()
        result = await pool.call(
            "127.0.0.1", server.port, "echo", {"text": "hi", "blob": b"abc"}
        )
        assert result["text"] == "hi"
        assert bytes(result["blob"]) == b"abc!"
        extra = await pool.call("127.0.0.1", server.port, "extra")
        assert extra["extra"] is True
        await pool.close()

    ioloop.run_sync(go())


def test_rpc_application_error(rpc_server):
    server, ioloop = rpc_server

    async def go():
        pool = RpcClientPool()
        with pytest.raises(RpcApplicationError) as ei:
            await pool.call("127.0.0.1", server.port, "fail", {"code": "SOURCE_NOT_FOUND"})
        assert ei.value.code == "SOURCE_NOT_FOUND"
        assert ei.value.data == {"k": 1}
        # unexpected handler exceptions surface as INTERNAL
        with pytest.raises(RpcApplicationError) as ei2:
            await pool.call("127.0.0.1", server.port, "crash")
        assert ei2.value.code == "INTERNAL"
        # unknown method
        with pytest.raises(RpcApplicationError) as ei3:
            await pool.call("127.0.0.1", server.port, "nope")
        assert ei3.value.code == "NO_SUCH_METHOD"
        await pool.close()

    ioloop.run_sync(go())


def test_rpc_timeout_and_concurrency(rpc_server):
    server, ioloop = rpc_server

    async def go():
        pool = RpcClientPool()
        with pytest.raises(RpcTimeout):
            await pool.call("127.0.0.1", server.port, "slow", {"delay": 5.0}, timeout=0.1)
        # a slow call must not block a fast one on the same connection
        slow = asyncio.ensure_future(
            pool.call("127.0.0.1", server.port, "slow", {"delay": 0.5})
        )
        fast = await pool.call("127.0.0.1", server.port, "echo", {"text": "quick"})
        assert fast["text"] == "quick"
        assert not slow.done()
        assert (await slow)["done"] is True
        await pool.close()

    ioloop.run_sync(go())


def test_client_pool_health_and_reconnect(rpc_server):
    server, ioloop = rpc_server

    async def go():
        pool = RpcClientPool()
        client = await pool.get_client("127.0.0.1", server.port)
        assert client.is_good
        # same healthy client is reused
        assert await pool.get_client("127.0.0.1", server.port) is client
        # connection refused flips to error
        with pytest.raises(RpcConnectionError):
            await pool.get_client("127.0.0.1", 1)  # nothing listens there
        # immediately retrying the bad addr is throttled
        with pytest.raises(RpcConnectionError) as ei:
            await pool.get_client("127.0.0.1", 1)
        assert "throttled" in str(ei.value)
        await pool.close()

    ioloop.run_sync(go())


def test_server_restart_client_reconnects():
    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    server.add_handler(EchoHandler())
    server.start()
    port = server.port

    async def first():
        pool = RpcClientPool()
        r = await pool.call("127.0.0.1", port, "echo", {"text": "a"})
        assert r["text"] == "a"
        return pool

    pool = ioloop.run_sync(first())
    server.stop()

    async def after_stop():
        client = pool.peek("127.0.0.1", port)
        # give the recv loop a beat to observe the close
        for _ in range(50):
            if not client.is_good:
                break
            await asyncio.sleep(0.05)
        assert not client.is_good
        with pytest.raises(RpcConnectionError):
            await pool.call("127.0.0.1", port, "echo", {"text": "b"})

    ioloop.run_sync(after_stop())

    server2 = RpcServer(port=port, host="127.0.0.1", ioloop=ioloop)
    server2.add_handler(EchoHandler())
    server2.start()

    async def after_restart():
        await asyncio.sleep(1.1)  # clear the reconnect throttle
        r = await pool.call("127.0.0.1", port, "echo", {"text": "back"})
        assert r["text"] == "back"
        await pool.close()

    try:
        ioloop.run_sync(after_restart())
    finally:
        server2.stop()


# ---------------------------------------------------------------------------
# router (reference thrift_router_test.cpp — 18 TESTs of role/AZ/locality)
# ---------------------------------------------------------------------------


SHARD_MAP = {
    "seg": {
        "num_shards": 3,
        "10.0.0.1:9090:az1": ["00000:M", "00001:S"],
        "10.0.0.2:9090:az2": ["00000:S", "00001:M", "00002:S"],
        "10.0.0.3:9090:az1": ["00000:S", "00002:M"],
    }
}


def _router(local_az="az1"):
    router = RpcRouter(local_az=local_az)
    router.update_layout(ClusterLayout.parse(json.dumps(SHARD_MAP).encode()))
    return router


def test_router_parse_and_counts():
    router = _router()
    assert router.num_shards("seg") == 3
    assert router.num_shards("missing") == 0
    assert router.get_hosts_for("missing", 0) == []


def test_router_leader_selection():
    router = _router()
    hosts = router.get_hosts_for("seg", 0, Role.LEADER, Quantity.ALL)
    assert [h.ip for h in hosts] == ["10.0.0.1"]
    hosts = router.get_hosts_for("seg", 1, Role.LEADER, Quantity.ALL)
    assert [h.ip for h in hosts] == ["10.0.0.2"]


def test_router_follower_selection():
    router = _router()
    hosts = router.get_hosts_for("seg", 0, Role.FOLLOWER, Quantity.ALL)
    assert sorted(h.ip for h in hosts) == ["10.0.0.2", "10.0.0.3"]
    # az1-local follower (10.0.0.3) must sort before az2
    assert hosts[0].ip == "10.0.0.3"


def test_router_any_prefers_leader_then_locality():
    router = _router(local_az="az1")
    hosts = router.get_hosts_for("seg", 0, Role.ANY, Quantity.ALL)
    assert len(hosts) == 3
    # leader in local az: first
    assert hosts[0].ip == "10.0.0.1"
    # local follower before remote follower
    assert hosts[1].ip == "10.0.0.3"
    assert hosts[2].ip == "10.0.0.2"


def test_router_any_remote_leader_still_preferred_within_tier():
    router = _router(local_az="az2")
    hosts = router.get_hosts_for("seg", 2, Role.ANY, Quantity.ALL)
    # shard 2: leader 10.0.0.3 (az1), follower 10.0.0.2 (az2 = local).
    # Locality tier sorts the local follower first, leader next.
    assert [h.ip for h in hosts] == ["10.0.0.2", "10.0.0.3"]


def test_router_quantities():
    router = _router()
    assert len(router.get_hosts_for("seg", 0, Role.ANY, Quantity.ONE)) == 1
    assert len(router.get_hosts_for("seg", 0, Role.ANY, Quantity.TWO)) == 2
    assert len(router.get_hosts_for("seg", 0, Role.ANY, Quantity.ALL)) == 3


def test_router_rotation_is_deterministic():
    router = _router(local_az="")
    a = router.get_hosts_for("seg", 0, Role.FOLLOWER, Quantity.ALL)
    b = router.get_hosts_for("seg", 0, Role.FOLLOWER, Quantity.ALL)
    assert a == b


def test_router_hot_reload_from_file(tmp_path, file_watcher):
    path = tmp_path / "shard_map.json"
    path.write_text(json.dumps(SHARD_MAP))
    router = RpcRouter(local_az="az1", shard_map_path=str(path))
    assert router.num_shards("seg") == 3
    # poll_now below is the ONLY poll. The watcher's own thread (every
    # 0.1 s) could read the new map first, advance the content digest and
    # still be inside the router's callback when poll_now, which finds
    # the digest unchanged, returns: the layout asserted on was then the
    # old one (seen under -n 6, a loaded host)
    file_watcher.stop()
    new_map = {"seg": {"num_shards": 1, "10.9.9.9:1:az9": ["00000:M"]}}
    path.write_text(json.dumps(new_map))
    file_watcher.poll_now()
    assert router.num_shards("seg") == 1
    assert router.get_hosts_for("seg", 0, Role.LEADER)[0].ip == "10.9.9.9"
    # malformed update keeps previous layout
    path.write_text("not json")
    file_watcher.poll_now()
    assert router.num_shards("seg") == 1


def test_router_get_clients_skips_bad_hosts():
    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    server.add_handler(EchoHandler())
    server.start()
    try:
        shard_map = {
            "seg": {
                "num_shards": 1,
                f"127.0.0.1:{server.port}:az1": ["00000:S"],
                "127.0.0.1:1:az1": ["00000:M"],  # dead leader
            }
        }
        router = RpcRouter(local_az="az1")
        router.update_layout(ClusterLayout.parse(json.dumps(shard_map).encode()))

        async def go():
            clients = await router.get_clients_for(
                "seg", 0, Role.ANY, Quantity.ONE
            )
            assert len(clients) == 1
            assert clients[0].port == server.port
            await router.pool.close()

        ioloop.run_sync(go())
    finally:
        server.stop()


def test_serde_rejects_bad_binary_refs():
    import json as _json

    payload = memoryview(b"0123456789")
    for ref in ([-10, 5], [0, 99], [5], "x", [0, -1]):
        header = _json.dumps({"v": {"$bin": ref}}).encode()
        with pytest.raises(ValueError):
            decode_message(memoryview(header), payload)


def test_router_local_group_prefix_locality():
    shard_map = {
        "seg": {
            "num_shards": 1,
            "10.0.0.1:1:us-east-1a": ["00000:S"],
            "10.0.0.2:1:us-east-1b": ["00000:S"],
            "10.0.0.3:1:eu-west-1a": ["00000:S"],
        }
    }
    router = RpcRouter(local_az="us-east-1a", local_group_prefix_len=9)
    router.update_layout(ClusterLayout.parse(json.dumps(shard_map).encode()))
    hosts = router.get_hosts_for("seg", 0, Role.FOLLOWER, Quantity.ALL)
    assert [h.ip for h in hosts] == ["10.0.0.1", "10.0.0.2", "10.0.0.3"]


def test_router_close_unregisters_watcher(tmp_path, file_watcher):
    path = tmp_path / "map.json"
    path.write_text(json.dumps({"seg": {"num_shards": 1, "1.2.3.4:1:az": ["00000:M"]}}))
    router = RpcRouter(local_az="az", shard_map_path=str(path))
    assert router.num_shards("seg") == 1
    router.close()
    path.write_text(json.dumps({"seg": {"num_shards": 9, "1.2.3.4:1:az": ["00000:M"]}}))
    file_watcher.poll_now()
    assert router.num_shards("seg") == 1  # no longer watching


def test_graceful_stop_drains_inflight_requests():
    """reference common/tests/graceful_shutdown_test.cpp: a request in
    flight at shutdown completes when a drain window is given."""
    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    server.add_handler(EchoHandler())
    server.start()
    port = server.port

    pool = RpcClientPool()
    fut = ioloop.run_coro(
        pool.call("127.0.0.1", port, "slow", {"delay": 0.6}, timeout=10)
    )
    import time as _time

    _time.sleep(0.15)  # let the request reach the server
    server.stop(drain_timeout=5.0)  # must wait for the slow handler
    assert fut.result(10)["done"] is True
    ioloop.run_sync(pool.close())


def test_hard_stop_cancels_inflight_requests():
    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    server.add_handler(EchoHandler())
    server.start()
    port = server.port
    pool = RpcClientPool()
    fut = ioloop.run_coro(
        pool.call("127.0.0.1", port, "slow", {"delay": 30}, timeout=5)
    )
    import time as _time

    _time.sleep(0.15)
    server.stop()  # no drain: cancelled
    with pytest.raises(Exception):
        fut.result(10)
    ioloop.run_sync(pool.close())


def test_drain_rejects_new_requests_on_live_connections():
    """A busy client on an existing connection cannot defeat the drain:
    frames arriving during the window get a typed SHUTDOWN error."""
    import threading as _threading
    import time as _time

    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    server.add_handler(EchoHandler())
    server.start()
    port = server.port
    pool = RpcClientPool()
    slow = ioloop.run_coro(
        pool.call("127.0.0.1", port, "slow", {"delay": 0.5}, timeout=10)
    )
    _time.sleep(0.15)
    stopper = _threading.Thread(target=lambda: server.stop(drain_timeout=5.0))
    stopper.start()
    _time.sleep(0.2)  # drain in progress, slow request still running
    with pytest.raises(RpcApplicationError) as ei:
        ioloop.run_coro(
            pool.call("127.0.0.1", port, "echo", {"text": "late"}, timeout=5)
        ).result(10)
    assert ei.value.code == "SHUTDOWN"
    assert slow.result(10)["done"] is True  # pre-drain request completed
    stopper.join(10)
    ioloop.run_sync(pool.close())


def test_frame_compression_roundtrip_and_bomb_guard():
    import asyncio as _a
    import zlib as _z

    from rocksplicator_tpu.rpc import framing

    async def go():
        # loopback stream pair
        server_reader = None

        async def on_conn(r, w):
            nonlocal server_reader
            server_reader = (r, w)

        srv = await _a.start_server(on_conn, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        cr, cw = await _a.open_connection("127.0.0.1", port)
        await _a.sleep(0.05)
        sr, sw = server_reader
        # large compressible payload: compressed on the wire
        payload = b"A" * 100_000
        await framing.write_frame(cw, b'{"id":1}', [payload])
        reader = framing.FrameReader(sr)
        header, got = await reader.read_frame()
        assert bytes(got) == payload
        # oversized-decompression frame is rejected
        bomb = _z.compress(b"B" * (framing.MAX_FRAME_BYTES + 10), 1)
        sw_head = framing._HEADER.pack(
            framing.MAGIC, framing.FLAG_PAYLOAD_ZLIB, 2, len(bomb))
        cw.write(sw_head + b"{}" + bomb)
        await cw.drain()
        try:
            await reader.read_frame()
            raised = False
        except ValueError:
            raised = True
        assert raised
        cw.close()
        srv.close()

    _a.run(go())


def test_server_restart_serves_after_drain_stop():
    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    server.add_handler(EchoHandler())
    server.start()
    port = server.port
    server.stop(drain_timeout=1.0)
    server2 = RpcServer(port=port, host="127.0.0.1", ioloop=ioloop)
    server2.add_handler(EchoHandler())
    server2.start()
    try:
        import time as _time

        _time.sleep(1.1)  # clear pool reconnect throttle
        pool = RpcClientPool()

        async def go():
            return await pool.call("127.0.0.1", port, "echo", {"text": "hi"})

        assert ioloop.run_sync(go())["text"] == "hi"
        ioloop.run_sync(pool.close())
    finally:
        server2.stop()


def test_router_hedged_call(rpc_server):
    """Router-level hedged reads (reference future_util speculation): a
    stuck primary is covered by the backup replica."""
    server, ioloop = rpc_server

    class StuckHandler:
        async def handle_probe(self):
            await asyncio.sleep(30)
            return {"who": "stuck"}

    stuck_server = RpcServer(port=0, ioloop=ioloop)
    stuck_server.add_handler(StuckHandler())
    stuck_server.start()

    class FastHandler:
        async def handle_probe(self):
            return {"who": "fast"}

    fast_server = RpcServer(port=0, ioloop=ioloop)
    fast_server.add_handler(FastHandler())
    fast_server.start()
    try:
        shard_map = {
            "seg": {
                "num_shards": 1,
                f"127.0.0.1:{stuck_server.port}:az1": ["00000:M"],
                f"127.0.0.1:{fast_server.port}:az1": ["00000:S"],
            }
        }
        router = RpcRouter(local_az="az1")
        router.update_layout(ClusterLayout.parse(json.dumps(shard_map).encode()))

        async def go():
            return await router.hedged_call(
                "seg", 0, "probe", role=Role.ANY,
                backup_delay_sec=0.05, timeout=10,
            )

        result = ioloop.run_sync(go(), timeout=15)
        assert result["who"] == "fast"  # backup replica answered

        async def cleanup():
            await router.pool.close()

        ioloop.run_sync(cleanup())
    finally:
        stuck_server.stop()
        fast_server.stop()


# ---------------------------------------------------------------------------
# TLS (reference: ssl_context_manager.h + SSL channels in the client pool)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tls_certs(tmp_path_factory):
    # minting the test CA needs pyca/cryptography (stdlib ssl can only
    # CONSUME certs): SKIP cleanly where the image doesn't ship it
    # instead of failing every TLS test as "pre-existing noise"
    pytest.importorskip(
        "cryptography",
        reason="TLS tests need the 'cryptography' package to mint the "
               "test CA (not installed in this image)")
    from rocksplicator_tpu.utils.ssl_context_manager import make_test_ca

    return make_test_ca(str(tmp_path_factory.mktemp("certs")))


def _managers(certs, with_client_cert=True):
    from rocksplicator_tpu.utils.ssl_context_manager import SslContextManager

    server = SslContextManager(
        certs["server_cert"], certs["server_key"], ca_path=certs["ca_cert"],
        server_side=True,
    )
    client = SslContextManager(
        certs["client_cert" if with_client_cert else "server_cert"],
        certs["client_key" if with_client_cert else "server_key"],
        ca_path=certs["ca_cert"], server_side=False,
    )
    return server, client


def test_rpc_over_mutual_tls(tls_certs):
    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer

    server_mgr, client_mgr = _managers(tls_certs)
    server = RpcServer(port=0, ssl_manager=server_mgr)
    server.add_handler(EchoHandler())
    server.start()
    ioloop = IoLoop.default()
    pool = RpcClientPool(ssl_manager=client_mgr)
    try:
        async def go():
            return await pool.call(
                "127.0.0.1", server.port, "echo", {"blob": b"\x00secret"})

        result = ioloop.run_sync(go(), timeout=15)
        assert bytes(result["blob"]) == b"\x00secret!"  # echo appends '!'
    finally:
        ioloop.run_sync(pool.close())
        server.stop()


def test_tls_server_rejects_plaintext_client(tls_certs):
    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer
    from rocksplicator_tpu.rpc.errors import RpcConnectionError, RpcError

    server_mgr, _ = _managers(tls_certs)
    server = RpcServer(port=0, ssl_manager=server_mgr)
    server.add_handler(EchoHandler())
    server.start()
    ioloop = IoLoop.default()
    pool = RpcClientPool()  # no TLS
    try:
        async def go():
            return await pool.call("127.0.0.1", server.port, "echo", {},
                                   timeout=3)

        with pytest.raises((RpcError, RpcConnectionError)):
            ioloop.run_sync(go(), timeout=10)
    finally:
        ioloop.run_sync(pool.close())
        server.stop()


def test_tls_server_requires_client_cert(tls_certs, tmp_path):
    """Per-connection auth: a TLS client WITHOUT a CA-signed client cert
    must be rejected by the mutual-TLS server."""
    import ssl as ssl_mod

    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer
    from rocksplicator_tpu.rpc.errors import RpcConnectionError, RpcError
    from rocksplicator_tpu.utils.ssl_context_manager import (
        SslContextManager, make_test_ca,
    )

    server_mgr, _ = _managers(tls_certs)
    server = RpcServer(port=0, ssl_manager=server_mgr)
    server.add_handler(EchoHandler())
    server.start()
    # client certified by a DIFFERENT CA — signature check must fail
    rogue = make_test_ca(str(tmp_path / "rogue"))
    rogue_mgr = SslContextManager(
        rogue["client_cert"], rogue["client_key"],
        ca_path=tls_certs["ca_cert"], server_side=False,
    )
    ioloop = IoLoop.default()
    pool = RpcClientPool(ssl_manager=rogue_mgr)
    try:
        async def go():
            return await pool.call("127.0.0.1", server.port, "echo", {},
                                   timeout=3)

        with pytest.raises((RpcError, RpcConnectionError, ssl_mod.SSLError)):
            ioloop.run_sync(go(), timeout=10)
    finally:
        ioloop.run_sync(pool.close())
        server.stop()


def test_tls_role_binding_rejects_swapped_certs(tls_certs):
    """EKU role binding: CA membership alone must not authenticate a
    role. A server presenting a CLIENT cert is rejected by connecting
    clients; a client presenting a SERVER cert is rejected by the
    server (utils/ssl_context_manager.check_peer_role)."""
    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer
    from rocksplicator_tpu.rpc.errors import RpcConnectionError, RpcError
    from rocksplicator_tpu.utils.ssl_context_manager import SslContextManager

    ioloop = IoLoop.default()

    async def go(pool, port):
        return await pool.call("127.0.0.1", port, "echo", {}, timeout=3)

    # case 1: server wearing the CLIENT cert — client must refuse it
    impostor_mgr = SslContextManager(
        tls_certs["client_cert"], tls_certs["client_key"],
        ca_path=tls_certs["ca_cert"], server_side=True,
    )
    server = RpcServer(port=0, ssl_manager=impostor_mgr)
    server.add_handler(EchoHandler())
    server.start()
    _, client_mgr = _managers(tls_certs)
    pool = RpcClientPool(ssl_manager=client_mgr)
    try:
        with pytest.raises((RpcError, RpcConnectionError)):
            ioloop.run_sync(go(pool, server.port), timeout=10)
    finally:
        ioloop.run_sync(pool.close())
        server.stop()

    # case 2: client wearing the SERVER cert — server must refuse it
    server_mgr, _ = _managers(tls_certs)
    server2 = RpcServer(port=0, ssl_manager=server_mgr)
    server2.add_handler(EchoHandler())
    server2.start()
    swapped_mgr = SslContextManager(
        tls_certs["server_cert"], tls_certs["server_key"],
        ca_path=tls_certs["ca_cert"], server_side=False,
    )
    pool2 = RpcClientPool(ssl_manager=swapped_mgr)
    try:
        with pytest.raises((RpcError, RpcConnectionError)):
            ioloop.run_sync(go(pool2, server2.port), timeout=10)
    finally:
        ioloop.run_sync(pool2.close())
        server2.stop()


def test_check_peer_role_reads_eku_from_der(tls_certs):
    """check_peer_role must actually parse the EKU (ssl's dict-form
    getpeercert() does not expose it) — exercised directly with a stub
    ssl_object so the check can't silently regress into a no-op that
    only passes because OpenSSL's handshake happened to reject first."""
    from rocksplicator_tpu.utils.ssl_context_manager import (
        PeerRoleError, check_peer_role)

    import ssl as ssl_mod

    class StubContext:
        verify_mode = ssl_mod.CERT_REQUIRED

    class StubSslObject:
        context = StubContext()

        def __init__(self, pem_path):
            from cryptography import x509
            from cryptography.hazmat.primitives.serialization import Encoding

            with open(pem_path, "rb") as f:
                cert = x509.load_pem_x509_certificate(f.read())
            self._der = cert.public_bytes(Encoding.DER)

        def getpeercert(self, binary_form=False):
            assert binary_form, "role check must request the DER form"
            return self._der

    # right roles pass
    check_peer_role(StubSslObject(tls_certs["server_cert"]), "server")
    check_peer_role(StubSslObject(tls_certs["client_cert"]), "client")
    # swapped roles raise
    with pytest.raises(PeerRoleError):
        check_peer_role(StubSslObject(tls_certs["client_cert"]), "server")
    with pytest.raises(PeerRoleError):
        check_peer_role(StubSslObject(tls_certs["server_cert"]), "client")
    # CA cert (no EKU) passes either role — externally-provisioned certs
    check_peer_role(StubSslObject(tls_certs["ca_cert"]), "server")


def test_tls_release_unpaired_stop_keeps_shared_thread(tls_certs):
    """Double stop() / stop()-without-start must not release another
    holder's refresh-thread claim."""
    import threading

    from rocksplicator_tpu.rpc import RpcServer
    from rocksplicator_tpu.utils.ssl_context_manager import SslContextManager

    def refresh_threads():
        return sum(1 for t in threading.enumerate()
                   if t.name == "ssl-refresh" and t.is_alive())

    base = refresh_threads()
    mgr = SslContextManager(
        tls_certs["server_cert"], tls_certs["server_key"],
        ca_path=tls_certs["ca_cert"], server_side=True,
        refresh_interval=30.0,
    )
    holder = RpcServer(port=0, ssl_manager=mgr)
    holder.add_handler(EchoHandler())
    holder.start()
    assert refresh_threads() == base + 1
    # a server that never started: its stop() must not steal the claim
    never_started = RpcServer(port=0, ssl_manager=mgr)
    never_started.stop()
    assert refresh_threads() == base + 1
    holder.stop()
    holder.stop()  # double stop: second release is a no-op
    assert refresh_threads() == base


def test_tls_refresh_thread_refcounted_across_servers(tls_certs):
    """A shared SslContextManager's refresh thread survives one server's
    stop and is reaped when the LAST user releases it."""
    import threading

    from rocksplicator_tpu.rpc import RpcServer
    from rocksplicator_tpu.utils.ssl_context_manager import SslContextManager

    def refresh_threads():
        return sum(1 for t in threading.enumerate()
                   if t.name == "ssl-refresh" and t.is_alive())

    base = refresh_threads()
    mgr = SslContextManager(
        tls_certs["server_cert"], tls_certs["server_key"],
        ca_path=tls_certs["ca_cert"], server_side=True,
        refresh_interval=30.0,
    )
    a = RpcServer(port=0, ssl_manager=mgr)
    b = RpcServer(port=0, ssl_manager=mgr)
    a.add_handler(EchoHandler())
    b.add_handler(EchoHandler())
    a.start()
    b.start()
    assert refresh_threads() == base + 1  # one shared thread
    a.stop()
    assert refresh_threads() == base + 1  # b still needs it
    b.stop()
    assert refresh_threads() == base  # last user out: reaped


def test_tls_context_refresh_picks_up_rotated_certs(tls_certs, tmp_path):
    """Rotating cert files and force_refresh()ing must keep new
    handshakes working (the refreshable-context contract)."""
    import shutil

    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer
    from rocksplicator_tpu.utils.ssl_context_manager import SslContextManager

    # server certs live at a rotating path
    live = tmp_path / "live"
    live.mkdir()
    for k in ("server_cert", "server_key", "ca_cert"):
        shutil.copy(tls_certs[k], str(live / k))
    server_mgr = SslContextManager(
        str(live / "server_cert"), str(live / "server_key"),
        ca_path=str(live / "ca_cert"), server_side=True,
        refresh_interval=0.0,
    )
    _, client_mgr = _managers(tls_certs)
    server = RpcServer(port=0, ssl_manager=server_mgr)
    server.add_handler(EchoHandler())
    server.start()
    ioloop = IoLoop.default()
    try:
        pool1 = RpcClientPool(ssl_manager=client_mgr)

        async def go(pool):
            return await pool.call("127.0.0.1", server.port, "echo",
                                   {"text": "hi"}, timeout=10)

        assert ioloop.run_sync(go(pool1), timeout=15)["text"] == "hi"
        ioloop.run_sync(pool1.close())
        # rotate: mint a genuinely NEW server cert under the SAME CA
        from rocksplicator_tpu.utils.ssl_context_manager import reissue_cert
        reissue_cert(tls_certs, "server",
                     str(live / "server_cert"), str(live / "server_key"))
        server_mgr.force_refresh()
        pool2 = RpcClientPool(ssl_manager=client_mgr)
        assert ioloop.run_sync(go(pool2), timeout=15)["text"] == "hi"
        ioloop.run_sync(pool2.close())
    finally:
        server.stop()
