"""Queue-ingestion stack tests (reference: common/kafka/tests with
MockKafkaCluster/MockKafkaConsumer; admin ingestion paths)."""

import struct
import time

import pytest

from rocksplicator_tpu.kafka.broker import (
    MockConsumer,
    MockKafkaCluster,
    get_cluster,
    reset_clusters_for_test,
)
from rocksplicator_tpu.kafka.publisher import QueuePublisher
from rocksplicator_tpu.kafka.watcher import (
    KafkaBrokerFileWatcher,
    KafkaConsumerPool,
    KafkaWatcher,
)
from rocksplicator_tpu.storage.records import OpType, decode_batch


def wait_until(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


@pytest.fixture(autouse=True)
def _fresh_clusters():
    reset_clusters_for_test()
    yield
    reset_clusters_for_test()


# ---------------------------------------------------------------------------
# broker + consumer
# ---------------------------------------------------------------------------


def test_produce_consume_roundtrip():
    cluster = MockKafkaCluster()
    cluster.create_topic("t", 2)
    cluster.produce("t", 0, b"k1", b"v1", timestamp_ms=100)
    cluster.produce("t", 1, b"k2", b"v2", timestamp_ms=200)
    cluster.produce("t", 0, b"k3", b"v3", timestamp_ms=300)
    c = MockConsumer(cluster)
    c.assign("t", [0, 1])
    got = [c.consume(0.5) for _ in range(3)]
    assert sorted((m.key, m.value) for m in got) == [
        (b"k1", b"v1"), (b"k2", b"v2"), (b"k3", b"v3")
    ]
    assert c.consume(0.05) is None  # drained


def test_timestamp_seek():
    cluster = MockKafkaCluster()
    cluster.create_topic("t", 1)
    for i in range(10):
        cluster.produce("t", 0, f"k{i}".encode(), b"v", timestamp_ms=i * 100)
    c = MockConsumer(cluster)
    c.assign("t", [0])
    c.seek_to_timestamp(450)  # first message at ts >= 450 is k5
    msg = c.consume(0.5)
    assert msg.key == b"k5"


def test_consumer_commit_and_blocking_fetch():
    cluster = MockKafkaCluster()
    cluster.create_topic("t", 1)
    c = MockConsumer(cluster)
    c.assign("t", [0])
    import threading

    results = []
    t = threading.Thread(target=lambda: results.append(c.consume(5.0)))
    t.start()
    time.sleep(0.1)
    cluster.produce("t", 0, b"late", b"v")
    t.join(timeout=5)
    assert results and results[0].key == b"late"
    c.commit()
    assert c.committed == {0: 1}


def test_consumer_pool():
    cluster = MockKafkaCluster()
    pool = KafkaConsumerPool(2, lambda: MockConsumer(cluster))
    a = pool.acquire()
    b = pool.acquire()
    with pytest.raises(Exception):
        pool.acquire(timeout=0.05)
    pool.release(a)
    assert pool.acquire(timeout=1) is a


# ---------------------------------------------------------------------------
# watcher: replay then live
# ---------------------------------------------------------------------------


def test_watcher_replay_then_live():
    cluster = MockKafkaCluster()
    cluster.create_topic("t", 1)
    for i in range(5):
        cluster.produce("t", 0, f"old{i}".encode(), b"v", timestamp_ms=1000 + i)
    seen = []
    watcher = KafkaWatcher(
        "w", MockConsumer(cluster), "t", [0], start_timestamp_ms=1002,
        on_message=lambda m, replay: seen.append((m.key, replay)),
    ).start()
    assert wait_until(lambda: watcher.replay_done.is_set())
    # replay starts at ts>=1002 (old2..old4), flagged as replay
    assert [(k, r) for k, r in seen] == [
        (b"old2", True), (b"old3", True), (b"old4", True)
    ]
    cluster.produce("t", 0, b"live1", b"v")
    assert wait_until(lambda: (b"live1", False) in seen)
    watcher.stop()


# ---------------------------------------------------------------------------
# broker serverset file watcher
# ---------------------------------------------------------------------------


def test_broker_file_watcher(tmp_path, file_watcher):
    path = tmp_path / "brokers"
    path.write_text("# comment\n10.0.0.1:9092\n10.0.0.2:9092\n")
    w = KafkaBrokerFileWatcher(str(path))
    assert w.broker_list == ["10.0.0.1:9092", "10.0.0.2:9092"]
    path.write_text("10.0.0.3:9092\n")
    file_watcher.poll_now()
    assert w.broker_list == ["10.0.0.3:9092"]
    w.close()


# ---------------------------------------------------------------------------
# end-to-end message ingestion via admin RPC
# ---------------------------------------------------------------------------


def test_message_ingestion_end_to_end(tmp_path):
    from tests.test_admin import FAST, AdminNode
    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool

    cluster = get_cluster("default")
    cluster.create_topic("events", 2)
    # pre-produce history with known timestamps
    for i in range(10):
        cluster.produce("events", 1, f"k{i}".encode(), f"v{i}".encode(),
                        timestamp_ms=1000 + i)
    node = AdminNode(tmp_path, "a")
    ioloop = IoLoop.default()
    pool = RpcClientPool()

    def call(method, **args):
        async def go():
            return await pool.call("127.0.0.1", node.admin_port, method, args)

        return ioloop.run_sync(go())

    try:
        # db for shard 1 consumes partition 1
        call("add_db", db_name="ev00001", role="LEADER")
        call("start_message_ingestion", db_name="ev00001",
             topic_name="events",
             kafka_broker_serverset_path="embedded://default")
        app_db = node.handler.db_manager.get_db("ev00001")
        assert wait_until(lambda: app_db.get(b"k9") == b"v9")
        # live messages flow; empty value = delete
        cluster.produce("events", 1, b"knew", b"x", timestamp_ms=5000)
        cluster.produce("events", 1, b"k0", b"", timestamp_ms=6000)
        assert wait_until(lambda: app_db.get(b"knew") == b"x")
        assert wait_until(lambda: app_db.get(b"k0") is None)
        # duplicate start rejected
        from rocksplicator_tpu.rpc import RpcApplicationError

        with pytest.raises(RpcApplicationError):
            call("start_message_ingestion", db_name="ev00001",
                 topic_name="events",
                 kafka_broker_serverset_path="embedded://default")
        call("stop_message_ingestion", db_name="ev00001")
        # timestamp persisted on stop: restart resumes (no duplicate replay
        # semantics guarantee here — resume-from-timestamp re-reads the last
        # window, reference does the same via replay)
        meta = node.handler.get_meta_data("ev00001")
        assert meta.last_kafka_msg_timestamp_ms == 6000
    finally:
        ioloop.run_sync(pool.close())
        node.stop()


# ---------------------------------------------------------------------------
# networked broker (kafka/network.py — the librdkafka-analog backend)
# ---------------------------------------------------------------------------


def test_network_broker_roundtrip():
    from rocksplicator_tpu.kafka.network import (
        BrokerServer, NetworkConsumer, NetworkProducer,
    )

    srv = BrokerServer(port=0).start()
    try:
        prod = NetworkProducer("127.0.0.1", srv.port)
        prod.create_topic("t", 2)
        for i in range(20):
            prod.produce("t", i % 2, f"k{i}".encode(), f"v{i}".encode(),
                         timestamp_ms=1000 + i)
        cons = NetworkConsumer("127.0.0.1", srv.port, group_id="g1")
        cons.assign("t", [0, 1])
        got = {}
        for _ in range(20):
            m = cons.consume(5.0)
            assert m is not None
            got[m.key] = m.value
        assert got[b"k7"] == b"v7" and len(got) == 20
        assert cons.consume(0.1) is None  # drained
        assert cons.high_watermark(0) == 10
        # timestamp seek replays the tail
        cons.seek_to_timestamp(1018)
        replay = [cons.consume(5.0) for _ in range(2)]
        assert sorted(m.key for m in replay) == [b"k18", b"k19"]
        # commit round-trips through the broker
        cons.commit()
        assert cons.committed == {0: 10, 1: 10}
    finally:
        srv.stop()


def test_network_broker_durable_restart(tmp_path):
    from rocksplicator_tpu.kafka.network import (
        BrokerServer, NetworkConsumer, NetworkProducer,
    )

    data = str(tmp_path / "broker")
    srv = BrokerServer(port=0, data_dir=data).start()
    prod = NetworkProducer("127.0.0.1", srv.port)
    prod.create_topic("t", 1)
    for i in range(5):
        prod.produce("t", 0, f"k{i}".encode(), f"v{i}".encode(),
                     timestamp_ms=100 + i)
    cons = NetworkConsumer("127.0.0.1", srv.port, group_id="g")
    cons.assign("t", [0])
    for _ in range(5):
        assert cons.consume(5.0) is not None
    cons.commit()
    srv.stop()
    # restart on the same data_dir: log + committed offsets survive
    srv2 = BrokerServer(port=0, data_dir=data).start()
    try:
        cons2 = NetworkConsumer("127.0.0.1", srv2.port, group_id="g")
        cons2.assign("t", [0])
        assert cons2.committed == {0: 5}
        assert cons2.high_watermark(0) == 5
        cons2.seek_to_timestamp(103)  # resume-from-timestamp post-restart
        m = cons2.consume(5.0)
        assert m is not None and m.key == b"k3"
        prod2 = NetworkProducer("127.0.0.1", srv2.port)
        assert prod2.produce("t", 0, b"knew", b"x") == 5  # offsets continue
    finally:
        srv2.stop()


def test_consumer_app_tails_broker_across_processes(tmp_path):
    """VERDICT item 5 'done' criterion: kafka_consumer_app tails a broker
    in another PROCESS; resume-from-timestamp works across a broker
    process restart."""
    import os
    import re
    import subprocess
    import sys

    from rocksplicator_tpu.kafka.network import NetworkProducer

    env = dict(os.environ, PYTHONPATH=os.getcwd(),
               JAX_PLATFORMS="cpu")
    data = str(tmp_path / "bk")

    def spawn_broker():
        proc = subprocess.Popen(
            [sys.executable, "-m", "rocksplicator_tpu.kafka.network",
             "--port", "0", "--data_dir", data],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env,
        )
        line = proc.stdout.readline()
        m = re.search(r"port=(\d+)", line)
        assert m, f"no port in broker banner: {line!r}"
        return proc, int(m.group(1))

    broker, port = spawn_broker()
    try:
        prod = NetworkProducer("127.0.0.1", port)
        prod.create_topic("t", 1)
        for i in range(6):
            prod.produce("t", 0, f"k{i}".encode(), f"v{i}".encode(),
                         timestamp_ms=1000 + i)
        out = subprocess.run(
            [sys.executable, "-m",
             "examples.kafka_consumer_app.kafka_consumer_app",
             "--broker", f"127.0.0.1:{port}", "--topic", "t",
             "--replay_timestamp_ms", "1000", "--max_messages", "6"],
            capture_output=True, text=True, timeout=60, env=env,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.count("[replay]") + out.stdout.count("[live]") >= 6
        assert "k5" in out.stdout
        # kill the broker, restart on the same data, resume from ts 1004
        broker.terminate()
        broker.wait(timeout=10)
        broker, port = spawn_broker()
        out2 = subprocess.run(
            [sys.executable, "-m",
             "examples.kafka_consumer_app.kafka_consumer_app",
             "--broker", f"127.0.0.1:{port}", "--topic", "t",
             "--replay_timestamp_ms", "1004", "--max_messages", "2"],
            capture_output=True, text=True, timeout=60, env=env,
        )
        assert out2.returncode == 0, out2.stderr
        assert "k4" in out2.stdout and "k5" in out2.stdout
        assert "k3" not in out2.stdout  # seek honored the timestamp
    finally:
        broker.terminate()
        broker.wait(timeout=10)


def test_ingestion_via_network_broker(tmp_path):
    """start_message_ingestion with a broker://host:port path applies
    messages from a networked broker into the DB."""
    from tests.test_admin import AdminNode
    from rocksplicator_tpu.kafka.network import BrokerServer, NetworkProducer
    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool

    srv = BrokerServer(port=0).start()
    prod = NetworkProducer("127.0.0.1", srv.port)
    prod.create_topic("events", 2)
    for i in range(5):
        prod.produce("events", 1, f"k{i}".encode(), f"v{i}".encode(),
                     timestamp_ms=1000 + i)
    node = AdminNode(tmp_path, "a")
    ioloop = IoLoop.default()
    pool = RpcClientPool()

    def call(method, **args):
        async def go():
            return await pool.call("127.0.0.1", node.admin_port, method, args)

        return ioloop.run_sync(go())

    try:
        call("add_db", db_name="ev00001", role="LEADER")
        call("start_message_ingestion", db_name="ev00001",
             topic_name="events",
             kafka_broker_serverset_path=f"broker://127.0.0.1:{srv.port}")
        app_db = node.handler.db_manager.get_db("ev00001")
        assert wait_until(lambda: app_db.get(b"k4") == b"v4")
        prod.produce("events", 1, b"klive", b"y", timestamp_ms=2000)
        assert wait_until(lambda: app_db.get(b"klive") == b"y")
        call("stop_message_ingestion", db_name="ev00001")
    finally:
        ioloop.run_sync(pool.close())
        node.stop()
        srv.stop()


# ---------------------------------------------------------------------------
# CDC → queue publisher
# ---------------------------------------------------------------------------


def test_cdc_publishes_to_queue(tmp_path):
    from tests.test_admin import FAST, AdminNode
    from rocksplicator_tpu.admin.cdc import CdcAdminHandler
    from rocksplicator_tpu.storage import WriteBatch

    cluster = get_cluster("cdcq")
    node = AdminNode(tmp_path, "a")
    cdc_node = AdminNode(tmp_path, "cdc")
    publisher = QueuePublisher("cdc-updates", cluster, num_partitions=4)
    cdc = CdcAdminHandler(cdc_node.replicator, publisher)
    try:
        from rocksplicator_tpu.rpc import IoLoop

        ioloop = cdc_node.replicator.ioloop
        # leader with data-plane writes
        import asyncio

        node.handler.db_manager  # ensure constructed
        fut = ioloop.run_coro(node.handler.handle_add_db(
            db_name="seg00002", role="LEADER"))
        fut.result(10)
        ioloop.run_coro(cdc.handle_add_observer(
            db_name="seg00002", upstream_ip="127.0.0.1",
            upstream_port=node.replicator.port)).result(10)
        app_db = node.handler.db_manager.get_db("seg00002")
        app_db.write(WriteBatch().put(b"cdc-key", b"cdc-val"))
        consumer = MockConsumer(cluster)
        consumer.assign("cdc-updates", [2])  # shard 2 -> partition 2
        msg = None

        def got():
            nonlocal msg
            msg = consumer.consume(0.1)
            return msg is not None

        assert wait_until(got, timeout=15)
        assert msg.key == b"seg00002:1"
        ops = list(decode_batch(msg.value).ops())
        assert (OpType.PUT, b"cdc-key", b"cdc-val") in ops
    finally:
        cdc.close()
        cdc_node.stop()
        node.stop()


# ---------------------------------------------------------------------------
# admin CLI
# ---------------------------------------------------------------------------


def test_admin_cli_config_gen_and_status(tmp_path, capsys):
    import json

    from rocksplicator_tpu.admin.tool import admin_cli

    host_file = tmp_path / "hosts"
    host_file.write_text("10.0.0.1:9090:az1\n10.0.0.2:9090:az2\n")
    rc = admin_cli.main([
        "config_gen", "--host_file", str(host_file),
        "--segment", "seg", "--shard_num", "4", "--replicas", "2",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["seg"]["num_shards"] == 4
    markers = [e for k, v in out["seg"].items() if k != "num_shards" for e in v]
    assert sum(1 for m in markers if m.endswith(":M")) == 4
    assert sum(1 for m in markers if m.endswith(":S")) == 4


def test_admin_cli_ping_and_failover(tmp_path, capsys):
    import json

    from rocksplicator_tpu.admin.tool import admin_cli
    from tests.test_admin import AdminNode

    a = AdminNode(tmp_path, "a")
    b = AdminNode(tmp_path, "b")
    try:
        assert admin_cli.main(
            ["ping", "--port", str(a.admin_port)]) == 0
        capsys.readouterr()
        # build a live shard map: a leads shard 0, b follows
        shard_map = {
            "seg": {
                "num_shards": 1,
                f"127.0.0.1:{a.admin_port}:az1:{a.replicator.port}": ["00000:M"],
                f"127.0.0.1:{b.admin_port}:az1:{b.replicator.port}": ["00000:S"],
            }
        }
        map_file = tmp_path / "map.json"
        map_file.write_text(json.dumps(shard_map))
        from rocksplicator_tpu.cluster.helix_utils import AdminClient

        admin = AdminClient()
        admin.add_db((("127.0.0.1"), a.admin_port), "seg00000", "LEADER")
        admin.add_db(("127.0.0.1", b.admin_port), "seg00000", "FOLLOWER",
                     ("127.0.0.1", a.replicator.port))
        # status shows both replicas
        assert admin_cli.main(["status", "--shard_map", str(map_file)]) == 0
        out = capsys.readouterr().out
        assert "seg00000 M" in out and "seg00000 S" in out
        # failover: promote b
        rc = admin_cli.main([
            "failover", "--shard_map", str(map_file), "--segment", "seg",
            "--shard", "0", "--new_leader", f"127.0.0.1:{b.admin_port}",
        ])
        assert rc == 0
        check = admin.check_db(("127.0.0.1", b.admin_port), "seg00000")
        assert check["role"] == "LEADER"
        check_a = admin.check_db(("127.0.0.1", a.admin_port), "seg00000")
        assert check_a["role"] == "FOLLOWER"
        admin.close()
    finally:
        a.stop()
        b.stop()


# ---------------------------------------------------------------------------
# rpcgrep proxy
# ---------------------------------------------------------------------------


def test_rpcgrep_decodes_proxied_traffic(tmp_path, capsys):
    import re
    import socket
    import threading

    from tests.test_admin import AdminNode
    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool

    node = AdminNode(tmp_path, "a")
    # free port for the proxy
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    proxy_port = s.getsockname()[1]
    s.close()

    import asyncio

    sys_path_root = __import__("sys").path[0]
    from tools import rpcgrep

    stop_loop = {}

    def run_proxy():
        loop = asyncio.new_event_loop()
        stop_loop["loop"] = loop
        asyncio.set_event_loop(loop)
        task = loop.create_task(rpcgrep.serve(
            proxy_port, "127.0.0.1", node.admin_port,
            re.compile("ping"), False,
        ))
        stop_loop["task"] = task
        try:
            loop.run_until_complete(task)
        except (Exception, asyncio.CancelledError):
            pass

    t = threading.Thread(target=run_proxy, daemon=True)
    t.start()
    time.sleep(0.5)
    ioloop = IoLoop.default()
    pool = RpcClientPool()

    async def go():
        return await pool.call("127.0.0.1", proxy_port, "ping", {})

    try:
        r = ioloop.run_sync(go())
        assert r["ok"] is True  # proxied call works end-to-end
        out = capsys.readouterr().out
        assert "method=ping" in out
        assert "reply id=" in out
    finally:
        ioloop.run_sync(pool.close())
        # cancel the serve task (not loop.stop) so the coroutine finishes
        # cleanly instead of leaking a never-awaited warning
        stop_loop["loop"].call_soon_threadsafe(stop_loop["task"].cancel)
        t.join(timeout=5)
        node.stop()


def test_rpcgrep_passive_sniff_decodes_live_traffic(tmp_path):
    """tgrep parity: the AF_PACKET passive mode must decode request and
    reply frames off live loopback traffic with NO proxy in the path.
    Skipped where CAP_NET_RAW is unavailable."""
    import os
    import socket
    import subprocess
    import sys as _sys
    import time as _time

    try:
        probe = socket.socket(socket.AF_PACKET, socket.SOCK_RAW,
                              socket.htons(0x0003))
        probe.close()
    except (PermissionError, AttributeError, OSError):
        pytest.skip("CAP_NET_RAW unavailable")

    from rocksplicator_tpu.admin import AdminHandler
    from rocksplicator_tpu.replication import Replicator
    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer

    repl = Replicator(port=0)
    handler = AdminHandler(str(tmp_path / "dbs"), repl)
    server = RpcServer(port=0, ioloop=repl.ioloop)
    server.add_handler(handler)
    server.start()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sniffer = subprocess.Popen(
        [_sys.executable, os.path.join(repo_root, "tools", "rpcgrep.py"),
         "--sniff", str(server.port), "--iface", "lo", "--show-args"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=repo_root)
    try:
        # wait for the sniffer to report its socket is bound (python
        # startup under a loaded CI box can take seconds)
        banner = sniffer.stdout.readline()
        assert "sniffing" in banner, banner
        _time.sleep(0.5)
        ioloop, pool = IoLoop.default(), RpcClientPool()

        def call(method, **a):
            async def go():
                return await pool.call("127.0.0.1", server.port, method, a,
                                       timeout=30)

            return ioloop.run_sync(go())

        call("add_db", db_name="seg00042", role="LEADER")
        call("get_sequence_number", db_name="seg00042")
        _time.sleep(1.5)
    finally:
        sniffer.terminate()
        out, _ = sniffer.communicate(timeout=15)
        server.stop()
        handler.close()
        repl.stop()
    assert "method=add_db" in out, out[-2000:]  # banner already consumed
    assert "method=get_sequence_number" in out
    assert "ok=True" in out
    assert "seg00042" in out  # --show-args decoded the payload
