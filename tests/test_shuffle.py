"""Shuffle tests: partitioning kernels, exchange-based multi-partition
queries, the TCP transport client/server, and heartbeats
(model: tests/.../shuffle suites — in-process, no real cluster)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.shuffle.heartbeat import (HeartbeatEndpoint,
                                                HeartbeatManager)
from spark_rapids_tpu.shuffle.manager import (ShuffleBlockId,
                                              TpuShuffleManager)
from spark_rapids_tpu.testing.asserts import (
    assert_tpu_and_cpu_are_equal_collect)
from spark_rapids_tpu.testing.data_gen import (IntegerGen, LongGen,
                                               StringGen, gen_df)


def test_hash_partition_ids_consistent_engines():
    """Murmur3 partition routing must agree across engines."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.device import batch_to_device
    from spark_rapids_tpu.expr.core import EvalContext, AttributeReference
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    rb = pa.record_batch({"k": pa.array([1, 2, 3, None, 5, 6, 7, 8],
                                        type=pa.int64())})
    part = HashPartitioning([AttributeReference("k")], 4).bind(
        ["k"], [__import__("spark_rapids_tpu.types",
                           fromlist=["LONG"]).LONG])
    out = {}
    for xp in (np, jnp):
        b = batch_to_device(rb, xp=xp)
        ctx = EvalContext(xp, b)
        pids = part.partition_ids(xp, ctx, b)
        out[xp.__name__] = np.asarray(pids)[:8].tolist()
    assert out["numpy"] == out["jax.numpy"]
    assert all(0 <= p < 4 for p in out["numpy"])


@pytest.mark.parametrize("n_parts", [2, 4])
def test_multi_partition_aggregate(n_parts):
    def q(spark):
        df = gen_df(spark, [("k", IntegerGen(lo=0, hi=40)),
                            ("v", LongGen())], length=2048,
                    num_partitions=n_parts)
        return df.group_by(col("k")).agg(F.sum(col("v")).alias("s"),
                                         F.count("*").alias("c"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_multi_partition_string_group():
    def q(spark):
        df = gen_df(spark, [("k", StringGen(max_len=5)),
                            ("v", LongGen())], length=1024,
                    num_partitions=3)
        return df.group_by(col("k")).agg(F.count("*").alias("c"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_multi_partition_join():
    def q(spark):
        a = gen_df(spark, [("k", IntegerGen(lo=0, hi=30)),
                           ("va", LongGen())], length=512, seed=1,
                   num_partitions=3)
        b = gen_df(spark, [("k2", IntegerGen(lo=0, hi=30)),
                           ("vb", LongGen())], length=256, seed=2,
                   num_partitions=2)
        return a.join(b, on=(col("k") == col("k2")), how="inner")
    assert_tpu_and_cpu_are_equal_collect(q)


def test_expanding_join_through_exchange_is_exact():
    """Regression: a hash join whose output EXCEEDS the probe batch
    capacity, feeding a shuffle exchange that materializes under the AQE
    reader's private ExecContext.  When the join guessed its output's
    capacity, the failed guard died with that private context, so the
    catalog kept the TRUNCATED map blocks and the query silently lost
    rows (each partition contributed exactly its capacity-bucket of join
    output).  The join now sizes its output from the count's sizes; the
    reader still verifies whatever guards its map stage registered."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs import metrics as m
    rng = np.random.default_rng(11)
    n, k, dups = 32_768, 4_096, 4     # 8192-row partitions, 4x expansion
    fact = pa.table({
        "k": pa.array(rng.integers(0, k, n).astype(np.int64)),
        "v": pa.array(rng.integers(-100, 100, n).astype(np.int64))})
    dim = pa.table({
        "k": pa.array(np.repeat(np.arange(k, dtype=np.int64), dups)),
        "w": pa.array(np.arange(k * dups, dtype=np.int64))})
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.singleChipFuse", "off")
         .get_or_create())
    fdf = s.create_dataframe(fact, num_partitions=4)
    ddf = s.create_dataframe(dim, num_partitions=4)
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    blocks_before = TpuShuffleManager.get().catalog.num_blocks()
    out = (fdf.join(ddf, on="k", how="left").group_by(col("k"))
           .agg(F.sum(col("w")).alias("sw"), F.count("*").alias("c"))
           .collect())
    # a replicated build reader with a stale pre-clone partner used to
    # shuffle the probe side a second time during planning and leak
    # every block it wrote (no plan node owned that shuffle id)
    assert TpuShuffleManager.get().catalog.num_blocks() == blocks_before
    kf = fact.column("k").to_numpy()
    sum_w = np.zeros(k, np.int64)
    np.add.at(sum_w, dim.column("k").to_numpy(),
              dim.column("w").to_numpy())
    fcnt = np.bincount(kf, minlength=k)
    present = np.flatnonzero(fcnt)
    assert out.num_rows == len(present)
    # every probe row matches `dups` build rows: exact totals, no
    # capacity-truncated partial input
    assert sum(out.column("c").to_pylist()) == n * dups
    order = np.argsort(out.column("k").to_numpy())
    assert np.array_equal(np.sort(out.column("k").to_numpy()), present)
    assert np.array_equal(
        np.asarray(out.column("sw").to_numpy())[order],
        (fcnt * sum_w)[present])


def test_shuffled_join_releases_all_planning_shuffles():
    """Regression: transition insertion clones every node, and its
    num_partitions probe EXECUTES the plan (the AQE reader over the agg
    exchange materializes its map stage to size its specs).  The
    replicated build reader's ``replicate_for`` still pointed at the
    PRE-clone probe partner at that moment, so the stale partner
    shuffled the probe side a second time — a shuffle no node in the
    final plan owned, leaking every block it wrote.  Partners must be
    relinked before anything can trigger materialization."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    rng = np.random.default_rng(7)
    dim_rows, probe_rows = 4_096, 16_384
    dim = pa.table({"k": pa.array(np.arange(dim_rows, dtype=np.int64)),
                    "w": pa.array(np.arange(dim_rows, dtype=np.int64))})
    fact = pa.table({"k": pa.array(
        rng.integers(0, dim_rows, probe_rows).astype(np.int64))})
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.singleChipFuse", "off")
         .config("spark.rapids.sql.autoBroadcastJoinThreshold", 1024)
         .get_or_create())
    fdf = s.create_dataframe(fact, num_partitions=4)
    ddf = s.create_dataframe(dim, num_partitions=4)
    before = TpuShuffleManager.get().catalog.num_blocks()
    out = (fdf.join(ddf, on="k", how="left").group_by(col("k"))
           .agg(F.sum(col("w")).alias("sw")).collect())
    kinds = []
    s.last_plan.foreach(lambda e: kinds.append(type(e).__name__))
    assert "ShuffledHashJoinExec" in kinds
    assert out.num_rows == len(np.unique(fact.column("k").to_numpy()))
    assert TpuShuffleManager.get().catalog.num_blocks() == before


def test_multi_partition_global_sort():
    def q(spark):
        df = gen_df(spark, [("a", IntegerGen()), ("b", LongGen())],
                    length=1024, num_partitions=4)
        return df.order_by(col("a"), col("b"))
    assert_tpu_and_cpu_are_equal_collect(q, ignore_order=False)


def test_repartition_roundtrip():
    def q(spark):
        df = gen_df(spark, [("k", IntegerGen()), ("v", LongGen())],
                    length=512, num_partitions=2)
        return df.repartition(5, col("k")).group_by(col("k")).agg(
            F.count("*").alias("c"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_shuffle_serialization_roundtrip():
    from spark_rapids_tpu.columnar.device import (batch_to_arrow,
                                                  batch_to_device)
    from spark_rapids_tpu.memory.meta import (deserialize_batch,
                                              serialize_batch)
    rb = pa.record_batch({
        "a": pa.array([1, None, 3], type=pa.int64()),
        "s": pa.array(["x", "yy", None])})
    b = batch_to_device(rb, xp=np)
    data = serialize_batch(b)
    back = deserialize_batch(data, xp=np)
    assert batch_to_arrow(back).to_pylist() == rb.to_pylist()


def test_transport_fetch():
    """Client/server over real sockets, serving catalog blocks."""
    from spark_rapids_tpu.columnar.device import (batch_to_arrow,
                                                  batch_to_device)
    from spark_rapids_tpu.shuffle.transport import (ShuffleClient,
                                                    ShuffleServer)
    TpuShuffleManager.reset()
    mgr = TpuShuffleManager.get()
    rb = pa.record_batch({"a": pa.array(list(range(100)), type=pa.int64())})
    b = batch_to_device(rb, xp=np)
    mgr.write_map_output(7, 0, {3: b})
    server = ShuffleServer(mgr).start()
    try:
        cli = ShuffleClient("127.0.0.1", server.port)
        metas = cli.fetch_metadata(7, 3).wait(10)
        assert len(metas) == 1
        (sid, mid, rid, idx), meta = metas[0]
        assert (sid, mid, rid) == (7, 0, 3)
        assert meta.num_rows == 100
        got = cli.fetch_block(sid, mid, rid, idx).wait(10)
        assert batch_to_arrow(got).to_pylist() == rb.to_pylist()
        # error path: missing block -> fetch-failed
        from spark_rapids_tpu.shuffle.errors import (
            TpuShuffleFetchFailedError)
        with pytest.raises(TpuShuffleFetchFailedError):
            cli.fetch_block(7, 0, 3, 99).wait(10)
        cli.close()
    finally:
        server.stop()
        TpuShuffleManager.reset()


def _serve_blocks(n_maps=4, rows=64, shuffle_id=11, reduce_id=2):
    """A manager with n_maps map outputs for one reduce partition, plus
    a running server. Caller owns cleanup."""
    from spark_rapids_tpu.columnar.device import batch_to_device
    from spark_rapids_tpu.shuffle.transport import ShuffleServer
    TpuShuffleManager.reset()
    mgr = TpuShuffleManager.get()
    for mid in range(n_maps):
        rb = pa.record_batch({"a": pa.array(
            [mid * 1000 + i for i in range(rows)], type=pa.int64())})
        mgr.write_map_output(shuffle_id, mid,
                             {reduce_id: batch_to_device(rb, xp=np)})
    return mgr, ShuffleServer(mgr).start()


def test_async_fetcher_happy_path():
    """Pipelined fetch yields every block in order and counts blocks +
    bytes in the tpu_shuffle_fetch_* metrics."""
    import spark_rapids_tpu.obs.metrics as m
    from spark_rapids_tpu.columnar.device import batch_to_arrow
    from spark_rapids_tpu.shuffle.transport import (AsyncBlockFetcher,
                                                    ShuffleClient)
    m.MetricsRegistry.reset_for_tests()
    mgr, server = _serve_blocks(n_maps=5)
    try:
        cli = ShuffleClient("127.0.0.1", server.port)
        fetched = [batch_to_arrow(b).column("a").to_pylist()[0]
                   for b in AsyncBlockFetcher(cli, 11, 2, window=2)]
        assert fetched == [0, 1000, 2000, 3000, 4000]
        assert m.counter("tpu_shuffle_fetch_blocks_total").value() == 5
        assert m.counter("tpu_shuffle_fetch_bytes_total").value() > 0
        cli.close()
    finally:
        server.stop()
        TpuShuffleManager.reset()
        m.MetricsRegistry.reset_for_tests()


def test_async_fetcher_server_killed_mid_fetch():
    """Killing the ShuffleServer while the iterator drains must surface
    a typed shuffle error (not hang, not a bare socket error) and count
    it in tpu_shuffle_fetch_errors_total."""
    import spark_rapids_tpu.obs.metrics as m
    from spark_rapids_tpu.shuffle.errors import TpuShuffleFetchFailedError
    from spark_rapids_tpu.shuffle.transport import (AsyncBlockFetcher,
                                                    ShuffleClient)
    m.MetricsRegistry.reset_for_tests()
    mgr, server = _serve_blocks(n_maps=8)
    cli = ShuffleClient("127.0.0.1", server.port)
    try:
        it = iter(AsyncBlockFetcher(cli, 11, 2, window=1, timeout=5.0))
        next(it)  # first block arrives fine
        server.stop()
        server = None
        with pytest.raises(TpuShuffleFetchFailedError):
            for _ in it:
                pass
        errs = m.counter("tpu_shuffle_fetch_errors_total",
                         labelnames=("kind",))
        assert sum(errs.value(kind=k) for k in
                   ("fetch_failed", "timeout", "truncated")) >= 1
        cli.close()
    finally:
        if server is not None:
            server.stop()
        TpuShuffleManager.reset()
        m.MetricsRegistry.reset_for_tests()


def test_async_fetcher_heartbeat_dead_peer():
    """A peer that heartbeat expiry declares dead fails the fetch with
    TpuShufflePeerDeadError BEFORE paying a socket timeout."""
    import spark_rapids_tpu.obs.metrics as m
    from spark_rapids_tpu.shuffle.errors import TpuShufflePeerDeadError
    from spark_rapids_tpu.shuffle.transport import (AsyncBlockFetcher,
                                                    ShuffleClient)
    import time
    m.MetricsRegistry.reset_for_tests()
    mgr, server = _serve_blocks(n_maps=2)
    try:
        hb = HeartbeatManager(timeout_s=0.2)
        hb.register_executor("peer-1", "127.0.0.1", server.port)
        time.sleep(0.4)  # peer-1 stops heartbeating -> expires
        cli = ShuffleClient("127.0.0.1", server.port)
        f = AsyncBlockFetcher(cli, 11, 2, heartbeat=hb, peer_id="peer-1")
        with pytest.raises(TpuShufflePeerDeadError) as ei:
            list(f)
        assert ei.value.peer_id == "peer-1"
        assert m.counter("tpu_shuffle_fetch_errors_total",
                         labelnames=("kind",)).value(kind="peer_dead") == 1
        cli.close()
    finally:
        server.stop()
        TpuShuffleManager.reset()
        m.MetricsRegistry.reset_for_tests()


def test_truncated_frame_typed_error():
    """A peer that dies mid-frame produces TpuShuffleTruncatedFrameError
    with the expected/got byte counts."""
    import socket
    import struct as _struct
    import threading
    from spark_rapids_tpu.shuffle.errors import (
        TpuShuffleTruncatedFrameError)
    from spark_rapids_tpu.shuffle.transport import (_FRAME,
                                                    MSG_METADATA_RESP,
                                                    ShuffleClient)

    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]

    def liar():
        conn, _ = lsock.accept()
        conn.recv(4096)  # the metadata request
        # declare a 100-byte body, deliver 10, vanish
        conn.sendall(_FRAME.pack(MSG_METADATA_RESP, 1, 100) + b"x" * 10)
        conn.close()

    t = threading.Thread(target=liar, daemon=True)
    t.start()
    try:
        cli = ShuffleClient("127.0.0.1", port, timeout=5.0)
        with pytest.raises(TpuShuffleTruncatedFrameError) as ei:
            cli.fetch_metadata(1, 0).wait(5)
        assert ei.value.expected == 100 and ei.value.got == 10
        cli.close()
    finally:
        lsock.close()
        t.join(timeout=2)


def test_sliced_map_output_zero_leaks():
    """Slice-view write path: one spill registration per map batch, per-
    reduce views serve correct rows, and unregister releases everything
    (no leaked blocks, clean SpillCatalog)."""
    from spark_rapids_tpu.columnar.device import (batch_to_arrow,
                                                  batch_to_device)
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.shuffle.manager import materialize_block
    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()
    mgr = TpuShuffleManager.get()
    # rows sorted by target partition: [0..9]->r0, [10..24]->r1, [25..39]->r2
    rb = pa.record_batch({"a": pa.array(list(range(40)), type=pa.int64())})
    mgr.write_map_output_sorted(
        3, 0, batch_to_device(rb, xp=np),
        layout=[(0, 0, 10), (1, 10, 15), (2, 25, 15)])
    assert mgr.catalog.num_blocks() == 3
    assert mgr.catalog.device_bytes() > 0
    got = [materialize_block(b, np) for b in mgr.read_partition(3, 1)]
    assert len(got) == 1
    assert batch_to_arrow(got[0]).column("a").to_pylist() == \
        list(range(10, 25))
    mgr.unregister(3)
    assert mgr.catalog.num_blocks() == 0
    leaks = SpillCatalog.get().leak_report()
    assert not leaks, leaks
    TpuShuffleManager.reset()


def test_heartbeats():
    mgr = HeartbeatManager(timeout_s=0.5)
    seen = {}
    e1 = HeartbeatEndpoint(mgr, "exec-1", "h1", 1111, interval_s=0.1,
                           on_peers=lambda ps: seen.__setitem__(
                               "e1", [p.executor_id for p in ps]))
    peers2 = mgr.register_executor("exec-2", "h2", 2222)
    assert [p.executor_id for p in peers2] == ["exec-1"]
    e1.start()
    import time
    time.sleep(0.3)
    assert seen.get("e1") == ["exec-2"]
    e1.stop()
    # exec-1 stops heartbeating; after timeout it expires
    time.sleep(0.7)
    mgr.executor_heartbeat("exec-2")
    assert [p.executor_id for p in mgr.live_peers()] == ["exec-2"]
    assert mgr.expire_dead() == ["exec-1"]
