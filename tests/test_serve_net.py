"""Network serving plane acceptance tests.

The contracts from the issue:
  * codec round-trips every message type; truncated/corrupt/misversioned
    bytes raise typed errors (and the server answers them typed);
  * localhost client → server → fleet answers match direct
    ``IndexFleet.query`` (identical gids, distances to float32 rounding)
    on routed AND exhaustive modes;
  * double-buffered admission demonstrably overlaps — batch N+1 is
    admitted while tick N executes;
  * backpressure (``RETRY_LATER``) and per-tenant quotas
    (``QUOTA_EXCEEDED``) refuse typed instead of queueing unboundedly;
  * graceful shutdown answers every admitted request before closing;
  * the legacy mutable-QueryRequest path still works, deprecated once.
"""
import socket
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                # container fallback
    from tests._hypothesis_fallback import given, settings, st

from repro.data import make_dataset, make_queries
from repro.eval.metrics import assert_same_answers
from repro.fleet import FleetConfig, FleetEngine, IndexFleet
from repro.obs import REGISTRY, to_prometheus
from repro.serve import ClimberEngine, api
from repro.serve import knn_engine as knn_engine_mod
from repro.serve.net import (ClimberClient, FrameError, RetryLater,
                             ServerError, codec, schema, serve_in_thread)
from repro.serve.net.server import ClimberServer
from repro.utils.config import ClimberConfig

K = 10
SCALE = 2 * 64          # bound on |q|^2 + |x|^2 for z-normalised length 64


def small_cfg() -> ClimberConfig:
    return ClimberConfig(series_len=64, paa_segments=8, num_pivots=32,
                         prefix_len=5, capacity=128, sample_frac=0.3,
                         max_centroids=12, k=K, candidate_groups=4,
                         adaptive_factor=4)


@pytest.fixture(scope="module")
def fleet_setup():
    cfg = small_cfg()
    data = np.asarray(make_dataset("randomwalk", jax.random.PRNGKey(0),
                                   1200, 64))
    queries = np.asarray(make_queries(jax.random.PRNGKey(2),
                                      jnp.asarray(data), 6))
    fleet = IndexFleet(FleetConfig(shard_cfg=cfg, fanout=2,
                                   delta_capacity=4096, auto_compact=False))
    for i in range(2):
        fleet.add_shard(f"tenant{i}", data[i * 600:(i + 1) * 600])
    return fleet, data, queries


def roundtrip(mtype, msg):
    frame = schema.encode_message(mtype, msg)
    got_type, length, _ = codec.decode_header(frame)
    assert length == len(frame) - codec.HEADER_LEN
    return schema.decode_message(got_type, frame[codec.HEADER_LEN:])


# -- codec / schema ---------------------------------------------------------

class TestCodec:
    @settings(max_examples=25)
    @given(st.integers(min_value=1, max_value=256),
           st.integers(min_value=0, max_value=64),
           st.integers(min_value=0, max_value=2**31),
           st.sampled_from(["", "tenant0", "αβγ-tenant"]))
    def test_query_roundtrip(self, series_len, k, rid, tenant):
        rng = np.random.default_rng(series_len * 31 + k)
        req = api.QueryRequest(
            series=rng.standard_normal(series_len).astype(np.float32),
            k=k, tenant=tenant, request_id=rid)
        mtype, got = roundtrip(schema.MsgType.QUERY, req)
        assert mtype == schema.MsgType.QUERY
        assert (got.k, got.tenant, got.request_id) == (k, tenant, rid)
        np.testing.assert_array_equal(got.series, req.series)
        assert got.series.dtype == np.float32

    @settings(max_examples=25)
    @given(st.integers(min_value=1, max_value=64),
           st.floats(min_value=0.0, max_value=1e6))
    def test_result_roundtrip(self, k, latency_ms):
        rng = np.random.default_rng(k)
        res = api.QueryResult(
            request_id=7, dist=rng.random(k).astype(np.float32),
            gid=rng.integers(0, 1000, k).astype(np.int32),
            partitions_touched=3, candidates_scanned=128,
            latency_ms=latency_ms, batch_fill=0.5)
        mtype, got = roundtrip(schema.MsgType.RESULT, res)
        assert mtype == schema.MsgType.RESULT
        np.testing.assert_array_equal(got.dist, res.dist)
        np.testing.assert_array_equal(got.gid, res.gid)
        assert got.candidates_scanned == 128
        assert got.latency_ms == pytest.approx(latency_ms)

    @settings(max_examples=10)
    @given(st.sampled_from(api.ERROR_CODES))
    def test_error_roundtrip(self, code):
        err = api.ErrorReply(request_id=3, code=code, message="m",
                             retry_after_ms=2.5)
        mtype, got = roundtrip(schema.MsgType.ERROR, err)
        assert mtype == schema.MsgType.ERROR
        assert (got.code, got.message, got.retry_after_ms) == (code, "m", 2.5)

    def test_info_and_handshake_roundtrip(self):
        info = api.ServerInfo(series_len=64, k_max=10, batch_size=8,
                              engine="fleet", variant="adaptive",
                              routing="signature", shards=3,
                              max_pending=64, tenant_quota=4)
        _, got = roundtrip(schema.MsgType.SERVER_INFO, info)
        assert got == info
        _, hello = roundtrip(schema.MsgType.HELLO, {"client": "t"})
        assert hello == {"wire_version": api.WIRE_VERSION, "client": "t"}
        mtype, _ = roundtrip(schema.MsgType.BYE, {})
        assert mtype == schema.MsgType.BYE

    def test_truncated_header(self):
        with pytest.raises(FrameError) as ei:
            codec.decode_header(b"\x00" * 4)
        assert ei.value.code == "TRUNCATED"

    def test_bad_magic(self):
        frame = bytearray(schema.encode_message(schema.MsgType.BYE, {}))
        frame[0] ^= 0xFF
        with pytest.raises(FrameError) as ei:
            codec.decode_header(bytes(frame))
        assert ei.value.code == "BAD_MAGIC"

    def test_version_mismatch_rejected(self):
        frame = codec.encode_frame(int(schema.MsgType.BYE), b"",
                                   version=api.WIRE_VERSION + 1)
        with pytest.raises(FrameError) as ei:
            codec.decode_header(frame)
        assert ei.value.code == "VERSION_MISMATCH"
        assert ei.value.peer_version == api.WIRE_VERSION + 1

    @settings(max_examples=25)
    @given(st.integers(min_value=0, max_value=200))
    def test_corrupt_payload_byte_fails_crc(self, offset):
        """Any flipped payload bit is caught by the crc before np.load."""
        req = api.QueryRequest(series=np.zeros(32, np.float32))
        frame = bytearray(schema.encode_message(schema.MsgType.QUERY, req))
        offset = codec.HEADER_LEN + offset % (len(frame) - codec.HEADER_LEN)
        frame[offset] ^= 0x01
        a, b = socket.socketpair()
        try:
            a.sendall(bytes(frame))
            with pytest.raises(FrameError) as ei:
                codec.read_frame_sync(b)
            assert ei.value.code == "BAD_CRC"
        finally:
            a.close(); b.close()

    def test_valid_crc_garbage_payload(self):
        frame = codec.encode_frame(int(schema.MsgType.QUERY),
                                   b"not an npz archive")
        msg_type, _, _ = codec.decode_header(frame)
        with pytest.raises(FrameError) as ei:
            schema.decode_message(msg_type, frame[codec.HEADER_LEN:])
        assert ei.value.code == "BAD_PAYLOAD"

    def test_missing_field_is_typed(self):
        payload = codec.encode_payload({"k": np.asarray(3)})
        with pytest.raises(FrameError) as ei:
            schema.decode_message(int(schema.MsgType.QUERY), payload)
        assert ei.value.code == "BAD_PAYLOAD"

    def test_no_pickle_either_way(self):
        with pytest.raises(TypeError):
            codec.encode_payload({"evil": object()})

    def test_oversized_length_prefix_refused(self):
        header = codec.HEADER.pack(codec.MAGIC, api.WIRE_VERSION, 1, 0,
                                   codec.MAX_PAYLOAD + 1, 0)
        with pytest.raises(FrameError) as ei:
            codec.decode_header(header)
        assert ei.value.code == "TOO_LARGE"


# -- api dataclasses / ServingConfig ---------------------------------------

class TestApi:
    def test_error_reply_validates_code(self):
        with pytest.raises(ValueError):
            api.ErrorReply(request_id=0, code="NOT_A_CODE")

    def test_config_and_kwargs_exclusive(self, fleet_setup):
        fleet, _, _ = fleet_setup
        with pytest.raises(TypeError):
            FleetEngine(fleet, config=api.ServingConfig(), batch_size=4)

    def test_unknown_kwarg_rejected(self, fleet_setup):
        fleet, _, _ = fleet_setup
        with pytest.raises(TypeError):
            FleetEngine(fleet, not_a_knob=1)

    def test_engines_share_one_config(self, fleet_setup):
        fleet, data, _ = fleet_setup
        cfg = api.ServingConfig(batch_size=4, k=K, variant="adaptive",
                                routing="signature")
        fe = FleetEngine(fleet, config=cfg)
        assert fe.config is cfg and fe.batch_size == 4
        assert fe.routing == "signature"

    def test_kwargs_fold_into_config(self, fleet_setup):
        fleet, _, _ = fleet_setup
        fe = FleetEngine(fleet, batch_size=2, maintenance_every=3)
        assert isinstance(fe.config, api.ServingConfig)
        assert fe.config.batch_size == 2
        assert fe.config.maintenance_every == 3

    def test_tenant_load(self, fleet_setup):
        fleet, _, queries = fleet_setup
        engine = FleetEngine(fleet, batch_size=4, k=K)
        fleet.reset_metrics()
        assert engine.tenant_load("tenant0") == 0.0     # unqueried
        fleet.query(queries, K, routing="exhaustive")
        load = engine.tenant_load("tenant0")
        assert 0.0 < load <= 1.0
        assert engine.tenant_load("no-such-tenant") == 0.0

    def test_legacy_submit_warns_once(self, fleet_setup, monkeypatch):
        from repro.serve import QueryRequest as LegacyRequest
        fleet, _, queries = fleet_setup
        engine = FleetEngine(fleet, batch_size=2, k=K)
        monkeypatch.setattr(knn_engine_mod, "_LEGACY_SUBMIT_WARNED", False)
        with pytest.warns(DeprecationWarning):
            engine.submit(LegacyRequest(rid=0, series=queries[0], k=K))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            engine.submit(LegacyRequest(rid=1, series=queries[1], k=K))
        assert not [w for w in caught
                    if issubclass(w.category, DeprecationWarning)]
        engine.step()
        assert not engine.queue


# -- live server ------------------------------------------------------------

@pytest.fixture(scope="module")
def net_setup(fleet_setup):
    fleet, data, queries = fleet_setup
    engine = FleetEngine(fleet, config=api.ServingConfig(
        batch_size=4, k=K, variant="adaptive", routing="signature"))
    server, stop = serve_in_thread(engine)
    yield fleet, engine, server, queries
    stop()


class TestServer:
    def test_handshake_card(self, net_setup):
        fleet, engine, server, queries = net_setup
        with ClimberClient("127.0.0.1", server.port) as c:
            assert c.info.series_len == 64
            assert c.info.k_max == K
            assert c.info.engine == "fleet"
            assert c.info.shards == len(fleet.shards)
            assert c.info.wire_version == api.WIRE_VERSION

    def test_bit_identity_routed(self, net_setup):
        """Acceptance: the socket adds zero numeric difference."""
        fleet, engine, server, queries = net_setup
        with ClimberClient("127.0.0.1", server.port) as c:
            got = c.query_batch(list(queries), k=K)
        dist, gid, _ = fleet.query(queries, K, routing="signature",
                                   variant="adaptive")
        # a served tick and a direct call may batch differently: gids
        # exact, distances to the float32 rounding of the expansion
        assert_same_answers(np.stack([r.dist for r in got]),
                            np.stack([r.gid for r in got]),
                            dist.astype(np.float32), gid, scale=SCALE)

    def test_bit_identity_exhaustive(self, fleet_setup):
        fleet, data, queries = fleet_setup
        engine = FleetEngine(fleet, config=api.ServingConfig(
            batch_size=4, k=K, variant="exhaustive", routing="exhaustive"))
        server, stop = serve_in_thread(engine)
        try:
            with ClimberClient("127.0.0.1", server.port) as c:
                got = c.query_batch(list(queries), k=K)
        finally:
            stop()
        dist, gid, _ = fleet.query(queries, K, routing="exhaustive",
                                   variant="exhaustive")
        assert_same_answers(np.stack([r.dist for r in got]),
                            np.stack([r.gid for r in got]),
                            dist.astype(np.float32), gid, scale=SCALE)

    def test_result_metrics_ride_along(self, net_setup):
        _, _, server, queries = net_setup
        with ClimberClient("127.0.0.1", server.port) as c:
            res = c.query(queries[0], k=K)
        assert res.latency_ms > 0.0
        assert res.candidates_scanned > 0
        assert 0.0 < res.batch_fill <= 1.0

    def test_bad_request_is_typed(self, net_setup):
        _, _, server, queries = net_setup
        with ClimberClient("127.0.0.1", server.port) as c:
            with pytest.raises(ServerError) as ei:
                c.query(np.zeros(13, np.float32))       # wrong series_len
            assert ei.value.code == "BAD_REQUEST"
            with pytest.raises(ServerError) as ei:
                c.query(queries[0], k=K + 1)            # k > k_max
            assert ei.value.code == "BAD_REQUEST"
            # the connection survives typed rejections
            res = c.query(queries[0], k=K)
            assert res.gid.shape == (K,)

    def test_wire_version_mismatch_over_socket(self, net_setup):
        _, _, server, _ = net_setup
        sock = socket.create_connection(("127.0.0.1", server.port),
                                        timeout=10)
        try:
            hello = codec.encode_frame(
                int(schema.MsgType.HELLO),
                codec.encode_payload({"wire_version": np.asarray(99)}),
                version=api.WIRE_VERSION + 1)
            sock.sendall(hello)
            msg_type, payload = codec.read_frame_sync(sock)
            mtype, reply = schema.decode_message(msg_type, payload)
            assert mtype == schema.MsgType.ERROR
            assert reply.code == "VERSION_MISMATCH"
        finally:
            sock.close()

    def test_corrupt_frame_gets_typed_reply(self, net_setup):
        _, _, server, queries = net_setup
        sock = socket.create_connection(("127.0.0.1", server.port),
                                        timeout=10)
        try:
            sock.sendall(schema.encode_message(schema.MsgType.HELLO,
                                               {"client": "t"}))
            codec.read_frame_sync(sock)                  # SERVER_INFO
            frame = bytearray(schema.encode_message(
                schema.MsgType.QUERY,
                api.QueryRequest(series=queries[0])))
            frame[-1] ^= 0x01                            # flip payload bit
            sock.sendall(bytes(frame))
            msg_type, payload = codec.read_frame_sync(sock)
            mtype, reply = schema.decode_message(msg_type, payload)
            assert mtype == schema.MsgType.ERROR
            assert reply.code == "BAD_FRAME"
        finally:
            sock.close()

    def test_net_metrics_exported(self, net_setup):
        _, _, server, queries = net_setup
        with ClimberClient("127.0.0.1", server.port) as c:
            c.query(queries[0], k=K)
        page = to_prometheus(REGISTRY)
        assert "repro_net_rtt_ms" in page
        assert "repro_net_connections" in page
        assert "repro_net_frames_in" in page
        assert "repro_net_queries" in page


def _slowed(engine, seconds):
    """Wrap engine._execute so every tick holds the device plane."""
    orig = engine._execute

    def slow(qbatch, nlive):
        time.sleep(seconds)
        return orig(qbatch, nlive)

    engine._execute = slow
    return engine


class TestAdmission:
    def test_double_buffer_overlap(self, fleet_setup):
        """Acceptance: batch N+1 is admitted while tick N executes.

        Three concurrent clients each stream 4 queries (retrying typed
        backpressure), so sends keep landing while 50ms ticks run — the
        admissions the double buffer takes during a tick are counted in
        ``server.overlap_admissions``.  Load on the host only makes
        ticks longer and overlap likelier, so the assert is stable under
        a full parallel test run.
        """
        fleet, _, queries = fleet_setup
        engine = _slowed(FleetEngine(fleet, config=api.ServingConfig(
            batch_size=2, k=K, admission_depth=2)), 0.05)
        server, stop = serve_in_thread(engine)
        results = []

        def worker(widx):
            with ClimberClient("127.0.0.1", server.port) as c:
                for i in range(4):
                    while True:
                        try:
                            results.append(
                                c.query(queries[(widx + i) % len(queries)],
                                        k=K))
                            break
                        except RetryLater as exc:
                            time.sleep(max(exc.retry_after_ms, 1.0) / 1e3)

        try:
            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert len(results) == 12
            assert all(isinstance(r, api.QueryResult) for r in results)
            assert server.overlap_admissions > 0
        finally:
            stop()

    def test_backpressure_retry_later(self, fleet_setup):
        fleet, _, queries = fleet_setup
        engine = _slowed(FleetEngine(fleet, config=api.ServingConfig(
            batch_size=2, k=K, admission_depth=1, max_pending=2)), 0.25)
        server, stop = serve_in_thread(engine)
        try:
            series = [queries[i % len(queries)] for i in range(6)]
            with ClimberClient("127.0.0.1", server.port) as c:
                with pytest.raises(RetryLater) as ei:
                    c.query_batch(series, k=K)
            assert ei.value.code == "RETRY_LATER"
            assert ei.value.retry_after_ms >= 1.0
        finally:
            stop()

    def test_tenant_quota(self, fleet_setup):
        fleet, _, queries = fleet_setup
        engine = _slowed(FleetEngine(fleet, config=api.ServingConfig(
            batch_size=4, k=K, tenant_quota=1)), 0.25)
        server, stop = serve_in_thread(engine)
        try:
            with ClimberClient("127.0.0.1", server.port,
                               tenant="hog") as c:
                with pytest.raises(RetryLater) as ei:
                    c.query_batch([queries[0], queries[1], queries[2]], k=K)
            assert ei.value.code == "QUOTA_EXCEEDED"
        finally:
            stop()
        assert engine.tenant_inflight("hog") == 0        # quota released

    def test_hot_tenant_share_halves_quota(self, fleet_setup):
        fleet, _, _ = fleet_setup
        engine = FleetEngine(fleet, config=api.ServingConfig(
            batch_size=2, k=K, tenant_quota=4, hot_tenant_share=0.5))
        server = ClimberServer(engine)
        engine.tenant_load = lambda tenant: 0.9          # hog the fleet
        assert server._effective_quota("hog") == 2
        engine.tenant_load = lambda tenant: 0.1
        assert server._effective_quota("cold") == 4

    def test_graceful_shutdown_drains_in_flight(self, fleet_setup):
        """stop() answers every admitted request before closing."""
        fleet, _, queries = fleet_setup
        engine = _slowed(FleetEngine(fleet, config=api.ServingConfig(
            batch_size=2, k=K, admission_depth=2)), 0.05)
        server, stop = serve_in_thread(engine)
        series = [queries[i % len(queries)] for i in range(6)]
        box = {}

        def client_run():
            with ClimberClient("127.0.0.1", server.port) as c:
                box["results"] = c.query_batch(series, k=K)

        t = threading.Thread(target=client_run)
        t.start()
        time.sleep(0.08)           # let requests admit; ticks in flight
        stop()                     # drain while executing
        t.join(timeout=30)
        assert not t.is_alive()
        assert len(box["results"]) == 6
        assert all(isinstance(r, api.QueryResult) for r in box["results"])

    def test_rejects_after_shutdown(self, fleet_setup):
        fleet, _, queries = fleet_setup
        engine = FleetEngine(fleet, config=api.ServingConfig(
            batch_size=2, k=K))
        server = ClimberServer(engine)
        server._draining = True

        class FakeConn:
            posted = []
            alive = True

            def post(self, mtype, msg):
                FakeConn.posted.append((mtype, msg))

        server._admit(api.QueryRequest(series=queries[0], k=K), FakeConn())
        (mtype, reply), = FakeConn.posted
        assert mtype == schema.MsgType.ERROR
        assert reply.code == "SHUTTING_DOWN"


class TestClimberEngineConfig:
    def test_single_index_engine_takes_config(self):
        from repro.core import build_index
        cfg = small_cfg()
        data = make_dataset("randomwalk", jax.random.PRNGKey(5), 400, 64)
        index = build_index(jax.random.PRNGKey(6), jnp.asarray(data), cfg)
        engine = ClimberEngine(index, config=api.ServingConfig(
            batch_size=2, k=K, variant="adaptive"))
        assert engine.batch_size == 2
        with pytest.raises(TypeError):
            ClimberEngine(index, config=api.ServingConfig(), batch_size=2)
