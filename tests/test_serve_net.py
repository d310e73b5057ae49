"""heat_tpu.serve.net — HTTP transport, replica pool, least-loaded router
(ISSUE 12).

Covers: the wire schema's bitwise round-trip contract (exact-mode answers
survive the network hop), the HTTP front's status mapping (admission
sheds → 503 + machine reason, the router's retry key), Server.drain
graceful-shutdown semantics (new submits shed ``draining``, backlog
completes), router policy against scripted fake replicas (sticky
degradation across siblings, connect-refused eviction + health re-add,
in-flight-drop failure semantics), the live==offline ``serving_net``
telemetry reconciliation, and — subprocess-verified, slow-marked — the
cross-process warm start: a restored-from-checkpoint replica serves
bit-identical answers with zero steady-state backend compiles and zero
autotune trials (the PR 11 replay oracle extended to the serving tier),
plus kill/recovery and drain-then-exit-0.
"""

import http.client
import json
import os
import threading
import time
from concurrent.futures import Future
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.serve import (
    ServeError,
    Server,
    ServerClosedError,
    ServerOverloadedError,
)
from heat_tpu.serve.net import (
    HttpFront,
    ReplicaDownError,
    Router,
    WireError,
    wire,
)


@pytest.fixture()
def rng():
    return np.random.default_rng(3)


def _cdist_server(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 1.0)
    srv = Server(**kw)
    y = np.random.default_rng(7).standard_normal((32, 8)).astype(np.float32)
    srv.register("cdist", ht.serve.cdist_query(y))
    return srv


def _wait_until(fn, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _http(host, port, method, path, body=None, timeout=10.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


# -- wire schema --------------------------------------------------------------


class TestWire:
    @pytest.mark.parametrize("dtype", ["float32", "float64", "int64", "bool"])
    def test_array_round_trip_bitwise(self, rng, dtype):
        arr = (rng.standard_normal((3, 5)) * 4).astype(dtype)
        back = wire.decode_array(wire.encode_array(arr))
        assert back.dtype == arr.dtype
        assert back.shape == arr.shape
        assert back.tobytes() == arr.tobytes()

    def test_scalar_and_one_dim_round_trip(self, rng):
        for arr in (np.float32(3.25), rng.standard_normal(7)):
            back = wire.decode_array(wire.encode_array(np.asarray(arr)))
            assert back.tobytes() == np.asarray(arr).tobytes()

    def test_object_dtype_refused(self):
        with pytest.raises(WireError):
            wire.encode_array(np.array([object()], dtype=object))

    def test_garbage_payloads_raise_wire_error(self):
        with pytest.raises(WireError):
            wire.decode_array("not base64!!")
        with pytest.raises(WireError):
            import base64

            wire.decode_array(
                base64.b64encode(b"not an npy blob").decode()
            )
        with pytest.raises(WireError):
            wire.decode_array(12345)
        with pytest.raises(WireError):
            wire.decode_request(b"not json")
        with pytest.raises(WireError):
            wire.decode_request(b'{"nope": 1}')
        with pytest.raises(WireError):
            wire.decode_response(b'{"no_ok_field": 1}')

    def test_request_response_round_trip(self, rng):
        payload = rng.standard_normal((2, 8)).astype(np.float32)
        assert wire.decode_request(
            wire.encode_request(payload)
        ).tobytes() == payload.tobytes()
        ok, result, reason = wire.decode_response(
            wire.encode_response(payload)
        )
        assert ok and reason == ""
        assert result.tobytes() == payload.tobytes()

    def test_error_envelope_carries_reason(self):
        ok, message, reason = wire.decode_response(
            wire.encode_error("queue is full", "queue_full")
        )
        assert not ok
        assert message == "queue is full"
        assert reason == "queue_full"


# -- HTTP front over a live server -------------------------------------------


class TestHttpFront:
    def test_routes_and_bit_identity(self, rng):
        q = rng.standard_normal((3, 8)).astype(np.float32)
        with _cdist_server() as srv:
            srv.warmup()
            want = np.asarray(srv.predict("cdist", q))
            with HttpFront(srv, port=0) as front:
                # healthz
                status, body = _http(front.host, front.port, "GET", "/healthz")
                assert status == 200 and json.loads(body)["ok"]
                # predict over the wire == in-process, bitwise
                status, body = _http(
                    front.host, front.port, "POST", "/v1/cdist",
                    wire.encode_request(q),
                )
                assert status == 200
                ok, got, _ = wire.decode_response(body)
                assert ok and got.tobytes() == want.tobytes()
                # stats carries the net block + server stats
                status, body = _http(front.host, front.port, "GET", "/stats")
                st = json.loads(body)
                assert status == 200
                assert st["net"]["port"] == front.port
                assert st["net"]["steady_backend_compiles"] == 0
                assert st["net"]["http_requests"] >= 1
                assert "cdist" in st["endpoints"]
                # unknown path / endpoint / malformed body
                status, body = _http(front.host, front.port, "GET", "/nope")
                assert status == 404
                status, body = _http(
                    front.host, front.port, "POST", "/v1/missing",
                    wire.encode_request(q),
                )
                assert status == 404
                assert json.loads(body)["reason"] == "not_found"
                status, body = _http(
                    front.host, front.port, "POST", "/v1/cdist", b"not json"
                )
                assert status == 400
                assert json.loads(body)["reason"] == "bad_request"

    def test_status_mapping_from_submit_errors(self):
        class _Stub:
            """Server stand-in scripted per test: the front only needs
            submit/stats/draining/_closed."""

            draining = False
            _closed = False
            behavior = "ok"

            def submit(self, name, payload, trace=None):
                if self.behavior == "queue_full":
                    raise ServerOverloadedError(
                        "full", reason="queue_full", endpoint=name
                    )
                if self.behavior == "closed":
                    raise ServerClosedError("closed")
                if self.behavior == "value":
                    raise ValueError("unknown endpoint")
                if self.behavior == "boom":
                    raise RuntimeError("kaboom")
                return Future()  # never resolves -> 504

            def stats(self):
                return {"pending": 0}

        stub = _Stub()
        front = HttpFront(stub, port=0, request_timeout=0.05)
        front.start()
        try:
            body = wire.encode_request(np.zeros((1, 2), np.float32))
            for behavior, status, reason in (
                ("queue_full", 503, "queue_full"),
                ("closed", 503, "closed"),
                ("value", 400, "bad_request"),
                ("boom", 500, "internal"),
                ("ok", 504, "timeout"),
            ):
                stub.behavior = behavior
                got, data = _http(
                    front.host, front.port, "POST", "/v1/e", body
                )
                assert got == status, (behavior, got)
                assert json.loads(data)["reason"] == reason
        finally:
            front.stop()

    def test_drain_stops_listener(self):
        with _cdist_server() as srv:
            front = HttpFront(srv, port=0)
            front.start()
            port = front.port
            assert front.drain(5.0) is True
            with pytest.raises(OSError):
                _http(front.host, port, "GET", "/healthz", timeout=0.5)


# -- Server.drain (graceful shutdown, ISSUE 12 satellite) ---------------------


class TestServerDrain:
    def test_drain_completes_backlog_then_closes(self, rng):
        srv = _cdist_server(max_wait_ms=5.0)
        srv.warmup()
        futs = [
            srv.submit(
                "cdist", rng.standard_normal((1, 8)).astype(np.float32)
            )
            for _ in range(6)
        ]
        assert srv.drain(30.0) is True
        for f in futs:
            assert np.asarray(f.result(0)).shape == (1, 32)
        assert srv.draining
        assert srv.stats()["closed"]
        assert srv.stats()["pending"] == 0
        # idempotent on a closed server
        assert srv.drain(1.0) is True

    def test_draining_sheds_new_submits_503(self, rng):
        srv = _cdist_server()
        try:
            srv.warmup()
            srv._draining = True  # freeze phase one without the close race
            with pytest.raises(ServerOverloadedError) as ei:
                srv.submit(
                    "cdist", rng.standard_normal((1, 8)).astype(np.float32)
                )
            assert ei.value.reason == "draining"
            assert ei.value.status == 503
            assert srv.stats()["shed"] == 1
        finally:
            srv._draining = False
            srv.close()


# -- router vs scripted fake replicas ----------------------------------------


class _FakeReplica:
    """Scripted replica front: /healthz + /stats always answer; POST
    behavior is a callable returning ``(status, body_bytes)`` or
    ``"drop"`` (close the socket after reading the request — the
    in-flight ambiguity case)."""

    def __init__(self, behavior, port=0):
        fake = self

        class _H(BaseHTTPRequestHandler):
            # HTTP/1.0: one request per connection. A keep-alive fake
            # would outlive stop() through its persistent handler
            # threads (only the LISTENER dies), unlike a killed replica
            # process, which closes every socket.
            protocol_version = "HTTP/1.0"

            def log_message(self, fmt, *args):
                pass

            def _reply(self, status, body):
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    self._reply(200, b'{"ok": true}')
                else:
                    self._reply(200, b'{"pending": 0}')

            def do_POST(self):
                fake.posts += 1
                self.rfile.read(
                    int(self.headers.get("Content-Length", 0))
                )
                out = fake.behavior()
                if out == "drop":
                    import socket

                    # shutdown, not just close: rfile/wfile still hold
                    # the fd, so close() alone would never send the FIN
                    # the client is waiting on
                    self.connection.shutdown(socket.SHUT_RDWR)
                    self.connection.close()
                    return
                self._reply(*out)

        self.behavior = behavior
        self.posts = 0
        self._cls = _H
        self.httpd = ThreadingHTTPServer(("127.0.0.1", port), _H)
        self.httpd.daemon_threads = True
        self.port = self.httpd.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join(5.0)

    def restart(self):
        """New listener on the SAME port (the recovered-replica case)."""
        self.httpd = ThreadingHTTPServer(("127.0.0.1", self.port), self._cls)
        self.httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()


def _ok_body(rng=None):
    arr = np.arange(6, dtype=np.float32).reshape(2, 3)
    return 200, wire.encode_response(arr)


def _shed_body():
    return 503, wire.encode_error("full", "queue_full")


class TestRouterPolicy:
    def test_sticky_degradation_retries_siblings(self):
        """First-in-rotation replica sheds 503 -> the request lands on
        the sibling, the client never sees the shed (score tie keeps
        list order, so the shedding replica IS tried first)."""
        shed = _FakeReplica(_shed_body)
        good = _FakeReplica(_ok_body)
        router = Router([shed.url, good.url], retries=2, poll_ms=1000.0,
                        workers=1)
        try:
            got = router.predict("e", np.zeros((1, 2), np.float32))
            assert np.asarray(got).tobytes() == \
                np.arange(6, dtype=np.float32).tobytes()
            assert shed.posts == 1 and good.posts == 1
            counts = router.stats()["router"]
            assert counts["retries"] == 1
            assert counts["requests"] == 1
            assert counts["shed"] == 0
            # the shedding replica stays in rotation (alive + talking)
            assert router.stats()["replicas"][shed.url]["up"]
        finally:
            router.close()
            shed.stop()
            good.stop()

    def test_every_replica_shedding_surfaces_503(self):
        shed = _FakeReplica(_shed_body)
        router = Router([shed.url], retries=3, poll_ms=1000.0, workers=1)
        try:
            with pytest.raises(ServerOverloadedError) as ei:
                router.predict("e", np.zeros((1, 2), np.float32))
            assert ei.value.reason == "queue_full"
            assert router.stats()["router"]["shed"] == 1
        finally:
            router.close()
            shed.stop()

    def test_connect_refused_evicts_and_sibling_serves(self):
        good = _FakeReplica(_ok_body)
        dead = _FakeReplica(_ok_body)
        dead_url = dead.url
        dead.stop()  # port is now refusing connections
        router = Router([dead_url, good.url], retries=2, poll_ms=1000.0,
                        workers=1)
        try:
            got = router.predict("e", np.zeros((1, 2), np.float32))
            assert np.asarray(got).shape == (2, 3)
            counts = router.stats()["router"]
            assert counts["evictions"] == 1
            assert not router.stats()["replicas"][dead_url]["up"]
        finally:
            router.close()
            good.stop()

    def test_in_flight_drop_fails_not_retried_by_default(self):
        dropper = _FakeReplica(lambda: "drop")
        sibling = _FakeReplica(_ok_body)
        router = Router([dropper.url, sibling.url], retries=2,
                        poll_ms=1000.0, workers=1)
        try:
            with pytest.raises(ReplicaDownError):
                router.predict("e", np.zeros((1, 2), np.float32))
            assert sibling.posts == 0  # ambiguous: never re-dispatched
            assert router.stats()["router"]["failed"] == 1
        finally:
            router.close()
            dropper.stop()
            sibling.stop()

    def test_in_flight_drop_retries_when_opted_in(self):
        dropper = _FakeReplica(lambda: "drop")
        sibling = _FakeReplica(_ok_body)
        router = Router([dropper.url, sibling.url], retries=2,
                        poll_ms=1000.0, workers=1, retry_in_flight=True)
        try:
            got = router.predict("e", np.zeros((1, 2), np.float32))
            assert np.asarray(got).shape == (2, 3)
            assert sibling.posts == 1
        finally:
            router.close()
            dropper.stop()
            sibling.stop()

    def test_slow_response_times_out_without_eviction(self):
        """A replica that is merely slow (response-read timeout) must
        NOT be evicted from rotation, and the ambiguous request is
        neither retried nor reported as a replica outage."""
        slow = _FakeReplica(lambda: (time.sleep(1.0), _ok_body())[1])
        router = Router([slow.url], retries=2, poll_ms=1000.0, workers=1,
                        request_timeout=0.3)
        try:
            with pytest.raises(ServeError) as ei:
                router.predict("e", np.zeros((1, 2), np.float32),
                               timeout=10)
            assert not isinstance(ei.value, ReplicaDownError)
            st = router.stats()
            assert st["replicas"][slow.url]["up"]
            assert st["router"]["evictions"] == 0
            assert st["router"]["failed"] == 1
        finally:
            router.close()
            slow.stop()

    def test_health_poll_evicts_then_readds(self):
        fake = _FakeReplica(_ok_body)
        router = Router([fake.url], retries=0, poll_ms=20.0, workers=1)
        try:
            router.predict("e", np.zeros((1, 2), np.float32))
            fake.stop()
            _wait_until(
                lambda: not router.stats()["replicas"][fake.url]["up"],
                what="health-poll eviction",
            )
            fake.restart()
            _wait_until(
                lambda: router.stats()["replicas"][fake.url]["up"],
                what="health-probe re-add",
            )
            assert router.stats()["router"]["readds"] == 1
            got = router.predict("e", np.zeros((1, 2), np.float32))
            assert np.asarray(got).shape == (2, 3)
        finally:
            router.close()
            fake.stop()

    def test_deterministic_upstream_error_not_retried(self):
        bad = _FakeReplica(
            lambda: (400, wire.encode_error("no such endpoint",
                                            "bad_request"))
        )
        sibling = _FakeReplica(_ok_body)
        router = Router([bad.url, sibling.url], retries=2, poll_ms=1000.0,
                        workers=1)
        try:
            with pytest.raises(ValueError):
                router.predict("missing", np.zeros((1, 2), np.float32))
            assert sibling.posts == 0
            counts = router.stats()["router"]
            assert counts["failed"] == 1 and counts["retries"] == 0
        finally:
            router.close()
            bad.stop()
            sibling.stop()

    def test_closed_router_rejects_and_add_target_dedupes(self):
        fake = _FakeReplica(_ok_body)
        router = Router([fake.url], poll_ms=1000.0, workers=1)
        try:
            router.add_target(fake.url)  # duplicate: no-op
            assert len(router.stats()["replicas"]) == 1
        finally:
            router.close()
        with pytest.raises(ServerClosedError):
            router.submit("e", np.zeros((1, 2), np.float32))
        fake.stop()


class TestRouterOverLiveServers:
    def test_bit_identity_and_both_replicas_used(self, rng):
        """Routed answers == in-process answers bitwise, and with the
        per-replica in-flight budget at 1 a concurrent burst must spill
        onto the second replica (least-loaded dispatch)."""
        q = rng.standard_normal((2, 8)).astype(np.float32)
        with _cdist_server() as direct:
            direct.warmup()
            want = np.asarray(direct.predict("cdist", q))
        servers = [_cdist_server(), _cdist_server()]
        fronts = [HttpFront(s, port=0) for s in servers]
        for s, f in zip(servers, fronts):
            s.warmup()
            f.start()
        router = Router([f.url for f in fronts], poll_ms=50.0, workers=4,
                        max_inflight=1)
        try:
            futs = [router.submit("cdist", q) for _ in range(16)]
            for fut in futs:
                got = np.asarray(fut.result(30))
                assert got.tobytes() == want.tobytes()
            per_front = [f.stats_payload()["net"]["http_requests"]
                         for f in fronts]
            assert all(n > 0 for n in per_front), per_front
            st = router.stats()
            assert st["router"]["requests"] == 16
            assert st["endpoints"]["cdist"]["requests"] == 16
        finally:
            router.close()
            for f in fronts:
                f.stop()
            for s in servers:
                s.close()


# -- telemetry: serving_net live == offline reconciliation --------------------


class TestServingNetTelemetry:
    def test_summarize_serving_net_block_live_equals_offline(self, rng):
        was_enabled = telemetry.enabled()
        reg = telemetry.get_registry()
        saved_counters = dict(reg.counters)
        saved_events = list(reg.events)
        saved_marks = dict(reg.watermarks)
        reg.clear()
        telemetry.enable()
        try:
            shed = _FakeReplica(_shed_body)
            good = _FakeReplica(_ok_body)
            router = Router([shed.url, good.url], retries=2,
                            poll_ms=1000.0, workers=1)
            try:
                for _ in range(3):
                    router.predict("e", np.zeros((1, 2), np.float32))
            finally:
                router.close()
                shed.stop()
                good.stop()
            live = telemetry.report.summarize()
            assert live["serving_net"]["requests"] == 3
            assert live["serving_net"]["retries"] == 3
            offline = telemetry.report.summarize(
                list(reg.events), dict(reg.watermarks)
            )
            assert offline["serving_net"] == live["serving_net"]
            # every serve_net event moved exactly one paired counter
            assert reg.counters["serve_net.requests"] == 3
            assert reg.counters["serve_net.retries"] == 3
        finally:
            if not was_enabled:
                telemetry.disable()
            reg.clear()
            reg.counters.update(saved_counters)
            reg.events.extend(saved_events)
            reg.watermarks.update(saved_marks)

    def test_no_serving_net_block_without_traffic(self):
        assert "serving_net" not in telemetry.report.summarize(events=[])


# -- cross-process warm start (subprocess-verified acceptance path) -----------


@pytest.mark.slow
class TestReplicaPoolSubprocess:
    def test_warm_start_bit_identity_chaos_and_graceful_drain(
        self, rng, tmp_path
    ):
        """One pool, full lifecycle: replica 0 populates the shared
        compile cache; replica 1 (spawned after) restores the SAME
        checkpoint, warm-starts from the shared cache + tuning DB, and
        must serve bit-identical answers with zero steady-state backend
        compiles and zero measured autotune trials. Then SIGKILL replica
        0 (only its in-flight work may fail; the router evicts it and
        the sibling answers), spawn a replacement into the rotation
        (crash recovery = restore-into-fresh-replica, bit-identical),
        and finally drain-then-remove gracefully: exit 0 + the drained
        exit record."""
        from heat_tpu.serve.net import ReplicaPool

        ckpt = str(tmp_path / "endpoints.ckpt")
        cache = str(tmp_path / "xla_cache")
        tune_db = str(tmp_path / "tune_db")
        srv = _cdist_server()
        srv.save(ckpt)
        srv.close()

        # direct in-process reference (restored exactly like a replica)
        q = rng.standard_normal((2, 8)).astype(np.float32)
        direct = Server.restore(ckpt)
        direct.warmup()
        want = np.asarray(direct.predict("cdist", q))
        direct.close()

        env = {
            "JAX_COMPILATION_CACHE_DIR": cache,
            "HEAT_TPU_TUNE_DB": tune_db,
            "HEAT_TPU_AUTOTUNE": "1",
            "HEAT_TPU_TELEMETRY": "1",
            "HEAT_TPU_SERVE_MAX_BATCH": "4",
        }
        pool = ReplicaPool(ckpt, 1, mesh=4, env=env,
                           log_dir=str(tmp_path / "logs"))
        try:
            pool.start()
            assert os.listdir(cache), "replica 0 populated no shared cache"
            h1 = pool.spawn()  # the warm-started second replica
            router = Router(pool, retries=2, poll_ms=50.0, workers=2)
            try:
                got = np.asarray(router.predict("cdist", q, timeout=60))
                assert got.tobytes() == want.tobytes()

                st1 = pool.stats(h1.index)["net"]
                assert st1["steady_backend_compiles"] == 0, st1
                assert st1["autotune_trials"] == 0, st1
                assert st1["warmup"]["endpoints"] == 1

                # chaos: SIGKILL replica 0; the sibling absorbs traffic
                pool.kill(0)
                for _ in range(3):
                    got = np.asarray(router.predict("cdist", q, timeout=60))
                    assert got.tobytes() == want.tobytes()

                # crash recovery: a fresh replica restored from the
                # checkpoint joins the rotation and answers bit-identically
                repl = pool.spawn()
                router.add_target(repl.url)
                _wait_until(
                    lambda: router.stats()["replicas"]
                    .get(repl.url, {}).get("up"),
                    what="replacement replica joining rotation",
                )
                got = np.asarray(router.predict("cdist", q, timeout=60))
                assert got.tobytes() == want.tobytes()
            finally:
                router.close()

            # graceful drain-then-remove: SIGTERM -> backlog drains,
            # telemetry flushes, exit 0, drained exit record on stdout
            rc = pool.remove(h1.index)
            assert rc == 0, pool.handle(h1.index).log_tail()
            _wait_until(
                lambda: any(
                    o.get("exit") for o in pool.handle(h1.index).exit_lines()
                ),
                what="graceful exit record",
            )
            exits = [o for o in pool.handle(h1.index).exit_lines()
                     if o.get("exit")]
            assert exits[0]["drained"] is True
        finally:
            pool.close()
