"""Gateway data-plane fast path (ISSUE 14): upstream connection pooling,
real HTTP/1.1 keep-alive, drain-vs-parked-socket semantics, and the
perf_compare-gated overhead microbench.

Reuse and failure semantics, pinned:

- N relays through the gateway accept <= pool-size upstream TCP
  connections (vs ~N before the pool);
- killing a replica that holds pooled sockets completes the herd with
  ZERO client-visible failures and counted discards;
- drain() closes idle pooled connections (a draining replica must not
  wedge on parked sockets);
- the pooled-vs-fresh A/B on the same stub fleet is strictly better
  pooled, and perf_compare gates it (0 on the pair, 1 on a degraded
  copy).

Stubs ride DrainableHTTPServer + KeepAliveHandlerMixin so kill()/drain()
have real sever semantics and responses are honest HTTP/1.1.
"""

from __future__ import annotations

import copy
import http.client
import json
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler

import pytest

from ditl_tpu.config import GatewayConfig
from ditl_tpu.gateway import (
    ConnectionPool,
    Fleet,
    FleetSupervisor,
    GatewayMetrics,
    InProcessReplica,
    make_gateway,
)
from ditl_tpu.infer.server import DrainableHTTPServer
from ditl_tpu.utils.http11 import KeepAliveHandlerMixin

pytestmark = pytest.mark.gateway


# ---------------------------------------------------------------------------
# Keep-alive stub replicas (DrainableHTTPServer lifecycle, HTTP/1.1 wire)
# ---------------------------------------------------------------------------


class _KAStubServer(DrainableHTTPServer):
    """Keep-alive stub replica: DrainableHTTPServer's conn/parked tracking
    (so kill() severs and drain() severs parked) plus an accepted-TCP-
    connection counter — the number the pooled-vs-fresh pin reads."""

    label = "stub"
    delay_s = 0.0

    def __init__(self, *args, **kw):
        self.connections = 0
        super().__init__(*args, **kw)

    def process_request(self, request, client_address):
        self.connections += 1
        super().process_request(request, client_address)


class _KAStubHandler(KeepAliveHandlerMixin, BaseHTTPRequestHandler):
    def log_message(self, *args):
        pass

    def _json(self, status, payload):
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        draining = bool(self.server.draining)
        self._json(200, {
            "status": "draining" if draining else "ok", "model": "stub",
            "draining": draining, "queue_depth": 0, "active_slots": 0,
            "n_slots": 4,
        })

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        if self.server.delay_s:
            time.sleep(self.server.delay_s)
        self._json(200, {
            "object": "text_completion",
            "choices": [{"index": 0, "text": self.server.label,
                         "finish_reason": "stop"}],
            "usage": {"prompt_tokens": 1, "completion_tokens": 1,
                      "total_tokens": 2},
        })


def _stub_replica(rid, servers: list, delay_s: float = 0.0):
    def factory():
        server = _KAStubServer(("127.0.0.1", 0), _KAStubHandler)
        server.label = rid
        server.delay_s = delay_s
        servers.append(server)
        return server

    return InProcessReplica(rid, factory)


def _fleet(*handles) -> Fleet:
    fleet = Fleet(list(handles))
    fleet.start_all()
    for rid in fleet.ids:
        assert fleet.probe(rid, timeout=5.0)
    return fleet


def _start_gateway(fleet, config=None, **kw):
    server = make_gateway(fleet, config=config or GatewayConfig(), port=0,
                          **kw)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, server.server_address[1]


def _post(port, body, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


# ---------------------------------------------------------------------------
# Pool units
# ---------------------------------------------------------------------------


def test_pool_checkout_hit_miss_age_address_and_cap():
    servers: list = []
    fleet = _fleet(_stub_replica("r0", servers))
    try:
        addr = fleet.views()[0].address
        pool = ConnectionPool(max_idle_per_replica=2, max_age_s=30.0)
        # Miss then hit: the first request connects fresh, the second
        # reuses the parked connection.
        assert pool.request("r0", addr, "GET", "/health")[0] == 200
        assert (pool.hits, pool.misses) == (0, 1)
        assert pool.idle_count() == 1
        assert pool.request("r0", addr, "GET", "/health")[0] == 200
        assert (pool.hits, pool.misses) == (1, 1)
        # Age cap: an over-age parked connection is discarded at checkout,
        # not reused.
        pool.max_age_s = 0.01
        time.sleep(0.05)
        assert pool.request("r0", addr, "GET", "/health")[0] == 200
        assert pool.misses == 2 and pool.discards == 1
        pool.max_age_s = 30.0
        # Address mismatch (a relaunched replica on a new port): parked
        # connection for the old address is discarded, never handed out.
        wrong = (addr[0], addr[1] + 1)
        conn = pool.checkout("r0", wrong, timeout=5.0)
        assert pool.discards == 2 and conn.port == wrong[1]
        conn.close()  # never connected; nothing pooled
        # Idle cap: three concurrently checked-out connections check back
        # in, the third over-cap one is closed-and-counted.
        conns = [pool.checkout("r0", addr, timeout=5.0) for _ in range(3)]
        assert pool.idle_count() == 0
        for c in conns:
            c.request("GET", "/health")
            resp = c.getresponse()
            resp.read()
            pool.checkin("r0", c, response=resp)
        assert pool.idle_count() == 2
        assert pool.discards == 3
        # Stub accepted exactly the distinct connects (no reuse
        # miscount): the fleet probe's own pooled conn + this pool's 2
        # sequential misses (incl. the age-out reconnect) + 3 concurrent.
        assert servers[0].connections == 1 + 2 + 3
        pool.close()
        assert pool.idle_count() == 0
    finally:
        fleet.stop_all(drain=False)


def test_pool_detects_stale_socket_from_dead_peer():
    servers: list = []
    fleet = _fleet(_stub_replica("r0", servers))
    addr = fleet.views()[0].address
    pool = ConnectionPool()
    assert pool.request("r0", addr, "GET", "/health")[0] == 200
    assert pool.idle_count() == 1
    # Sever every open connection (the in-process kill -9): the parked
    # socket reads EOF, so the next checkout discards it instead of
    # handing it out.
    servers[0].kill()
    time.sleep(0.05)
    discards0 = pool.discards
    conn = pool.checkout("r0", addr, timeout=5.0)
    assert pool.discards == discards0 + 1  # stale conn never handed out
    assert conn.sock is None  # fresh, lazily-connecting
    conn.close()
    fleet.stop_all(drain=False)


@pytest.mark.recovery
def test_adopted_replica_same_port_discards_pre_crash_sockets():
    """Crash-recovery aliasing pin (ISSUE 20): adoption and same-port
    relaunch keep the SAME (host, port), so the pool's address check
    alone can NOT invalidate sockets parked before a crash — only the
    checkout staleness probe stands between a pre-crash half-open socket
    and a cross-wired request. Kill the listener a parked socket points
    at, rebind the SAME port with a different incarnation: checkout must
    discard the stale socket (counted) and serve from the reborn
    listener, never write the request down the dead peer's socket."""
    old = _KAStubServer(("127.0.0.1", 0), _KAStubHandler)
    old.label = "old-incarnation"
    threading.Thread(target=old.serve_forever, daemon=True).start()
    addr = ("127.0.0.1", old.server_address[1])
    pool = ConnectionPool()
    body = json.dumps({"prompt": "x", "max_tokens": 1}).encode()
    hdrs = {"Content-Type": "application/json"}
    status, _, data = pool.request("r0", addr, "POST", "/v1/completions",
                                   body=body, headers=hdrs)
    assert status == 200
    assert json.loads(data)["choices"][0]["text"] == "old-incarnation"
    assert pool.idle_count() == 1  # parked socket to the doomed peer
    old.kill()
    old.shutdown()
    old.server_close()
    # Rebind the SAME port (SO_REUSEADDR — exactly what a recovery
    # relaunch or an adopted replica's address looks like to the pool).
    reborn = _KAStubServer(addr, _KAStubHandler)
    reborn.label = "reborn"
    threading.Thread(target=reborn.serve_forever, daemon=True).start()
    try:
        time.sleep(0.05)
        d0 = pool.discards
        status, _, data = pool.request("r0", addr, "POST",
                                       "/v1/completions", body=body,
                                       headers=hdrs)
        assert status == 200
        assert json.loads(data)["choices"][0]["text"] == "reborn"
        assert pool.discards == d0 + 1  # the pre-crash socket, discarded
    finally:
        reborn.kill()
        reborn.shutdown()
        reborn.server_close()
        pool.close()


def test_fleet_health_polls_reuse_pooled_connections():
    servers: list = []
    fleet = _fleet(_stub_replica("r0", servers))
    try:
        for _ in range(5):
            assert fleet.probe("r0", timeout=5.0)
        # 6 probes total (incl. _fleet's) over ONE upstream connection.
        assert servers[0].connections == 1
        assert fleet.pool.hits >= 5
    finally:
        fleet.stop_all(drain=False)


def test_park_quarantine_and_drain_stop_invalidate_pooled_sockets():
    servers: list = []
    fleet = _fleet(_stub_replica("r0", servers), _stub_replica("r1", servers))
    try:
        assert fleet.pool.idle_count() == 2  # one parked probe conn each
        d0 = fleet.pool.discards
        fleet.set_deactivated("r0", True)
        assert fleet.pool.discards == d0 + 1
        assert fleet.pool.idle_count() == 1
        fleet.set_deactivated("r0", False)
        # drain_stop_locked (rolling restarts + the actuator's scale-down/
        # drain paths) invalidates before stopping the replica.
        supervisor = FleetSupervisor(fleet)
        assert fleet.probe("r1", timeout=5.0)
        d1 = fleet.pool.discards
        with supervisor.fleet_lock:
            supervisor.drain_stop_locked("r1", fleet._state("r1"), 1.0)
        assert fleet.pool.discards > d1
        assert fleet.pool.idle_count() == 0
        fleet.set_quarantined("r1", True)  # idempotent on an empty pool
        assert fleet.pool.idle_count() == 0
    finally:
        fleet.stop_all(drain=False)


def test_pool_ages_out_the_unpopped_tail():
    """LIFO reuse only ever pops the newest entry, so the age cap must be
    enforced by an explicit old-end sweep at checkin/checkout — without it
    a burst's tail would sit parked past max_age_s forever, each entry
    pinning a handler thread at the replica (review-hardening pin)."""

    class _FakeSock:
        def settimeout(self, t):
            pass

    class _FakeConn:
        host, port = "127.0.0.1", 1234

        def __init__(self):
            self.sock = _FakeSock()
            self.closed = False

        def close(self):
            self.closed = True

    class _FakeResp:
        will_close = False

        @staticmethod
        def isclosed():
            return True

    pool = ConnectionPool(max_idle_per_replica=8, max_age_s=0.05)
    # checkin without a completed response must NOT park (unverified
    # protocol state — a response could still be in flight).
    unverified = _FakeConn()
    pool.checkin("r0", unverified)
    assert pool.idle_count() == 0 and unverified.closed
    burst = [_FakeConn() for _ in range(4)]
    for c in burst:
        pool.checkin("r0", c, response=_FakeResp())
    assert pool.idle_count() == 4
    time.sleep(0.1)
    fresh = _FakeConn()
    # The checkin sweep reaps the aged tail.
    pool.checkin("r0", fresh, response=_FakeResp())
    assert pool.idle_count() == 1
    assert pool.discards == 4 + 1  # aged burst + the unverified checkin
    assert all(c.closed for c in burst) and not fresh.closed


# ---------------------------------------------------------------------------
# Gateway end-to-end: reuse pin, kill drill, drain semantics
# ---------------------------------------------------------------------------


def test_gateway_relays_pin_upstream_connection_count():
    """THE reuse pin: N relays <= pool-size accepted TCP connections
    (vs ~N before the pool), and the client side keeps ONE connection to
    the gateway alive across all N (end-to-end HTTP/1.1)."""
    servers: list = []
    fleet = _fleet(_stub_replica("r0", servers))
    gw, port = _start_gateway(fleet, GatewayConfig(router="round_robin"))
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        for i in range(16):
            conn.request("POST", "/v1/completions",
                         body=json.dumps({"prompt": f"p{i}",
                                          "max_tokens": 1}).encode(),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            assert resp.status == 200
            assert out["choices"][0]["text"] == "r0"
        conn.close()  # 16 requests rode ONE client connection
        # Upstream: the probe + 16 relays share pooled connections — the
        # stub accepted far fewer TCP connections than requests (the
        # pre-pool behavior was one per relay).
        assert servers[0].connections <= 4
        assert fleet.pool.hits >= 14
    finally:
        gw.shutdown()
        gw.server_close()
        fleet.stop_all(drain=False)


def test_gateway_pool_disabled_connects_fresh_per_relay():
    """The A/B control: pool_max_idle_per_replica=0 restores the
    connect-per-hop behavior (every relay is a counted miss+discard)."""
    servers: list = []
    fleet = _fleet(_stub_replica("r0", servers))
    gw, port = _start_gateway(
        fleet,
        GatewayConfig(router="round_robin", pool_max_idle_per_replica=0),
    )
    try:
        base = servers[0].connections
        for i in range(8):
            status, _ = _post(port, {"prompt": f"p{i}", "max_tokens": 1})
            assert status == 200
        assert servers[0].connections - base >= 8
        assert fleet.pool.hits == 0
    finally:
        gw.shutdown()
        gw.server_close()
        fleet.stop_all(drain=False)


def test_kill_mid_pooled_relay_completes_herd_with_counted_discards():
    """SIGKILL a replica HOLDING pooled sockets (the handle still
    advertises it — the gateway has not noticed yet, exactly like a real
    kill -9): the herd completes with zero client-visible failures, the
    dead replica's pooled sockets are discarded-and-counted, and the
    survivor serves everything."""
    servers: list = []
    fleet = _fleet(_stub_replica("r0", servers), _stub_replica("r1", servers))
    gw, port = _start_gateway(fleet, GatewayConfig(router="round_robin"))
    try:
        # Warm pooled connections to BOTH replicas. checkin runs in the
        # handler's finally AFTER the response bytes are relayed, so poll
        # briefly instead of racing the handler thread.
        for i in range(6):
            status, _ = _post(port, {"prompt": f"warm{i}", "max_tokens": 1})
            assert status == 200
        deadline = time.monotonic() + 5
        while fleet.pool.idle_count() < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fleet.pool.idle_count() >= 2
        discards0 = fleet.pool.discards
        # Kill r0's server WITHOUT telling the handle (handle.kill() would
        # null the address and route around it instantly — a real SIGKILL
        # leaves a corpse the gateway discovers mid-relay).
        r0_server = next(s for s in servers if s.label == "r0")
        r0_server.kill()
        with ThreadPoolExecutor(max_workers=6) as pool:
            results = list(pool.map(
                lambda i: _post(port, {"prompt": f"herd{i}",
                                       "max_tokens": 1}),
                range(12),
            ))
        assert all(status == 200 for status, _ in results)
        assert all(out["choices"][0]["text"] == "r1" for _, out in results)
        assert fleet.pool.discards > discards0
    finally:
        gw.shutdown()
        gw.server_close()
        fleet.stop_all(drain=False)


def test_drain_severs_idle_pooled_connections_not_inflight():
    """drain() closes exactly the PARKED keep-alive connections: the
    pooled idle socket dies (stale at next checkout, counted), while a
    request in flight at drain time completes untouched."""
    servers: list = []
    fleet = _fleet(_stub_replica("r0", servers, delay_s=0.3))
    try:
        addr = fleet.views()[0].address
        server = servers[0]
        # Park one pooled connection (the probe's), then drain with a
        # request in flight on a SECOND connection.
        assert fleet.pool.idle_count() == 1
        results: list = []

        def slow_post():
            conn = http.client.HTTPConnection(addr[0], addr[1], timeout=30)
            conn.request("POST", "/v1/completions",
                         body=json.dumps({"prompt": "x"}).encode(),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            results.append((resp.status, resp.read()))
            conn.close()

        t = threading.Thread(target=slow_post, daemon=True)
        t.start()
        time.sleep(0.1)  # request is mid-handler (delay_s=0.3)
        server.drain()
        t.join(timeout=10)
        assert results and results[0][0] == 200  # in-flight survived
        # The parked pooled connection was severed: checkout detects the
        # stale socket and discards it instead of reusing.
        time.sleep(0.05)
        d0 = fleet.pool.discards
        conn = fleet.pool.checkout("r0", addr, timeout=5.0)
        assert fleet.pool.discards == d0 + 1
        conn.close()
        # The server still answers (metadata keeps working while
        # draining) — on a FRESH connection, which is no longer kept
        # alive while draining.
        health = fleet.probe("r0", timeout=5.0)
        assert health
        assert fleet.views()[0].draining
    finally:
        fleet.stop_all(drain=False)


# ---------------------------------------------------------------------------
# The overhead drill, pooled and fresh, + the perf_compare gate
# ---------------------------------------------------------------------------


def test_gateway_overhead_bench_ab_and_perf_compare(tmp_path):
    """THE acceptance drill (ISSUE 14): pooled and fresh on the same stub
    fleet via run_gateway_overhead_bench — upstream connects collapsing
    from ~one-per-request to ~pool-size and the pool's hit ratio say the
    reuse happened (which leg is FASTER is a timing on shared CPU cores,
    read for presence only); perf_compare 0 on a row against its copy and
    1 on a synthetically degraded copy."""
    from ditl_tpu.telemetry.perf_compare import compare_records
    from tests.gateway_drivers import run_gateway_overhead_bench

    fresh = run_gateway_overhead_bench(150, pool_max_idle=0)
    pooled = run_gateway_overhead_bench(150)
    fb, pb = fresh["gateway_overhead"], pooled["gateway_overhead"]
    assert not fb["pooled"] and pb["pooled"]
    # The throughput instrument ran on both legs.
    assert pb["gateway_rps"] > 0 and fb["gateway_rps"] > 0
    # Reuse evidence: fresh pays ~a connect per request, pooled a handful.
    assert fb["upstream_connects"] >= 150
    assert pb["upstream_connects"] <= 3 * 8 + 4
    assert pb["pool_hit_ratio"] > 0.8
    assert fb["pool_hit_ratio"] == 0.0
    # perf_compare: a row against its own copy passes (exit 0)...
    code, report = compare_records(pooled, copy.deepcopy(pooled), 0.05)
    assert code == 0, report
    # ...and a synthetically degraded copy is a gated regression (exit 1)
    # on exactly the three advertised keys.
    degraded = copy.deepcopy(pooled)
    degraded["value"] = round(pooled["value"] * 0.5, 1)
    block = degraded["gateway_overhead"]
    block["gateway_rps"] = degraded["value"]
    block["gateway_added_p50_s"] = pb["gateway_added_p50_s"] * 3
    block["gateway_added_p95_s"] = pb["gateway_added_p95_s"] * 3
    code, report = compare_records(pooled, degraded, 0.05)
    assert code == 1
    assert "gateway_rps" in report
    assert "gateway_added_p50_s" in report
