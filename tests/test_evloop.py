"""Event-driven gateway data plane drills (ISSUE 17, gateway/evloop.py).

The claims under test, in order of how expensive they are to get wrong:

- **Many streams, few threads** — the module's reason to exist: a
  four-digit idle SSE hold must not grow the gateway's resident thread
  count past loop + offload pool (thread-per-stream reads ~N here; the
  threaded plane is exempt by design).
- **Drain under open streams** — every live relay either completes or
  is severed WITH its accounting (``stream_aborts``); completed +
  aborted == opened, zero silent drops.
- **Framing units** — ``_frame_request`` is the loop's only parser;
  partial/pipelined/malformed/oversized each have one exact behavior.
- **Sticky/pipelining plumbing** — two requests written back-to-back on
  one connection both answer (the carry/leftover path between loop and
  offload worker).
- **Loop self-metrics** — the ``ditl_gateway_loop_*`` family shows up
  on a live /metrics scrape with believable values.
- **Threaded fallback** — ``gateway.data_plane = "threaded"`` still
  selects the legacy transport and relays a stream end to end.

The SSE replica stand-ins and the open-loop hold client are imported
from tests/gateway_drivers.py (selector-based on both sides, so the drills
measure the GATEWAY's threads, not scaffolding threads)."""

from __future__ import annotations

import json
import socket
import threading
import time

import pytest

from ditl_tpu.config import GatewayConfig
from ditl_tpu.gateway import (
    Fleet, GatewayMetrics, InProcessReplica, make_gateway,
)
from ditl_tpu.gateway.evloop import (
    EventLoopGateway, _BadRequest, _frame_request,
)
from tests.gateway_drivers import (
    _SelectorSSEStub, gateway_thread_count, hold_open_sse_streams,
)

pytestmark = [pytest.mark.evloop, pytest.mark.gateway]


# ---------------------------------------------------------------------------
# framing units
# ---------------------------------------------------------------------------


def test_frame_request_units():
    # incomplete header block: need more bytes
    assert _frame_request(bytearray(b"POST /x HTTP/1.1\r\nHost: a\r\n")) \
        is None
    # complete, no body
    req = b"GET /metrics HTTP/1.1\r\nHost: a\r\n\r\n"
    assert _frame_request(bytearray(req)) == len(req)
    # complete with Content-Length body
    body = b'{"k": 1}'
    req = (b"POST /v1/completions HTTP/1.1\r\nHost: a\r\n"
           b"Content-Length: %d\r\n\r\n" % len(body)) + body
    assert _frame_request(bytearray(req)) == len(req)
    # body still in flight
    assert _frame_request(bytearray(req[:-3])) is None
    # pipelined: frames the FIRST request only
    assert _frame_request(bytearray(req + req)) == len(req)
    with pytest.raises(_BadRequest):
        _frame_request(bytearray(
            b"POST /x HTTP/1.1\r\nContent-Length: nope\r\n\r\n"))
    with pytest.raises(_BadRequest):  # oversized header block, no CRLFCRLF
        _frame_request(bytearray(b"X" * (70 * 1024)))
    with pytest.raises(_BadRequest):  # lying Content-Length
        _frame_request(bytearray(
            b"POST /x HTTP/1.1\r\nContent-Length: 999999999999\r\n\r\n"))


# ---------------------------------------------------------------------------
# live-gateway drills
# ---------------------------------------------------------------------------


def _sse_fleet(n=2):
    stubs: list[_SelectorSSEStub] = []

    def factory():
        stub = _SelectorSSEStub()
        stubs.append(stub)
        return stub

    fleet = Fleet([InProcessReplica(f"s{i}", factory) for i in range(n)])
    fleet.start_all()
    for rid in fleet.ids:
        assert fleet.probe(rid, timeout=5.0)
    return fleet, stubs


def _start_evloop_gateway(fleet, config=None, metrics=None):
    server = make_gateway(fleet, config=config or GatewayConfig(),
                          metrics=metrics or GatewayMetrics(), port=0)
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="gw-loop").start()
    return server, server.server_address[1]


def test_make_gateway_dispatches_on_data_plane():
    fleet, _ = _sse_fleet(n=1)
    try:
        ev = make_gateway(fleet, config=GatewayConfig(), port=0)
        try:
            assert isinstance(ev, EventLoopGateway)  # evloop is default
        finally:
            ev.server_close()
        thr = make_gateway(
            fleet, config=GatewayConfig(data_plane="threaded"), port=0)
        try:
            assert not isinstance(thr, EventLoopGateway)
        finally:
            thr.server_close()
    finally:
        fleet.stop_all(drain=False)


def test_idle_stream_hold_small_thread_ceiling():
    """1000 held SSE streams; the gateway's resident thread count must
    stay pinned at loop + offload pool — the claim the whole data plane
    exists for. Relative to the pre-test baseline so another module's
    not-yet-reaped pool thread cannot fail the drill."""
    baseline = gateway_thread_count()
    fleet, _ = _sse_fleet()
    metrics = GatewayMetrics()
    server, port = _start_evloop_gateway(fleet, metrics=metrics)
    peak = 0
    socks: list = []
    try:
        def sample():
            nonlocal peak
            peak = max(peak, gateway_thread_count())

        socks, opened = hold_open_sse_streams(port, 1000, sample=sample)
        assert opened == 1000
        for _ in range(5):  # steady state, not just the ramp burst
            time.sleep(0.05)
            sample()
        # loop + offload workers (+ lazily spawned hedge/fanout), never
        # thread-per-stream: 1000 streams, ceiling stays in the teens.
        assert peak - baseline <= 16, (
            f"gateway grew {peak - baseline} threads under a 1000-stream "
            f"hold (baseline {baseline}, peak {peak})")
        assert metrics.loop_open_sse_streams.value >= opened
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


def test_drain_under_open_streams_no_silent_drops():
    """100 live relays; one replica finishes its streams (clean upstream
    EOF -> completed), then drain severs the rest before its deadline —
    and every severed stream is COUNTED (stream_aborts). The books must
    close exactly: completed + aborted == opened."""
    fleet, stubs = _sse_fleet()
    metrics = GatewayMetrics()
    server, port = _start_evloop_gateway(fleet, metrics=metrics)
    socks: list = []
    try:
        socks, opened = hold_open_sse_streams(port, 100)
        assert opened == 100
        finishing = stubs[0].streams_opened
        assert 0 < finishing < 100  # both outcomes exercised
        stubs[0].finish_streams()
        deadline = time.monotonic() + 10.0
        while (metrics.completed.value < finishing
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert metrics.completed.value == finishing
        server.drain(timeout_s=1.0)
        # Severed-stream accounting runs on offload workers: poll, then
        # pin the invariant exactly.
        deadline = time.monotonic() + 10.0
        while (metrics.completed.value + metrics.stream_aborts.value < 100
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert metrics.completed.value + metrics.stream_aborts.value == 100
        assert metrics.stream_aborts.value == 100 - finishing
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


def test_pipelined_requests_on_one_connection():
    """Two requests written in a single send: the first dispatches off
    the loop's framing, the second rides the carry/leftover path through
    the offload worker (sticky) or back into the loop's inbuf — either
    way both must answer, in order, on the same connection."""
    fleet, _ = _sse_fleet(n=1)
    server, port = _start_evloop_gateway(fleet)
    try:
        req = (b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n")
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=10.0) as s:
            s.sendall(req + req)
            s.settimeout(10.0)
            buf = b""
            deadline = time.monotonic() + 10.0
            while (buf.count(b"HTTP/1.1 200") < 2
                   and time.monotonic() < deadline):
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
        assert buf.count(b"HTTP/1.1 200") == 2, buf[:200]
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


def test_loop_metrics_on_scrape():
    """The ditl_gateway_loop_* family is live on /metrics while a stream
    is held: open connections and open streams read >= 1, the tick
    histogram has observations."""
    fleet, _ = _sse_fleet(n=1)
    server, port = _start_evloop_gateway(fleet)
    socks: list = []
    try:
        socks, opened = hold_open_sse_streams(port, 1)
        assert opened == 1
        deadline = time.monotonic() + 10.0
        text = ""
        while time.monotonic() < deadline:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=10.0) as s:
                s.sendall(b"GET /metrics HTTP/1.1\r\nHost: t\r\n"
                          b"Connection: close\r\n\r\n")
                chunks = []
                while True:
                    c = s.recv(65536)
                    if not c:
                        break
                    chunks.append(c)
            text = b"".join(chunks).decode("utf-8", "replace")
            if "ditl_gateway_loop_open_sse_streams 1" in text:
                break
            time.sleep(0.05)
        assert "ditl_gateway_loop_open_sse_streams 1" in text
        assert "ditl_gateway_loop_tick_seconds_count" in text
        assert "ditl_gateway_loop_accept_backlog_drops_total" in text
        # at least the scrape's own connection is open right now
        for line in text.splitlines():
            if line.startswith("ditl_gateway_loop_open_connections "):
                assert float(line.split()[1]) >= 1.0
                break
        else:
            raise AssertionError("no open_connections sample")
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)


def test_threaded_fallback_relays_stream_end_to_end():
    """data_plane="threaded" still selects the legacy transport and a
    full SSE relay works: first chunk, then [DONE] + EOF when the
    replica finishes."""
    fleet, stubs = _sse_fleet(n=1)
    metrics = GatewayMetrics()
    server = make_gateway(
        fleet, config=GatewayConfig(data_plane="threaded"),
        metrics=metrics, port=0)
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="gw-threaded").start()
    port = server.server_address[1]
    try:
        payload = json.dumps({"prompt": "x", "max_tokens": 4,
                              "stream": True}).encode()
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=10.0) as s:
            s.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Content-Length: %d\r\n\r\n" % len(payload)
                      + payload)
            s.settimeout(10.0)
            buf = b""
            while b"data:" not in buf:
                chunk = s.recv(65536)
                assert chunk, f"EOF before first SSE chunk: {buf[:200]!r}"
                buf += chunk
            stubs[0].finish_streams()
            while True:
                try:
                    chunk = s.recv(65536)
                except socket.timeout:
                    raise AssertionError(
                        f"no EOF after upstream finish: {buf[-200:]!r}")
                if not chunk:
                    break
                buf += chunk
        assert b"data: [DONE]" in buf
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)
