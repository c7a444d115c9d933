"""Host-side fleet drivers shared by the gateway drills.

Each ``run_*`` function stands up an in-process fleet (real tiny engines or
zero-compute stubs) behind a real gateway, drives it over HTTP, and returns
one row: counts the drills assert on, plus the timings the instruments took
— which a drill may check for presence and hand to ``perf_compare`` against
a copy of the same row, never order against another live run (every number
here is taken on shared CPU cores). The selector-based SSE stub and hold
client keep both sides of a stream hold thread-free, so the drills count
the GATEWAY's threads, not scaffolding.
"""

from __future__ import annotations

import json
import os
import random
import selectors
import shutil
import socket
import tempfile
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

# The drills' models: just deep enough that a long prefill is visibly more
# work than a decode tick, small enough that three replicas compile in
# seconds on the CPU.
_FLEET_MODEL = dict(
    vocab_size=2048, hidden_size=128, intermediate_size=344, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=32, max_seq_len=1024,
    dtype="bfloat16", param_dtype="float32",
)
_REPLAY_MODEL = dict(
    vocab_size=512, hidden_size=64, intermediate_size=176, num_layers=1,
    num_heads=2, num_kv_heads=2, head_dim=32, max_seq_len=256,
    dtype="bfloat16", param_dtype="float32",
)
_PAGE_SIZE = 16
_REPLAY_SLOTS = 2
_REPLAY_SPEED = 1.5  # recorded offsets are replayed this much faster
_SLO_TTFT_S = 2.5
_OVERHEAD_REPLICAS = 2
_OVERHEAD_CLIENTS = 3

# What a healthy, idle replica answers on GET /health.
_HEALTH_BODY = json.dumps({
    "status": "ok", "draining": False, "queue_depth": 0,
    "active_slots": 0, "n_slots": 8,
}).encode()

_ANALYSIS_CLEAN: bool | None = None


def _analysis_clean() -> bool:
    """True when the invariant lint (`python -m ditl_tpu.analysis`) passes
    over the package. Computed once per process and stamped on every row so
    `perf_compare` treats a newly-dirty tree as a "now fails" regression.
    An analyzer crash stamps False (a gate that cannot run must not read
    as clean)."""
    global _ANALYSIS_CLEAN
    if _ANALYSIS_CLEAN is None:
        try:
            import ditl_tpu
            from ditl_tpu.analysis import run as _run_lint

            pkg_dir = os.path.dirname(os.path.abspath(ditl_tpu.__file__))
            _ANALYSIS_CLEAN = not _run_lint(pkg_dir)
        except Exception:  # noqa: BLE001 - the stamp must never kill a drill
            _ANALYSIS_CLEAN = False
    return _ANALYSIS_CLEAN


def _record_meta() -> dict:
    """Schema + provenance stamp every row carries, so `perf_compare` can
    refuse cross-schema diffs and gate on the lint verdict."""
    from ditl_tpu.telemetry.perf import SWEEP_SCHEMA, git_rev

    return {"schema": SWEEP_SCHEMA, "git_rev": git_rev(),
            "analysis_clean": _analysis_clean()}


def _post_completion(address, body: dict, timeout: float = 600) -> dict:
    req = urllib.request.Request(
        f"http://{address[0]}:{address[1]}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _tiny_fleet_parts(model: dict):
    """(params, cfg, tokenizer, shared Generator) for an in-process fleet
    of real engines over one set of weights."""
    import jax

    from ditl_tpu.config import ModelConfig
    from ditl_tpu.data.tokenizer import ByteTokenizer
    from ditl_tpu.infer.engine import Generator
    from ditl_tpu.models import llama

    cfg = ModelConfig(name="drill-tiny", **model)
    params = llama.init_params(jax.random.key(0), cfg)
    tok = ByteTokenizer()
    # The Generator serves the tokenize/metadata routes only.
    return params, cfg, tok, Generator(params, cfg, tok)


def run_gateway_bench(n_replicas: int, *, roles: str, slots: int,
                      decode_chunk: int, prompt_len: int, max_new: int,
                      prefill_chunk: int, token_budget: int,
                      trace_out: str = "") -> dict:
    """The mixed-trace fleet drill: ``n_replicas`` continuous-engine
    replicas (paged KV, so the prefix-cache hit ratio is measured) behind
    the gateway's affinity router, one role each (``roles``:
    comma-separated, gateway/roles.py; shorter specs pad with hybrid, and
    each replica's engine knobs derive via role_knobs from the base
    slots/prefill_chunk/token_budget). The traffic is interactive-class
    short streams in prefix groups plus one long batch-class prompt per
    replica, submitted last so batch work lands while the streams are
    mid-decode. The row carries per-class serving summaries of the driven
    region only, ``fleet_roles`` and per-role sub-blocks; ``trace_out``
    arms request tracing on the gateway and every engine and writes the
    merged journals there as Chrome-trace JSON."""
    from ditl_tpu.config import GatewayConfig
    from ditl_tpu.gateway import (
        Fleet, GatewayMetrics, InProcessReplica, make_gateway, parse_roles,
        role_knobs,
    )
    from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
    from ditl_tpu.infer.engine import GenerateConfig
    from ditl_tpu.infer.server import make_server
    from ditl_tpu.telemetry.serving import (
        serving_bench_summary, snapshot_serving,
    )

    role_list = parse_roles(roles, n_replicas)
    params, cfg, tok, shared_gen = _tiny_fleet_parts(_FLEET_MODEL)
    n_requests = n_replicas * slots * 2
    # Every request must fit in one replica's admission queue (a worst-case
    # affinity pileup must spill, not 429 the drill).
    total_requests = n_requests + n_replicas
    tracers: list = [None] * n_replicas
    gw_tracer = None
    trace_dir = ""
    trace_journals: list = []
    if trace_out:
        from ditl_tpu.telemetry.journal import EventJournal
        from ditl_tpu.telemetry.tracing import Tracer

        trace_dir = tempfile.mkdtemp(prefix="ditl-drill-trace-")
        for source in [f"replica-{i}" for i in range(n_replicas)] + [
                "gateway"]:
            trace_journals.append(EventJournal(
                os.path.join(trace_dir, f"events-{source}.jsonl"),
                source=source))
        tracers = [Tracer(j) for j in trace_journals[:-1]]
        gw_tracer = Tracer(trace_journals[-1])
    # Pages are made explicit so a role's scale applies to the same
    # contiguous-equivalent default the engine would have picked.
    maxp = -(-cfg.max_seq_len // _PAGE_SIZE)
    knob_list = [
        role_knobs(role, n_slots=slots, decode_chunk=decode_chunk,
                   prefill_chunk=prefill_chunk, token_budget=token_budget)
        for role in role_list
    ]
    engines = [
        ThreadedEngine(ContinuousEngine(
            params, cfg, tok, n_slots=k["n_slots"],
            decode_chunk=decode_chunk,
            gen=GenerateConfig(max_new_tokens=max_new),
            max_queue=total_requests,
            cache_mode="paged", page_size=_PAGE_SIZE,
            n_pages=int(k["pages_scale"] * (k["n_slots"] * maxp + 1)),
            prefill_chunk=k["prefill_chunk"],
            token_budget=k["token_budget"],
            tracer=tracers[i],
        ))
        for i, k in enumerate(knob_list)
    ]

    def factory(eng, role):
        # make_server derives its tracer from the engine's, so replica
        # server.request spans land in the same per-replica journal.
        return lambda: make_server(shared_gen, port=0, threaded_engine=eng,
                                   default_max_tokens=max_new, role=role)

    fleet = Fleet([
        InProcessReplica(f"r{i}", factory(eng, role_list[i]),
                         role=role_list[i])
        for i, eng in enumerate(engines)
    ])
    fleet.start_all(wait_healthy_s=30.0)
    # Key on exactly the shared group prefix: the default 32 would swallow
    # the unique suffix whenever prompt_len < 32, making every key distinct.
    server = make_gateway(
        fleet, config=GatewayConfig(router="affinity",
                                    affinity_prefix_tokens=prompt_len),
        metrics=GatewayMetrics(), port=0, tracer=gw_tracer)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    gw_address = ("127.0.0.1", server.server_address[1])

    # n_replicas * 2 prefix groups of interactive streams with alternating
    # generation lengths (identical lengths would march the fleet in
    # synchronized admit/decode cohorts where prefills never co-schedule
    # against live decodes), shuffled deterministically so groups
    # interleave; then the longs (4x prompt_len, distinct prefixes — they
    # must not seed the groups' caches).
    groups = n_replicas * 2
    long_plen = prompt_len * 4
    prompts = []
    for g in range(groups):
        prefix = " ".join(f"g{g}tok{j}" for j in range(prompt_len))
        for i in range(max(1, n_requests // groups)):
            prompts.append((f"{prefix} q{i}", "interactive",
                            max_new * 2 if i % 2 else max_new))
    random.Random(7).shuffle(prompts)
    prompts += [
        (" ".join(f"long{g}tok{j}" for j in range(long_plen)), "batch",
         max_new)
        for g in range(n_replicas)
    ]

    def one(item):
        prompt, slo_class, max_tokens = item
        return _post_completion(gw_address, {
            "prompt": prompt, "max_tokens": max_tokens,
            "slo_class": slo_class,
        })["usage"]["completion_tokens"]

    warm_prompt = " ".join(f"warmtok{j}" for j in range(prompt_len))
    warm_long = " ".join(f"warmlongtok{j}" for j in range(long_plen))

    def warm(view):
        # Compile each engine OUTSIDE the driven region by hitting every
        # replica directly (routed warm-ups would herd on whatever subset
        # the policy picks). The second prompt is the PREFIX-HIT admission
        # shape — a group's second request prefills only the short suffix,
        # a different program than the whole-prompt warm; uncompiled, it
        # would land as a multi-second interference observation on
        # whichever decode co-scheduled with it. The long bucket is warmed
        # wherever batch work can land (role steering keeps longs off
        # decode_heavy).
        warms = [warm_prompt, f"{warm_prompt} q0"]
        if view.role != "decode_heavy":
            warms.append(warm_long)
        for p in warms:
            _post_completion(view.address, {"prompt": p,
                                            "max_tokens": max_new})

    bundles_by_role: dict = {}
    for role, eng in zip(role_list, engines):
        bundles_by_role.setdefault(role, []).append(eng._engine.metrics)
    with ThreadPoolExecutor(max_workers=n_replicas * slots) as pool:
        list(pool.map(warm, fleet.views()))
        # Snapshot AFTER warm-up: the summaries cover the driven region
        # only (warm TTFTs are compile seconds), per role too, and the
        # worst-observation trackers reset with them.
        serving_base = snapshot_serving(
            [eng._engine.metrics for eng in engines])
        role_base = {
            role: snapshot_serving(b) for role, b in bundles_by_role.items()
        }
        for eng in engines:
            eng._engine.interference_max_s = 0.0
            eng._engine.interference_max_by_class = {}
        tokens = sum(pool.map(one, prompts))
    if trace_out:
        from ditl_tpu.telemetry.trace_export import (
            load_trace_records, to_chrome_trace,
        )

        for j in trace_journals:
            j.close()
        with open(trace_out, "w") as f:
            json.dump(to_chrome_trace(load_trace_records(trace_dir)), f)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # Worst single interactive interference observation across the fleet:
    # the stall an interactive stream absorbed in one tick. None when no
    # interactive victim was ever co-scheduled.
    i_max = [
        eng._engine.interference_max_by_class.get("interactive")
        for eng in engines
    ]
    i_max = [v for v in i_max if v is not None]
    row = {
        **_record_meta(),
        "generated_tokens": tokens,
        "requests": len(prompts),
        "serving": {
            "interactive_interference_max_s": (
                round(max(i_max), 6) if i_max else None
            ),
            **serving_bench_summary(
                [eng._engine.metrics for eng in engines],
                since=serving_base,
            ),
        },
        "gateway": {
            "fleet_roles": role_list,
            "serving_by_role": {
                role: serving_bench_summary(b, since=role_base[role])
                for role, b in bundles_by_role.items()
            },
        },
    }
    server.shutdown()
    server.server_close()
    fleet.stop_all(drain=True, timeout=10.0)
    for eng in engines:
        eng.close()
    return row


def run_trace_replay_bench(trace_path: str, n_replicas: int, *,
                           autoscale: bool = False, min_replicas: int = 1,
                           autoscale_overrides: dict | None = None,
                           bulk_backlog: int = 0) -> dict:
    """Traffic-trace replay: drive a recorded request shape (``gateway
    --save-trace`` JSONL, or a committed shape under
    ``tests/fixtures/traces/``) through an in-process gateway fleet with
    its inter-arrival times preserved (compressed by ``_REPLAY_SPEED``).
    The row embeds ``replica_seconds`` (the integral of live replicas over
    the replay) and the actions the planner took next to the serving block.
    ``autoscale=True`` arms an Actuator on the FleetSupervisor
    (``min_replicas`` floors ordinary scale-down, ``autoscale_overrides``
    tunes the planner); ``bulk_backlog`` > 0 submits an N-item job through
    the real ``POST /v1/bulk/jobs`` before the replay, which soaks spare
    decode capacity through ``best_effort`` relays while the interactive
    trace replays — the row grows a ``bulk`` block."""
    from ditl_tpu.config import AutoscaleConfig, GatewayConfig
    from ditl_tpu.gateway import (
        Actuator, Fleet, FleetSupervisor, GatewayMetrics, InProcessReplica,
        load_trace, make_gateway,
    )
    from ditl_tpu.infer.continuous import ContinuousEngine, ThreadedEngine
    from ditl_tpu.infer.engine import GenerateConfig
    from ditl_tpu.infer.server import make_server

    rows = load_trace(trace_path)
    if not rows:
        raise ValueError(f"no replayable rows in {trace_path}")
    default_max_new = max(
        [int(r.get("max_new") or 0) for r in rows] + [8]
    )
    params, cfg, tok, shared_gen = _tiny_fleet_parts(_REPLAY_MODEL)
    engines = [
        ThreadedEngine(ContinuousEngine(
            params, cfg, tok, n_slots=_REPLAY_SLOTS, decode_chunk=2,
            gen=GenerateConfig(max_new_tokens=default_max_new),
            max_queue=len(rows) + 8,
        ))
        for _ in range(n_replicas)
    ]

    def factory(eng):
        # In-process replicas adopt their engine across restarts, so the
        # cold start is the (tiny) server rebuild.
        return lambda: make_server(shared_gen, port=0, threaded_engine=eng,
                                   default_max_tokens=default_max_new,
                                   cold_start_s=0.05)

    fleet = Fleet([
        InProcessReplica(f"r{i}", factory(eng))
        for i, eng in enumerate(engines)
    ])
    fleet.start_all(wait_healthy_s=30.0)
    gw_metrics = GatewayMetrics()
    supervisor = FleetSupervisor(
        fleet, interval_s=0.05, fail_threshold=3,
        probe_timeout_s=2.0, restart_timeout_s=20.0,
    )
    bulk_manager = None
    bulk_dir = ""
    if bulk_backlog > 0:
        from ditl_tpu.config import BulkConfig
        from ditl_tpu.gateway.bulk import BulkJobManager

        # One in-flight slot per replica: the lane soaks spare decode
        # slots without queueing deeper than the fleet can absorb.
        bulk_dir = tempfile.mkdtemp(prefix="ditl-bulk-drill-")
        bulk_manager = BulkJobManager(
            bulk_dir,
            BulkConfig(dir=bulk_dir, max_in_flight=max(1, n_replicas)),
            registry=gw_metrics.registry,
        )
    actuator = None
    if autoscale:
        as_kwargs = dict(
            enabled=True, min_replicas=min_replicas,
            up_hysteresis_polls=1, hysteresis_polls=4,
            cooldown_s=1.0, drain_wait_s=2.0,
        )
        as_kwargs.update(autoscale_overrides or {})
        actuator = Actuator(
            fleet, supervisor, AutoscaleConfig(**as_kwargs),
            metrics=gw_metrics, bulk=bulk_manager,
        )
        supervisor.autoscaler = actuator
    gwcfg = GatewayConfig(router="affinity", affinity_prefix_tokens=4)
    server = make_gateway(fleet, config=gwcfg, metrics=gw_metrics, port=0,
                          actuator=actuator, bulk=bulk_manager)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        row = _run_trace_replay_timed(
            rows, engines, fleet, supervisor, actuator,
            server.server_address[1], default_max_new=default_max_new,
            bulk=bulk_manager, bulk_backlog=bulk_backlog,
        )
    finally:
        # One finally covers the replay too: a failed request must not leak
        # the gateway server, the supervisor, or the engines into the rest
        # of the pytest session. The bulk manager stops FIRST so its
        # dispatch threads quit issuing relays before the fleet drains.
        if bulk_manager is not None:
            bulk_manager.close()
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=True, timeout=10.0)
        for eng in engines:
            eng.close()
        if bulk_manager is not None:
            shutil.rmtree(bulk_dir, ignore_errors=True)
    row["metric"] = "trace replay (%d replica(s) x %d slots, autoscale=%s%s)" % (
        n_replicas, _REPLAY_SLOTS, "on" if autoscale else "off",
        ", bulk=%d" % bulk_backlog if bulk_backlog else "")
    return row


def _run_trace_replay_timed(rows, engines, fleet, supervisor, actuator,
                            port, *, default_max_new, bulk,
                            bulk_backlog) -> dict:
    """The warmed+replayed half of :func:`run_trace_replay_bench`; the
    caller owns (and always tears down) the fleet/server/engines."""
    from ditl_tpu.gateway import ReplicaSecondsSampler
    from ditl_tpu.telemetry.serving import (
        serving_bench_summary, snapshot_serving, ttft_slo_violation_rate,
    )

    gw_address = ("127.0.0.1", port)

    def prompt_for(row) -> str:
        # Tenant digest as the shared token prefix: same-tenant traffic
        # shares an affinity key (and a reusable prompt prefix), the
        # regime the recorded shape came from.
        tenant = str(row.get("tenant") or "anon")
        n = max(4, int(row.get("prompt_tokens") or 8))
        return " ".join(f"{tenant}w{j}" for j in range(n))

    def one(row):
        delay = t_start + row["t"] / _REPLAY_SPEED - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        body = {"prompt": prompt_for(row),
                "max_tokens": int(row.get("max_new") or default_max_new)}
        if row.get("slo_class"):
            body["slo_class"] = row["slo_class"]
        deadline = time.monotonic() + 120.0
        while True:
            try:
                return _post_completion(
                    gw_address, body, timeout=120
                )["usage"]["completion_tokens"]
            except urllib.error.HTTPError as e:
                # 429 = throttle or scale-to-zero wake promise: honor the
                # Retry-After like a real client. Anything else fails.
                e.read()
                if e.code != 429 or time.monotonic() > deadline:
                    raise
                time.sleep(min(5.0, float(e.headers.get("Retry-After", 1))))

    # Warm every PROMPT SHAPE the trace will replay, on every replica: the
    # byte tokenizer makes prefill shape = byte length, so warm with the
    # EXACT replay prompts, or a compile lands inside the replay.
    warm_prompts = sorted({prompt_for(r) for r in rows})

    def warm(view):
        for prompt in warm_prompts:
            _post_completion(view.address, {"prompt": prompt,
                                            "max_tokens": default_max_new})

    bundles = [eng._engine.metrics for eng in engines]
    sampler = ReplicaSecondsSampler(fleet, interval_s=0.02)
    # The sampler/supervisor threads stop even when a replay request
    # fails; the caller's finally owns the server/fleet/engine teardown.
    try:
        with ThreadPoolExecutor(max_workers=max(8, len(rows))) as pool:
            list(pool.map(warm, fleet.views()))
            serving_base = snapshot_serving(bundles)
            bulk_job_id, bulk_tok0 = "", 0
            if bulk is not None:
                # Submit through the REAL endpoint so the row exercises the
                # whole lane (parse -> quota -> journal -> relay). Prompts
                # cycle the already-warmed shapes.
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/bulk/jobs",
                    data=json.dumps({
                        "prompts": [warm_prompts[i % len(warm_prompts)]
                                    for i in range(bulk_backlog)],
                        "max_new": default_max_new,
                    }).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=30) as resp:
                    bulk_job_id = json.loads(resp.read())["id"]
            supervisor.start()
            sampler.start()
            if bulk is not None:
                bulk_tok0 = bulk.tokens_total()
            t_start = time.perf_counter()
            tokens = sum(pool.map(one, rows))
            dt = time.perf_counter() - t_start
    finally:
        replica_seconds = sampler.stop()
        supervisor.stop()
    actions: dict[str, int] = {}
    if actuator is not None:
        for entry in actuator.recent():
            key = f"{entry['kind']}_{entry['outcome']}"
            actions[key] = actions.get(key, 0) + 1
    # Summarize the replay BEFORE draining the bulk tail — the post-replay
    # drain would otherwise leak its (idle-fleet) TTFTs into the block.
    serving_summary = serving_bench_summary(bundles, since=serving_base)
    row = {
        **_record_meta(),
        "generated_tokens": tokens,
        "requests": len(rows),
        "serving": serving_summary,
        "autoscale": {
            "replica_seconds": round(replica_seconds, 3),
            "ttft_slo_violation_rate": ttft_slo_violation_rate(
                bundles, _SLO_TTFT_S, since=serving_base),
            "actions": actions,
        },
    }
    if bulk is not None:
        bulk_tokens = bulk.tokens_total() - bulk_tok0
        drained = bulk.drain(timeout_s=120.0)
        rec = bulk.status(bulk_job_id) or {}
        # Interactive TTFT p95 measured WITH the backlog running:
        # class-split when the trace carries SLO classes, fleet-wide
        # otherwise.
        ttft = serving_summary.get("interactive_ttft_p95_s")
        if ttft is None:
            ttft = serving_summary.get("ttft_p95_s")
        row["bulk"] = {
            "backlog": bulk_backlog,
            "bulk_tokens_per_s": round(bulk_tokens / dt, 1) if dt > 0 else 0.0,
            "bulk_interactive_ttft_p95_s": ttft,
            "drained": drained,
            "items_completed": int(rec.get("n_done") or 0),
        }
    return row


def _percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(q * len(sorted_vals))))
    return sorted_vals[idx]


class _SelectorSSEStub:
    """Selector-based SSE replica stand-in: answers ``GET
    /health`` with the usual JSON and every POST with an SSE first chunk,
    then HOLDS the stream open — no thread per connection on the replica
    either, so a stream hold doesn't smuggle N *stub* threads into the
    count it exists to pin. Implements the InProcessReplica lifecycle
    contract (``serve_forever`` / ``close`` / ``kill`` /
    ``server_address``); ``finish_streams()`` completes every held
    stream (``data: [DONE]`` + close) — the drain drill's "some streams
    finish" lever."""

    def __init__(self):
        self._sel = selectors.DefaultSelector()
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(1024)
        self._lsock.setblocking(False)
        self.server_address = self._lsock.getsockname()[:2]
        self._rsock, self._wsock = socket.socketpair()
        self._rsock.setblocking(False)
        self._wsock.setblocking(False)
        self._cmds: list = []  # append/pop(0) are atomic; wake byte signals
        self._bufs: dict = {}  # parsing sockets -> request bytearray
        self._held: list = []  # sockets with an open SSE stream
        self.streams_opened = 0
        self._stopped = threading.Event()
        self._stopped.set()

    def _wake(self, cmd: str) -> None:
        self._cmds.append(cmd)
        try:
            self._wsock.send(b"\x00")
        except OSError:
            pass

    def serve_forever(self, poll_interval: float = 0.5) -> None:
        self._stopped.clear()
        self._sel.register(self._lsock, selectors.EVENT_READ, "accept")
        self._sel.register(self._rsock, selectors.EVENT_READ, "wake")
        try:
            while True:
                for key, _ in self._sel.select(poll_interval):
                    if key.data == "accept":
                        self._accept()
                    elif key.data == "wake":
                        self._drain_wake()
                    else:
                        self._client(key.fileobj)
                while self._cmds:
                    if self._cmds.pop(0) == "finish":
                        self._finish_all()
                    else:  # "stop"
                        return
        finally:
            for sock in [*self._bufs, *self._held]:
                try:
                    sock.close()
                except OSError:
                    pass
            self._bufs.clear()
            self._held.clear()
            for sock in (self._lsock, self._rsock, self._wsock):
                try:
                    sock.close()
                except OSError:
                    pass
            self._sel.close()
            self._stopped.set()

    def _drain_wake(self) -> None:
        try:
            while self._rsock.recv(4096):
                pass
        except OSError:
            pass

    def _accept(self) -> None:
        for _ in range(128):
            try:
                sock, _ = self._lsock.accept()
            except OSError:
                return
            sock.setblocking(False)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._bufs[sock] = bytearray()
            try:
                self._sel.register(sock, selectors.EVENT_READ, "client")
            except (KeyError, ValueError, OSError):
                sock.close()
                del self._bufs[sock]

    def _drop(self, sock) -> None:
        try:
            self._sel.unregister(sock)
        except (KeyError, ValueError, OSError):
            pass
        self._bufs.pop(sock, None)
        try:
            self._held.remove(sock)
        except ValueError:
            pass
        try:
            sock.close()
        except OSError:
            pass

    def _client(self, sock) -> None:
        try:
            data = sock.recv(65536)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._drop(sock)
            return
        if not data:
            self._drop(sock)
            return
        buf = self._bufs.get(sock)
        if buf is None:
            return  # bytes on a held stream: ignore
        buf += data
        end = buf.find(b"\r\n\r\n")
        if end < 0:
            return
        head = bytes(buf[:end])
        length = 0
        for line in head.split(b"\r\n")[1:]:
            if line[:15].lower() == b"content-length:":
                try:
                    length = int(line[15:])
                except ValueError:
                    length = 0
        if len(buf) < end + 4 + length:
            return  # body still arriving
        self._respond(sock, head)

    def _respond(self, sock, head: bytes) -> None:
        del self._bufs[sock]
        try:
            if head.startswith(b"GET"):
                body = _HEALTH_BODY
                sock.sendall(
                    b"HTTP/1.1 200 OK\r\n"
                    b"Content-Type: application/json\r\n"
                    b"Content-Length: " + str(len(body)).encode() +
                    b"\r\nConnection: close\r\n\r\n" + body)
                self._drop(sock)
                return
            sock.sendall(
                b"HTTP/1.1 200 OK\r\n"
                b"Content-Type: text/event-stream\r\n"
                b"Cache-Control: no-cache\r\n"
                b"Connection: close\r\n\r\n"
                b'data: {"choices": [{"index": 0, "text": "s"}]}\n\n')
        except OSError:
            self._drop(sock)
            return
        self._held.append(sock)
        self.streams_opened += 1

    def _finish_all(self) -> None:
        for sock in list(self._held):
            try:
                sock.sendall(b"data: [DONE]\n\n")
            except OSError:
                pass
            self._drop(sock)

    def finish_streams(self) -> None:
        """Complete every held stream: terminal SSE event, then close
        (SSE is close-delimited — this is a clean upstream EOF)."""
        self._wake("finish")

    def close(self, drain: bool = True, timeout: float = 10.0) -> None:
        self._wake("stop")
        self._stopped.wait(timeout)

    def kill(self) -> None:
        self.close(drain=False)


def gateway_thread_count() -> int:
    """Resident gateway threads right now: every thread the gateway
    owns carries a ``gw-`` name (``gw-loop`` / ``gw-offload`` /
    ``gw-hedge`` / ``gw-fanout``) — the number a stream hold pins to
    loop + offload pool where thread-per-stream would read ~N."""
    return sum(1 for t in threading.enumerate()
               if t.name.startswith("gw-"))


def hold_open_sse_streams(port: int, n: int,
                          sample=None) -> tuple[list, int]:
    """Open-loop SSE client: open ``n`` streams against the
    gateway and hold them, all from THE CALLING THREAD — one selector,
    no client thread per stream (the whole point is that neither side
    of the hold pays a thread). A stream counts as open once its first
    SSE chunk arrives (headers + ``data:``). Connects ride in waves of
    256 so the gateway's accept backlog never overflows. Returns
    ``(sockets, opened)`` — the caller owns closing the sockets;
    ``sample`` (optional callable) runs once per loop pass (thread-count
    sampling during the ramp, when the offload pool is busiest)."""
    payload = json.dumps({"prompt": "hold", "max_tokens": 4,
                          "stream": True}).encode()
    request = (b"POST /v1/completions HTTP/1.1\r\n"
               b"Host: gw\r\nContent-Type: application/json\r\n"
               b"Content-Length: " + str(len(payload)).encode() +
               b"\r\n\r\n" + payload)
    sel = selectors.DefaultSelector()
    socks: list = []
    states: dict = {}  # sock -> [sent_offset, recv_buf, opened]
    opened = dead = 0
    remaining = n
    inflight = 0
    deadline = time.monotonic() + 180.0

    def launch():
        nonlocal remaining, inflight
        while remaining and inflight < 256:
            remaining -= 1
            inflight += 1
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setblocking(False)
            try:
                s.connect_ex(("127.0.0.1", port))
                sel.register(s, selectors.EVENT_WRITE, None)
            except OSError:
                settle(s, ok=False)
                continue
            socks.append(s)
            states[s] = [0, bytearray(), False]

    def settle(s, ok: bool):
        nonlocal opened, dead, inflight
        inflight -= 1
        if ok:
            opened += 1
        else:
            dead += 1
        try:
            sel.unregister(s)
        except (KeyError, ValueError, OSError):
            pass

    launch()
    while opened + dead < n and time.monotonic() < deadline:
        events = sel.select(1.0)
        if sample is not None:
            sample()
        for key, ev in events:
            s = key.fileobj
            st = states[s]
            if ev & selectors.EVENT_WRITE:
                try:
                    sent = s.send(request[st[0]:])
                except (BlockingIOError, InterruptedError):
                    continue
                except OSError:
                    settle(s, ok=False)
                    continue
                st[0] += sent
                if st[0] >= len(request):
                    sel.modify(s, selectors.EVENT_READ, None)
                continue
            try:
                data = s.recv(65536)
            except (BlockingIOError, InterruptedError):
                continue
            except OSError:
                settle(s, ok=False)
                continue
            if not data:
                settle(s, ok=False)
                continue
            st[1] += data
            if not st[2] and b"data:" in st[1]:
                st[2] = True
                # Held: no further events needed — the stream just
                # stays open (the stub never sends more).
                settle(s, ok=True)
        launch()
    sel.close()
    return socks, opened

def run_gateway_overhead_bench(requests: int, *, pool_max_idle: int = -1,
                               usage_dir: str | None = None) -> dict:
    """Gateway data-plane overhead drill: a closed loop of keep-alive HTTP
    clients driving in-process STUB replicas — first directly, then
    through the gateway — so the row isolates the gateway's OWN
    per-request work (routing, admission, relay, the upstream connect)
    from any device work; nothing here imports jax. The hoisted
    ``gateway_overhead`` block embeds requests/sec through the gateway,
    the added latency over the direct leg, and the upstream pool's hit
    ratio + accepted-connection count. ``pool_max_idle=0`` is the
    fresh-connect leg (every upstream hop connects fresh); the default
    (-1) takes GatewayConfig's pooled default.

    ``usage_dir`` runs a further closed loop through a second gateway over
    the same stub fleet with the full per-tenant metering plane armed:
    tenant admission accounting, the credential-safe label digest per
    request, X-Tenant-Label stamping on every relay, routing-ring
    attribution, and the gateway-edge usage LEDGER (one JSONL row per
    request into ``usage_dir``); the row gains a ``usage_metering``
    block."""
    import http.client
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from ditl_tpu.config import GatewayConfig
    from ditl_tpu.gateway import (
        Fleet, GatewayMetrics, InProcessReplica, make_gateway,
    )
    from ditl_tpu.utils.http11 import KeepAliveHandlerMixin

    clients = _OVERHEAD_CLIENTS
    if requests < clients:
        raise ValueError(f"requests ({requests}) must be >= clients "
                         f"({clients})")

    stub_body = json.dumps({
        "object": "text_completion",
        "choices": [{"index": 0, "text": "stub", "finish_reason": "stop"}],
        "usage": {"prompt_tokens": 1, "completion_tokens": 1,
                  "total_tokens": 2},
    }).encode()

    class _StubServer(ThreadingHTTPServer):
        """Keep-alive-capable replica stand-in with the lifecycle hooks
        InProcessReplica drives, counting accepted TCP connections — the
        number the pooled-vs-fresh pair pins (pooled: ~pool size; fresh:
        ~one per request)."""

        daemon_threads = True
        allow_reuse_address = True

        def __init__(self, *args, **kw):
            self.connections = 0
            super().__init__(*args, **kw)

        def process_request(self, request, client_address):
            self.connections += 1
            super().process_request(request, client_address)

        def close(self, drain=True, timeout=30.0):
            self.shutdown()
            self.server_close()

        def kill(self):
            self.close()

    class _StubHandler(KeepAliveHandlerMixin, BaseHTTPRequestHandler):
        def log_message(self, *args):
            pass

        def _json(self, body: bytes):
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            self._json(_HEALTH_BODY)

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            self._json(stub_body)

    stubs: list = []

    def factory():
        server = _StubServer(("127.0.0.1", 0), _StubHandler)
        stubs.append(server)
        return server

    fleet = Fleet([InProcessReplica(f"r{i}", factory)
                   for i in range(_OVERHEAD_REPLICAS)])
    # One try/finally covers startup too: a stub that fails its probe (or
    # a gateway that fails to build) must not leak already-started stub
    # serve loops into the rest of the pytest session.
    server = None
    try:
        fleet.start_all()
        for rid in fleet.ids:
            if not fleet.probe(rid, timeout=5.0):
                raise RuntimeError(f"stub replica {rid} failed its probe")
        gwcfg_kwargs = dict(router="round_robin")
        if pool_max_idle >= 0:
            gwcfg_kwargs["pool_max_idle_per_replica"] = pool_max_idle
        gwcfg = GatewayConfig(**gwcfg_kwargs)
        server = make_gateway(fleet, config=gwcfg,
                              metrics=GatewayMetrics(), port=0)
    except BaseException:
        if server is not None:
            server.server_close()
        fleet.stop_all(drain=False)
        raise
    threading.Thread(target=server.serve_forever, daemon=True,
                     name="gw-loop").start()
    gw_port = server.server_address[1]
    payload = json.dumps({"prompt": "overhead probe",
                          "max_tokens": 1}).encode()
    per_client = requests // clients
    total = per_client * clients

    def drive(port: int, latencies: list, n: int, bearer: str = "") -> None:
        # One kept-alive client connection per thread (all legs): the
        # client side is held constant so the pooled-vs-fresh delta is the
        # UPSTREAM hop alone. ``bearer`` (metered leg) exercises the real
        # per-tenant admission/label path per request.
        headers = {"Content-Type": "application/json"}
        if bearer:
            headers["Authorization"] = f"Bearer {bearer}"
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30.0)
        try:
            conn.connect()
            # The client half of the keep-alive Nagle fix (utils/http11):
            # without NODELAY every request on a kept-alive connection
            # stalls ~40 ms behind the peer's delayed ACK.
            conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            for _ in range(n):
                t0 = time.perf_counter()
                conn.request("POST", "/v1/completions", body=payload,
                             headers=headers)
                resp = conn.getresponse()
                data = resp.read()
                if resp.status != 200:
                    # BEFORE recording the latency: a failed request must
                    # fail the drill, never count as a "served" sample.
                    raise RuntimeError(
                        f"overhead drill got {resp.status}: {data[:200]!r}"
                    )
                latencies.append(time.perf_counter() - t0)
        finally:
            conn.close()

    def closed_loop(port: int, bearer_prefix: str = "") -> tuple[float, list]:
        lat_lists = [[] for _ in range(clients)]
        errors: list = []

        def run(i):
            try:
                drive(port, lat_lists[i], per_client,
                      bearer=f"{bearer_prefix}-{i}" if bearer_prefix else "")
            except BaseException as e:  # re-raised on the caller below
                errors.append(e)

        threads = [
            threading.Thread(target=run, args=(i,), daemon=True)
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errors:
            # The real failure, not an opaque lost-request count.
            raise errors[0]
        lats = sorted(x for lst in lat_lists for x in lst)
        if len(lats) != total:
            raise RuntimeError(
                f"overhead drill lost requests: {len(lats)} != {total}"
            )
        return dt, lats

    metered = None
    try:
        # Warm both legs outside the driven region, then snapshot the pool
        # so its hit ratio covers the gateway loop only.
        direct_port = fleet.views()[0].address[1]
        for port in (direct_port, gw_port):
            drive(port, [], 4)
        _, direct_lats = closed_loop(direct_port)
        p0 = fleet.pool.stats()
        c0 = sum(s.connections for s in stubs)
        gw_dt, gw_lats = closed_loop(gw_port)
        p1 = fleet.pool.stats()
        connects = sum(s.connections for s in stubs) - c0
        if usage_dir is not None:
            from ditl_tpu.gateway.admission import TenantAdmission
            from ditl_tpu.telemetry.flight import FlightRecorder
            from ditl_tpu.telemetry.usage import (
                UsageLedger, usage_ledger_path,
            )

            ledger = UsageLedger(
                usage_ledger_path(usage_dir, "gateway-bench"),
                source="gateway-bench")
            server2 = make_gateway(
                fleet, config=gwcfg, metrics=GatewayMetrics(), port=0,
                admission=TenantAdmission(),  # no limits: pure accounting
                usage=ledger, flight=FlightRecorder(),
            )
            threading.Thread(target=server2.serve_forever,
                             daemon=True).start()
            try:
                m_port = server2.server_address[1]
                drive(m_port, [], 4, bearer="warm-tenant")
                metered = closed_loop(m_port, bearer_prefix="bench-tenant")
            finally:
                server2.shutdown()
                server2.server_close()
                ledger.close()
    finally:
        server.shutdown()
        server.server_close()
        fleet.stop_all(drain=False)
    hits, misses = p1["hits"] - p0["hits"], p1["misses"] - p0["misses"]
    gw_rps = total / gw_dt
    d_p50, d_p95 = _percentile(direct_lats, 0.50), _percentile(direct_lats,
                                                               0.95)
    g_p50, g_p95 = _percentile(gw_lats, 0.50), _percentile(gw_lats, 0.95)
    row = {
        **_record_meta(),
        "value": round(gw_rps, 1),
        "gateway_overhead": {
            "schema": 1,
            "pooled": fleet.pool.max_idle_per_replica > 0,
            "gateway_rps": round(gw_rps, 1),
            "gateway_added_p50_s": round(g_p50 - d_p50, 6),
            "gateway_added_p95_s": round(g_p95 - d_p95, 6),
            "pool_hit_ratio": (
                round(hits / (hits + misses), 4) if hits + misses else 0.0
            ),
            "upstream_connects": connects,
        },
    }
    if metered is not None:
        from ditl_tpu.telemetry.usage import load_usage, rollup

        m_dt, _ = metered
        m_rps = total / m_dt
        ledger_rows = load_usage(usage_dir)
        row["usage_metering"] = {
            "schema": 1,
            "gateway_rps_metered": round(m_rps, 1),
            # Fractional rps cost of arming the ledger vs the unmetered
            # gateway leg on the same fleet (negative = noise in the
            # metered leg's favor).
            "metering_overhead_ratio": round(1.0 - m_rps / gw_rps, 4),
            "ledger_rows": len(ledger_rows),
            "tenants": len(rollup(ledger_rows)),
        }
    return row
