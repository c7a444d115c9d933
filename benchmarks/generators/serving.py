"""What serving generators share: starting the server behind the
benchmark's launcher, a streaming client on one thread, warm-up, the
counters, the traced window, and the run record.

The client is a user's client: HTTP and SSE over a socket, from another
process than the one that holds the chip. One asyncio loop drives every
request, so the load comes from one thread and the instants are read on one
clock (``time.monotonic``).
"""

from __future__ import annotations

import asyncio
import glob
import json
import os
import socket
import statistics
import time

import numpy as np
import tokenizer
from harness import BenchFailure, Child, device_block, log, model_override_args

now = time.monotonic


# --------------------------------------------------------------------------
# Traffic arithmetic (pure; the tests drive these)
# --------------------------------------------------------------------------


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from a traffic file's length block: ``lognormal``
    (median, sigma) or ``uniform``, clipped to [min, max]."""
    u = rng.uniform(size=n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(min(max(p, 1e-12), 1 - 1e-12))
                      for p in u])
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(int)


def draw_ids(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    """``n`` token ids that are no special token, so the text built from
    them encodes back to exactly them."""
    ids = rng.integers(3, vocab, n)
    for special in tokenizer.SPECIALS:
        ids[ids == special] = 3
    return ids


def scaled(spec: dict, scale: float) -> dict:
    """A length block with its token counts multiplied (rehearsal)."""
    out = dict(spec)
    for k in ("median", "min", "max"):
        if k in out:
            out[k] = max(2, int(out[k] * scale))
    return out


# --------------------------------------------------------------------------
# The server child
# --------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def server_args(ctx) -> list[str]:
    args = list(ctx.traffic["server_args"])
    if ctx.rehearsal:
        repl = ctx.rehearsal["server_args"]
        for i in range(0, len(args) - 1):
            if args[i] in repl:
                args[i + 1] = str(repl[args[i]])
    return args


def start_server(ctx):
    """(child, port, vocab, model overrides). Returns once /health is ok."""
    config = ctx.config
    overrides = model_override_args(config, "serve")
    if ctx.rehearsal:
        # the shared tiny size, then what this family needs beside it
        overrides += ctx.rehearsal["serve_overrides"] + config.get("rehearsal_overrides", [])
    vocab = config["vocab_size"]
    for o in overrides:
        if o.startswith("vocab_size="):
            vocab = int(o.split("=", 1)[1])
    tok_dir = tokenizer.ensure(os.path.join(ctx.out_dir, "tokenizers"), vocab)
    port = free_port()
    argv = ["--preset", config["preset"], "--host", "127.0.0.1",
            "--port", str(port), "--tokenizer", os.path.abspath(tok_dir)]
    argv += server_args(ctx)
    for o in overrides:
        argv += ["--override", o]
    if ctx.trace:
        argv += ["--trace-dir", os.path.join(ctx.run_dir, "spans")]
    child = Child(role="serve", run_dir=ctx.run_dir, workload=ctx.workload,
                  chips=ctx.chips, config_path=ctx.config_path,
                  spec={"role": "serve", "model_overrides": overrides,
                        "rehearsal": bool(ctx.rehearsal)},
                  argv=argv, allow_cpu=bool(ctx.rehearsal))
    return child, port, vocab, overrides


# --------------------------------------------------------------------------
# The client
# --------------------------------------------------------------------------


def request_bytes(method: str, path: str, body: bytes) -> bytes:
    return (f"{method} {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            f"Content-Type: application/json\r\nContent-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n").encode() + body


async def http_json(port: int, method: str, path: str, body: dict | None = None,
                    timeout: float = 30.0) -> tuple[int, dict]:
    async def go():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            payload = json.dumps(body).encode() if body is not None else b""
            writer.write(request_bytes(method, path, payload))
            await writer.drain()
            raw = await reader.read()
        finally:
            writer.close()
        head, _, rest = raw.partition(b"\r\n\r\n")
        return int(head.split()[1]), json.loads(rest or b"{}")

    return await asyncio.wait_for(go(), timeout)


def new_record(prompt_tokens: int, max_tokens: int, due: float | None, **kw) -> dict:
    return {"due": due, "sent": None, "first": None, "last": None, "done": None,
            "n_first": 0, "n_out": 0, "events": [], "status": None, "finished": False,
            "finish_reason": None, "error": None, "prompt_tokens": prompt_tokens,
            "max_tokens": max_tokens, **kw}


async def complete(port: int, prompt: str, rec: dict) -> dict:
    """One greedy streamed /v1/completions request; fills ``rec`` with the
    instant and token count (pieces) of every event that carried tokens."""
    body = json.dumps({"prompt": prompt, "max_tokens": rec["max_tokens"],
                       "temperature": 0, "stream": True}).encode()
    rec["sent"] = now()
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(request_bytes("POST", "/v1/completions", body))
        await writer.drain()
        status_line = await reader.readline()
        rec["status"] = int(status_line.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if rec["status"] != 200:
            rec["error"] = (await reader.read())[:200].decode("utf-8", "replace")
            return rec
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data:"):
                continue
            t = now()
            data = line[5:].strip()
            if data == b"[DONE]":
                rec["finished"] = True
                break
            choice = json.loads(data)["choices"][0]
            rec["finish_reason"] = choice.get("finish_reason") or rec["finish_reason"]
            n = tokenizer.count_tokens(choice.get("text") or "")
            if n:
                if rec["first"] is None:
                    rec["first"], rec["n_first"] = t, n
                rec["last"] = t
                rec["n_out"] += n
                rec["events"].append((t, n))
    except (OSError, ValueError, IndexError, asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()
        rec["done"] = now()
    return rec


def answered(rec: dict) -> bool:
    return rec["status"] == 200 and rec["finished"] and rec["error"] is None


def request_ok(rec: dict) -> bool:
    """A 200 that streamed to its [DONE] with at least one token and no more
    than asked for."""
    return answered(rec) and 1 <= rec["n_out"] <= rec["max_tokens"]


def empty_stop(rec: dict) -> bool:
    """An answer of no token whose last event says ``stop``: with random
    weights the greedy first token is the end-of-text id about once in 1,500
    requests (seen once in 1,700 on the chip), and the server then rightly
    ends at once. ``judge`` lets a window hold a fixed few of these."""
    return answered(rec) and rec["n_out"] == 0 and rec["finish_reason"] == "stop"


EMPTY_STOP_SHARE = 0.01


def judge(counted: list[dict]) -> list[dict]:
    """Sets each counted request's ``ok`` and returns the failed ones. Empty
    stops are correct answers only while they are at most a hundredth of the
    window's requests; beyond that every one of them is a failure (and is
    charged the window's length by ``ttft_client_p95_ms``, as any failure is), so a
    change that ends streams early cannot pass for a faster one."""
    empties = [r for r in counted if empty_stop(r)]
    allow = len(empties) <= EMPTY_STOP_SHARE * len(counted)
    for r in counted:
        r["ok"] = request_ok(r) or (allow and empty_stop(r))
    return [r for r in counted if not r["ok"]]


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------


async def wait_health(child: Child, port: int, timeout_s: float) -> None:
    deadline = now() + timeout_s
    while True:
        child.check_alive("before answering /health")
        if now() > deadline:
            raise BenchFailure(f"server: /health not ok within {timeout_s:.0f}s")
        try:
            status, health = await http_json(port, "GET", "/health", timeout=5)
            if status == 200 and health.get("status") == "ok":
                return
        except (OSError, ValueError, IndexError, asyncio.TimeoutError):
            pass
        await asyncio.sleep(0.1)


async def warm_up(ctx, port: int, vocab: int) -> int:
    """One request for each shape the traffic file's ``warmup`` lists: a
    shared prefix of ``prefix_tokens`` (0: none) followed by each of
    ``suffix_tokens``, sent one after the other so that a later one finds
    the earlier one's pages. Repeated while the compile cache still grows
    (at most three rounds). Returns the requests sent."""
    rng = np.random.default_rng(10_000_019 + ctx.seed)
    scale = ctx.rehearsal["length_scale"] if ctx.rehearsal else 1.0
    sent = 0
    for round_no in range(3):
        before = ctx.cache_entries()
        for shape in ctx.traffic["warmup"]:
            n_prefix = int(shape["prefix_tokens"] * scale)
            prefix = draw_ids(rng, n_prefix, vocab)
            for n_suffix in shape["suffix_tokens"]:
                ids = np.concatenate(
                    [prefix, draw_ids(rng, max(1, int(n_suffix * scale)), vocab)])
                rec = new_record(len(ids) + 1, max(2, int(shape["max_tokens"] * scale)), None)
                await complete(port, tokenizer.text_of(ids), rec)
                sent += 1
                if not (request_ok(rec) or empty_stop(rec)):  # it compiled either way
                    raise BenchFailure(f"warm-up request failed: {rec}")
        grew = ctx.cache_entries() - before
        log(f"warm-up round {round_no + 1}: {sent} requests so far, "
            f"{grew} new programs in the compile cache")
        if grew == 0:
            break
    return sent


async def poll_stats(port: int, every_s: float, out: list) -> None:
    while True:
        try:
            _, stats = await http_json(port, "GET", "/v1/stats", timeout=5)
            out.append({"t": now(), "slots_busy": stats.get("slots_busy"),
                        "queue_depth": stats.get("queue_depth"),
                        "pages_free": stats.get("pages_free"),
                        "pages_cached": stats.get("pages_cached_evictable")})
        except (OSError, ValueError, IndexError, asyncio.TimeoutError):
            pass
        await asyncio.sleep(every_s)


async def counters(port: int) -> dict:
    _, stats = await http_json(port, "GET", "/v1/stats", timeout=30)
    pc = stats.get("prefix_cache", {})
    return {"hit_tokens": pc.get("hit_tokens", 0), "miss_tokens": pc.get("miss_tokens", 0),
            "evictions": pc.get("evictions", 0),
            "preemptions": stats.get("preemptions", 0),
            "pages_total": stats.get("pages_total"), "n_slots": stats.get("n_slots")}


async def traced_window(ctx, child: Child, t0: float) -> dict:
    """In a traced run: start the device trace ``seconds`` before the window
    closes and stop it as it closes (later by what the start took), from the
    launcher's thread in the process that holds the chip (``serve()`` has no
    switch for a device trace). Writing the capture stalls the server for a
    second or so: at the window's end that falls on the post-roll, which
    nobody counts, and not on a twentieth of the window's requests (PERF.md
    section 6, PR 36)."""
    loop = asyncio.get_running_loop()
    trace_dir = os.path.join(ctx.run_dir, "trace")
    length = min(ctx.traffic["trace"]["seconds"], ctx.seconds / 3)
    await asyncio.sleep(max(0.0, t0 + ctx.seconds - length - now()))
    start = await loop.run_in_executor(
        None, lambda: child.command("trace_start", dir=trace_dir))
    await asyncio.sleep(length)
    stop = await loop.run_in_executor(
        None, lambda: child.command("trace_stop", timeout_s=300))
    return {"start": start, "stop": stop}


def read_spans(run_dir: str) -> list[dict]:
    """The server's ``--trace-dir`` span records: name, wall start, seconds."""
    spans = []
    for path in glob.glob(os.path.join(run_dir, "spans", "events-server-*.jsonl*")):
        with open(path) as f:
            for ln in f:
                try:
                    rec = json.loads(ln)
                except ValueError:
                    continue
                if rec.get("event") == "trace.span":
                    spans.append({"name": rec["name"], "t0": rec["ts"],
                                  "dur_s": rec["dur_s"]})
    return spans


def run_serving(ctx, drive) -> dict:
    """Start the server, warm it up, let ``drive`` offer the load, collect
    the run record. ``drive(ctx, port, vocab, open_window, read_counters,
    state)`` is the generator's coroutine: it calls ``open_window()`` at the
    first instant of the measured window, leaves the server's counters at
    both ends of the window in ``state["counters0"]`` / ``["counters1"]`` and
    the compile-cache count at its end in ``state["cache1"]``,
    and returns (request records, extra keys of the run record)."""
    child, port, vocab, overrides = start_server(ctx)
    state: dict = {}

    async def main():
        await wait_health(child, port, ctx.setup_timeout_s)
        state["health_s"] = now() - ctx.t_start
        state["warmup_requests"] = await warm_up(ctx, port, vocab)
        tasks = []

        def open_window() -> float:
            t0 = now()
            state.update(t0=t0, wall0=time.time(), cache0=ctx.cache_entries())
            if ctx.trace:
                state["polls"] = []
                tasks.append(asyncio.ensure_future(poll_stats(port, 0.5, state["polls"])))
                tasks.append(asyncio.ensure_future(traced_window(ctx, child, t0)))
            return t0

        requests, extra = await drive(ctx, port, vocab, open_window,
                                      lambda: counters(port), state)
        if ctx.trace:
            tasks[0].cancel()
            state["trace_cmds"] = await tasks[1]
        return requests, extra

    try:
        requests, extra = asyncio.run(main())
        reference = child.wait_file("reference.json", 5, "reference check")
        device = device_block(child)
    finally:
        child.stop(term_timeout_s=3.0)

    reduced = None
    spans = read_spans(ctx.run_dir) if ctx.trace else []
    if ctx.trace:
        found = glob.glob(os.path.join(ctx.run_dir, "trace", "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            raise BenchFailure("the traced run left no .xplane.pb")
        reduced = ctx.reduce_trace(found[0], device, spans)
    counted = [r for r in requests if r["counted"]]
    failed = judge(counted)
    if failed:
        log(f"{len(failed)} failed requests, first: {failed[0]}")
    return {
        "kind": "serve",
        "correct": bool(counted and not failed and reference.get("ok")),
        "attempted": len(counted),
        "failed": len(failed),
        "setup_s": state["t0"] - ctx.t_start,
        "window_s": float(ctx.seconds),
        "window_t0": state["t0"],  # on the requests' clock: spread.py cuts by it
        "window_wall": [state["wall0"], state["wall0"] + ctx.seconds],
        "chips": ctx.chips,
        "requests": counted,
        "reference": reference,
        "compiles_in_window": state["cache1"] - state["cache0"],
        "counters": [state["counters0"], state["counters1"]],
        "polls": [p for p in state.get("polls", [])
                  if state["t0"] <= p["t"] <= state["t0"] + ctx.seconds],
        "device": device,
        "trace": reduced,
        "host_spans": spans,
        "health_s": state["health_s"],
        "warmup_requests": state["warmup_requests"],
        "model_overrides": overrides,
        **extra,
    }
