#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the main path once at the full published width and depth of
``qwen2-0.5b`` (random weights from a seed), through the entry points a user
calls, each as its own OS process, one after the other:

1. **kernels** — every Pallas kernel on that path, compiled by Mosaic
   (``interpret=False``) at this model's shapes, against the XLA reference
   already in the tree: flash attention forward and gradients (with and
   without packed-sequence segment ids), and paged attention in its three
   forms (plain, tail, tail over int8 pools).
2. **trainer** — ``python -m ditl_tpu.launch --preset qwen2-0.5b`` with the
   flash kernel and the fused loss, eight optimizer steps at 2,048 tokens:
   the loss is finite and lower at the last step than at the first.
3. **server** — ``python -m ditl_tpu.infer.server --preset qwen2-0.5b
   --engine continuous --cache-mode paged``: requests of different prompt
   lengths, two in flight at once, one streamed, one prompt repeated; every
   answer is a 200 with ``completion_tokens == max_tokens``, the repeat
   returns the same text and hits the prefix cache; SIGTERM drains to exit 0.

On a four-chip host (``jax.device_count() == 4``) the same trainer runs with
``mesh.fsdp=4`` and the same server with ``--mesh tensor=2`` (two kv heads
allow no more), and the smoke also requires that the train state and the
serving parameters are spread over four distinct devices — read from the
arrays' own shardings and from each device's ``memory_stats()``, not from
the mesh log line.

This parent process never initialises a JAX backend: a chip belongs to one
process at a time, so it only starts children, strictly in sequence, and
reads the device from THEIR output (the kernel child's report, the trainer's
summary JSON, the server's /v1/stats). Children get ``JAX_PLATFORMS=tpu``
whatever was inherited, so a machine where libtpu cannot start fails instead
of quietly giving a CPU. Any failed phase makes the exit code non-zero and no
result line is printed; there is no CPU mode. The times printed are
observations, not metrics.

The last line of stdout on success is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import contextlib
import functools
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PRESET = "qwen2-0.5b"
SEQ_LEN = 2048
TRAIN_STEPS = 8
# Serving shapes the kernel child checks and the server child then runs:
# the server's defaults (8 slots, 256-token pages, 16-token decode chunk)
# under a 2,048-token context cap, i.e. 8 pages per slot.
SLOTS, PAGE_SIZE, DECODE_CHUNK, MAX_CONTEXT = 8, 256, 16, 2048

# Kernel-vs-reference tolerance, as max|kernel - ref| / max|ref| per tensor.
# The reference is float32 math at "highest" matmul precision on the same
# values. bfloat16 keeps 8 significant bits (one rounding is 2^-8 ~ 0.4%
# relative); the kernels round the probabilities to bf16 before the p.v
# matmul and the result to bf16, and Mosaic's default-precision f32 matmuls
# pass through bf16 on the MXU, so a correct kernel lands within a few
# roundings of the tensor's scale: under 1% (0.5% was the most the first
# chip run saw). A wrong mask, head mapping, page index or scale is an error
# of order 1. 3% separates the two with room on both sides.
TOL = 3e-2


class SmokeFailure(Exception):
    """A phase did not do what it must; the message says which and why."""


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Child 1 (the only code here that touches jax): kernels vs references
# ---------------------------------------------------------------------------


def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, dtype=np.float32)
    ref = np.asarray(ref, dtype=np.float32)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-30))


def _flash_case(with_segments: bool, heads) -> dict:
    """Flash attention forward + (dq, dk, dv) against ``_xla_attention``, in
    the trainer's compute dtype (bfloat16)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ditl_tpu.ops.attention import _xla_attention
    from ditl_tpu.ops.flash_attention import flash_attention

    h, kv, d = heads
    b, s = 2, SEQ_LEN
    dtype = jnp.bfloat16
    kq, kk, kvv, kw = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kvv, (b, s, kv, d), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (b, s, h, d), jnp.float32)  # cotangent
    seg = None
    if with_segments:
        # Packed documents of uneven length, boundaries off every tile edge,
        # different per row — what data/loader.py's packing produces.
        bounds = [[300, 1000, 1500], [17, 513, 2047]]
        seg = jnp.asarray(np.stack([
            np.searchsorted(np.asarray(bb), np.arange(s), side="right") + 1
            for bb in bounds
        ]).astype(np.int32))

    def out_and_grads(attn, q_, k_, v_):
        out, vjp = jax.vjp(
            lambda a, b_, c: attn(a, b_, c, causal=True, segment_ids=seg),
            q_, k_, v_)
        return (out,) + vjp(w.astype(out.dtype))

    kernel = functools.partial(flash_attention, interpret=False)
    t0 = time.perf_counter()
    got = jax.block_until_ready(
        jax.jit(functools.partial(out_and_grads, kernel))(q, k, v))
    wall = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = jax.block_until_ready(
            jax.jit(functools.partial(out_and_grads, _xla_attention))(
                *(x.astype(jnp.float32) for x in (q, k, v))))
    errs = {n: _rel_err(g, r)
            for n, g, r in zip(("out", "dq", "dk", "dv"), got, ref)}
    return {"errs": errs, "compile_and_run_s": round(wall, 2), "tol": TOL}


def _paged_case(form: str, heads) -> dict:
    """Paged decode attention (``plain`` | ``tail`` | ``tail_int8`` |
    ``tail_ragged``: rows of 1 to 8 pages, odd and even counts, so that a
    walk of several pages a step meets whole and ragged last groups)
    against ``paged_attention_xla``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ditl_tpu.ops.paged_attention import paged_attention, paged_attention_xla

    h, kv, d = heads
    b, ps, t = SLOTS, PAGE_SIZE, DECODE_CHUNK
    maxp = MAX_CONTEXT // ps
    n_pool = b * maxp + 1  # page 0 is the engine's sentinel
    keys = jax.random.split(jax.random.key(2), 8)
    f32, bf16 = jnp.float32, jnp.bfloat16
    q = jax.random.normal(keys[0], (b, h, d), f32).astype(bf16)
    rng = np.random.default_rng(0)
    table = (rng.permutation(n_pool - 1)[: b * maxp] + 1).reshape(b, maxp)
    table = jnp.asarray(table.astype(np.int32))
    kw: dict = {}
    ref_kw: dict = {}
    if form == "plain":
        # A dead slot, both sides of a page edge, a full context.
        lengths = [0, 1, 255, 256, 257, 1000, 2047, 2048]
    else:
        # starts = tokens already flushed into pages (any value: prompts end
        # anywhere); [starts, lengths) sit in the tail. A dead slot, an
        # empty tail, a full tail, a tail that ends the context.
        starts = [0, 0, 5, 256, 300, 1000, 2000, 2032]
        if form == "tail_ragged":
            starts = [0, 256, 300, 700, 1024, 1100, 1700, 2032]
        lengths = [s + n for s, n in zip(starts, [0, 3, 16, 0, 16, 7, 16, 16])]
        tk = jax.random.normal(keys[1], (b, kv, t, d), f32).astype(bf16)
        tv = jax.random.normal(keys[2], (b, kv, t, d), f32).astype(bf16)
        st = jnp.asarray(starts, jnp.int32)
        kw.update(tail_k=tk, tail_v=tv, starts=st)
        ref_kw.update(tail_k=tk.astype(f32), tail_v=tv.astype(f32), starts=st)
    lengths = jnp.asarray(lengths, jnp.int32)
    if form == "tail_int8":
        kp = jax.random.randint(keys[3], (n_pool, kv, ps, d), -127, 128, jnp.int8)
        vp = jax.random.randint(keys[4], (n_pool, kv, ps, d), -127, 128, jnp.int8)
        # Per-position scales that put the dequantized values near unit scale.
        ks = jax.random.uniform(keys[5], (n_pool, kv, 1, ps), f32, 0.005, 0.02)
        vs = jax.random.uniform(keys[6], (n_pool, kv, 1, ps), f32, 0.005, 0.02)
        kw.update(k_scale=ks, v_scale=vs)
        ref_kw.update(k_scale=ks, v_scale=vs)
        ref_pools = (kp, vp)
    else:
        kp = jax.random.normal(keys[3], (n_pool, kv, ps, d), f32).astype(bf16)
        vp = jax.random.normal(keys[4], (n_pool, kv, ps, d), f32).astype(bf16)
        ref_pools = (kp.astype(f32), vp.astype(f32))

    kernel = jax.jit(lambda *a, **k: paged_attention(*a, **k, interpret=False))
    t0 = time.perf_counter()
    got = jax.block_until_ready(kernel(q, kp, vp, table, lengths, **kw))
    wall = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = jax.block_until_ready(jax.jit(paged_attention_xla)(
            q.astype(f32), *ref_pools, table, lengths, **ref_kw))
    errs = {"out": _rel_err(got, ref)}
    dead = np.asarray(got, dtype=np.float32)[np.asarray(lengths) == 0]
    if dead.size and np.any(dead != 0):
        errs["dead_slot_nonzero"] = float("inf")
    return {"errs": errs, "compile_and_run_s": round(wall, 2), "tol": TOL}


def kernel_check() -> int:
    """Runs in the first child. Exit 3 = no TPU could be initialised (before
    compiling anything); 1 = a kernel was refused or disagreed."""
    import jax

    try:
        platform = jax.devices()[0].platform
    except RuntimeError as e:
        log(f"no TPU could be initialised: {e}")
        return 3
    if platform != "tpu":
        log(f"no TPU could be initialised: jax landed on {platform!r}")
        return 3

    from ditl_tpu.models.presets import get_preset
    from ditl_tpu.runtime.distributed import device_summary, enable_compile_cache

    enable_compile_cache()
    cfg = get_preset(PRESET)
    heads = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    cases = [
        ("flash fwd+grad", _flash_case, (False, heads)),
        ("flash fwd+grad segment_ids", _flash_case, (True, heads)),
        ("paged plain", _paged_case, ("plain", heads)),
        ("paged tail", _paged_case, ("tail", heads)),
        # 4 kv heads of 128: two pages a step (``pages_a_step``), as the 7B and
        # Trinity-Mini cells' pools give it
        ("paged tail 4 kv heads, ragged groups", _paged_case, ("tail_ragged", (32, 4, 128))),
        ("paged tail int8", _paged_case, ("tail_int8", heads)),
    ]
    checks = []
    for name, fn, args in cases:
        # Every case runs even after a failure, so one chip call shows every
        # kernel the compiler refuses; any failure still fails the phase.
        try:
            row = fn(*args)
            row["ok"] = all(e <= row["tol"] for e in row["errs"].values())
        except Exception as e:  # noqa: BLE001 - the compiler's words are the finding
            row = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            log(f"kernel case {name!r} raised:\n{row['error']}")
        row["name"] = name
        checks.append(row)
        log(f"kernel {name}: {'ok' if row['ok'] else 'FAILED'} "
            f"{ {k: v for k, v in row.items() if k in ('errs', 'tol', 'compile_and_run_s')} }")
    print(json.dumps({"device": device_summary(), "checks": checks}))
    return 0 if all(c["ok"] for c in checks) else 1


# ---------------------------------------------------------------------------
# The parent: stdlib only
# ---------------------------------------------------------------------------

_children: list[subprocess.Popen] = []


def child_env() -> dict:
    env = dict(os.environ)
    # Whatever was inherited (this sandbox exports JAX_PLATFORMS=cpu; the
    # chip's machine may too): the children run on the TPU or fail.
    env["JAX_PLATFORMS"] = "tpu"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def spawn(argv: list[str], **kw) -> subprocess.Popen:
    # Its own session, so that stop() reaches everything the child started.
    proc = subprocess.Popen(argv, env=child_env(), cwd=ROOT,
                            start_new_session=True, **kw)
    _children.append(proc)
    return proc


def stop(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def run_to_end(name: str, argv: list[str], timeout_s: float) -> tuple[str, float]:
    """Run one child to its exit; returns (stdout, wall seconds). The
    child's stderr passes through to ours."""
    log(f"[{name}] $ {' '.join(argv)}")
    t0 = time.monotonic()
    proc = spawn(argv, stdout=subprocess.PIPE, text=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:  # main() stops it
        raise SmokeFailure(f"{name}: no exit within {timeout_s:.0f}s") from None
    wall = time.monotonic() - t0
    if proc.returncode != 0:
        raise SmokeFailure(f"{name}: exit code {proc.returncode}")
    return out, wall


def last_json_line(name: str, out: str) -> dict:
    lines = [ln for ln in out.strip().splitlines() if ln.strip()]
    try:
        return json.loads(lines[-1])
    except (IndexError, ValueError):
        raise SmokeFailure(f"{name}: last stdout line is not JSON: "
                           f"{lines[-1][:200] if lines else '(empty)'}") from None


def check_device(name: str, dev: dict, expect: dict | None) -> dict:
    """``dev`` is runtime/distributed.device_summary() as a child printed it."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)

    if dev.get("platform") != "tpu":
        raise SmokeFailure(f"{name}: ran on platform {dev.get('platform')!r}, not tpu")
    kind = str(dev.get("device_kind", "")).lower().strip()
    if not isinstance(peaks.get(kind), dict):
        raise SmokeFailure(f"{name}: device_kind {dev.get('device_kind')!r} is "
                           "not in benchmarks/peaks.json")
    if expect is not None and dev != expect:
        raise SmokeFailure(f"{name}: device {dev} differs from the first child's {expect}")
    return dev


def phase_kernels() -> dict:
    out, wall = run_to_end(
        "kernels", [sys.executable, os.path.abspath(__file__), "--kernel-check"], 600)
    report = last_json_line("kernels", out)
    dev = check_device("kernels", report["device"], None)
    log(f"[kernels] {len(report['checks'])} kernel cases agree with their "
        f"XLA references; child wall {wall:.1f}s")
    return dev


def check_spread(name: str, what: str, per_device: dict, n: int,
                 total: float | None = None) -> None:
    """``per_device`` maps device -> bytes. All ``n`` devices hold a
    comparable share (the smallest at least half the largest) and, where
    ``total`` is given, none holds most of it: spread, not parked on the
    first chip and not merely replicated."""
    vals = [float(v) for v in per_device.values()]
    if len(vals) != n or min(vals, default=0) <= 0 or min(vals) < 0.5 * max(vals):
        raise SmokeFailure(f"{name}: {what} is not spread over {n} devices: {per_device}")
    if total is not None and max(vals) > 0.6 * total:
        raise SmokeFailure(f"{name}: one device holds {max(vals):.0f} of {total:.0f} "
                           f"bytes of {what}: replicated or parked, not sharded")
    log(f"[{name}] {what} by device: {per_device}")


def phase_trainer(dev: dict, workdir: str) -> None:
    metrics_file = os.path.join(workdir, "train_metrics.jsonl")
    n = dev["device_count"]
    argv = [
        sys.executable, "-m", "ditl_tpu.launch", "--preset", PRESET,
        "model.attention_impl=flash", "model.loss_impl=fused",
        "data.synthetic=true", f"data.seq_len={SEQ_LEN}", "data.batch_size=4",
        f"train.total_steps={TRAIN_STEPS}", "train.log_every=1",
        f"train.metrics_file={metrics_file}",
    ] + ([f"mesh.fsdp={n}"] if n > 1 else [])
    out, wall = run_to_end("trainer", argv, 900)
    summary = last_json_line("trainer", out)
    check_device("trainer", summary["device"], dev)
    if n > 1:
        sp = summary["state_placement"]
        check_spread("trainer", "params + optimizer state (from shardings)",
                     sp["per_device_bytes"], n, total=sp["bytes"])
        check_spread("trainer", "bytes_in_use (memory_stats)",
                     {k: v["bytes_in_use"] for k, v in summary["memory"].items()}, n)
    with open(metrics_file) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    losses = [r["loss"] for r in rows]
    if summary.get("steps") != TRAIN_STEPS or len(losses) != TRAIN_STEPS:
        raise SmokeFailure(f"trainer: took {summary.get('steps')} steps "
                           f"({len(losses)} metric rows), wanted {TRAIN_STEPS}")
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise SmokeFailure(f"trainer: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"trainer: loss did not fall: {losses}")
    gp = summary.get("goodput", {})
    log(f"[trainer] {TRAIN_STEPS} steps, loss {losses[0]:.4f} -> {losses[-1]:.4f} "
        f"({' '.join(f'{x:.3f}' for x in losses)})")
    log(f"[trainer] observations: child wall {wall:.1f}s, startup "
        f"{gp.get('startup_s')}s, first window (compile + first step) "
        f"{gp.get('compile_s')}s, warm step "
        f"{summary.get('step_anatomy', {}).get('per_step_ms', {}).get('wall')} ms, "
        f"params {summary.get('params_m', 0):.1f}M, native dataprep loaded: "
        f"{summary.get('native_dataprep')}")


def device_memory_in_use(port: int) -> dict:
    """Per-device ``bytes_in_use`` from the server's /metrics (the
    ``ditl_memory_device<i>_bytes_in_use`` gauges, sampled from each
    device's memory_stats() at scrape time)."""
    with request(port, "GET", "/metrics", timeout=60) as resp:
        text = resp.read().decode("utf-8")
    out = {}
    for line in text.splitlines():
        name, _, value = line.partition(" ")
        if name.startswith("ditl_memory_device") and name.endswith("_bytes_in_use") \
                and "peak" not in name:
            out[name[len("ditl_memory_device"):-len("_bytes_in_use")]] = float(value)
    return out


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@contextlib.contextmanager
def request(port: int, method: str, path: str, body: dict | None = None,
            timeout: float = 600.0):
    """One HTTP exchange with the server child; yields the response."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     body=json.dumps(body) if body is not None else None,
                     headers={"Content-Type": "application/json"})
        yield conn.getresponse()
    finally:
        conn.close()


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 600.0) -> tuple[int, dict]:
    with request(port, method, path, body, timeout) as resp:
        return resp.status, json.loads(resp.read() or b"{}")


def complete(port: int, prompt: str, max_tokens: int, stream: bool = False) -> dict:
    """One greedy /v1/completions request; returns status, text, token
    counts and latency. Streamed answers are reassembled from their SSE
    chunks (the stream carries no usage block, so completion_tokens is
    None there)."""
    body = {"prompt": prompt, "max_tokens": max_tokens, "temperature": 0,
            "stream": stream}
    t0 = time.monotonic()
    if not stream:
        status, payload = http_json(port, "POST", "/v1/completions", body)
        res = {"status": status, "latency_s": time.monotonic() - t0}
        if status == 200:
            res["text"] = payload["choices"][0]["text"]
            res["finish_reason"] = payload["choices"][0]["finish_reason"]
            res["completion_tokens"] = payload["usage"]["completion_tokens"]
            res["prompt_tokens"] = payload["usage"]["prompt_tokens"]
        return res
    with request(port, "POST", "/v1/completions", body) as resp:
        res = {"status": resp.status, "text": "", "done": False,
               "finish_reason": None, "completion_tokens": None}
        for raw in resp:
            line = raw.decode("utf-8").strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                res["done"] = True
                break
            choice = json.loads(data)["choices"][0]
            res["text"] += choice.get("text") or ""
            res["finish_reason"] = choice.get("finish_reason") or res["finish_reason"]
        res["latency_s"] = time.monotonic() - t0
        return res


def _prompt(n_bytes: int, salt: str) -> str:
    """A deterministic prompt of exactly ``n_bytes`` bytes (= tokens under
    the byte tokenizer)."""
    text = f"[{salt}] " + "the quick brown fox jumps over the lazy dog. " * (n_bytes // 40 + 1)
    return text[:n_bytes]


def phase_server(dev: dict) -> None:
    port = free_port()
    n = dev["device_count"]
    argv = [
        sys.executable, "-m", "ditl_tpu.infer.server", "--preset", PRESET,
        "--engine", "continuous", "--cache-mode", "paged",
        "--host", "127.0.0.1", "--port", str(port),
        "--slots", str(SLOTS), "--page-size", str(PAGE_SIZE),
        "--max-cache-len", str(MAX_CONTEXT),
    ] + (["--mesh", "tensor=2"] if n > 1 else [])
    log(f"[server] $ {' '.join(argv)}")
    t0 = time.monotonic()
    proc = spawn(argv)  # main() stops it whatever happens below
    while True:
        if proc.poll() is not None:
            raise SmokeFailure(f"server: exited with code {proc.returncode} "
                               "before answering /health")
        if time.monotonic() - t0 > 600:
            raise SmokeFailure("server: /health not ok within 600s")
        try:
            status, health = http_json(port, "GET", "/health", timeout=5)
            if status == 200 and health.get("status") == "ok":
                break
        except (OSError, ValueError):
            pass
        time.sleep(0.5)
    cold = time.monotonic() - t0
    log(f"[server] observations: /health ok after {cold:.1f}s "
        f"(its own cold_start_s: {health.get('cold_start_s')})")

    # Prompt lengths in tokens: under one page; over two full pages (so
    # the repeat can hit them); most of the context.
    short, shared, long_ = _prompt(40, "a"), _prompt(600, "b"), _prompt(1500, "c")
    results: dict[str, dict] = {}

    def go(key, *a, **k):
        results[key] = complete(port, *a, **k)

    go("short", short, 16)
    # Two in flight at once, one of them streamed.
    threads = [
        threading.Thread(target=go, args=("shared_first", shared, 24)),
        threading.Thread(target=go, args=("long_streamed", long_, 16),
                         kwargs={"stream": True}),
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=900)
    if any(th.is_alive() for th in threads):
        raise SmokeFailure("server: concurrent requests did not finish in 900s")
    _, before = http_json(port, "GET", "/v1/stats")
    go("shared_repeat", shared, 24)
    _, stats = http_json(port, "GET", "/v1/stats")

    wanted = {"short": 16, "shared_first": 24, "long_streamed": 16,
              "shared_repeat": 24}
    for key, want in wanted.items():
        r = results.get(key) or {}
        log(f"[server] {key}: status {r.get('status')}, "
            f"{r.get('prompt_tokens', '-')} prompt + "
            f"{r.get('completion_tokens')} completion tokens, finish "
            f"{r.get('finish_reason')!r}, {r.get('latency_s', 0):.2f}s, "
            f"text {r.get('text')!r}")
        if r.get("status") != 200:
            raise SmokeFailure(f"server: {key} answered {r.get('status')}")
        if r.get("finish_reason") != "length":
            raise SmokeFailure(f"server: {key} finished {r.get('finish_reason')!r}, "
                               "not 'length' (max_tokens not reached)")
        if key == "long_streamed":
            if not r["done"]:
                raise SmokeFailure("server: stream ended without [DONE]")
        elif r["completion_tokens"] != want:
            raise SmokeFailure(f"server: {key} returned {r['completion_tokens']} "
                               f"completion tokens, wanted {want}")
    if results["shared_repeat"]["text"] != results["shared_first"]["text"]:
        raise SmokeFailure("server: the repeated greedy prompt returned different text")
    check_device("server", stats.get("device") or {}, dev)
    if stats.get("engine") != "continuous" or stats.get("cache_mode") != "paged":
        raise SmokeFailure(f"server: engine {stats.get('engine')!r} / cache_mode "
                           f"{stats.get('cache_mode')!r}, wanted continuous / paged")
    hits = (stats["prefix_cache"]["hit_tokens"]
            - before["prefix_cache"]["hit_tokens"])
    full_pages = (600 // PAGE_SIZE) * PAGE_SIZE
    if hits < full_pages:
        raise SmokeFailure(f"server: the repeat hit {hits} prefix-cache tokens, "
                           f"wanted its {full_pages} full-page tokens")
    log(f"[server] the repeat hit {hits} prefix-cache tokens; "
        f"prefix_cache {stats['prefix_cache']}")
    if n > 1:
        pp = stats["param_placement"]
        check_spread("server", "params (from shardings)",
                     pp["per_device_bytes"], n, total=pp["bytes"])
        check_spread("server", "bytes_in_use (memory_stats)",
                     device_memory_in_use(port), n)

    t1 = time.monotonic()
    os.kill(proc.pid, signal.SIGTERM)
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        raise SmokeFailure("server: no exit within 120s of SIGTERM") from None
    if rc != 0:
        raise SmokeFailure(f"server: exit code {rc} after SIGTERM, wanted 0")
    log(f"[server] SIGTERM -> drained and exited 0 in {time.monotonic() - t1:.1f}s")


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "ditl_tpu")):
        log(f"the ditl_tpu package is not next to this script ({ROOT}); "
            "chip_smoke.py drives the repository, it is not a program of its own")
        return 2
    sys.path.insert(0, ROOT)
    t0 = time.monotonic()
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
            dev = phase_kernels()
            if dev["device_count"] not in (1, 4):
                raise SmokeFailure(f"{dev['device_count']} devices: the smoke knows "
                                   "one chip and one four-chip host")
            phase_trainer(dev, workdir)
            phase_server(dev)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    finally:
        for proc in _children:
            stop(proc)
    log(f"all phases passed in {time.monotonic() - t0:.0f}s "
        f"(jax {dev['jax']}, jaxlib {dev['jaxlib']}, libtpu {dev['libtpu']})")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}))
    return 0


if __name__ == "__main__":
    if sys.argv[1:] == ["--kernel-check"]:
        sys.path.insert(0, ROOT)
        sys.exit(kernel_check())
    sys.exit(main())
