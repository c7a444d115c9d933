"""Generator ``stream_sessions``: a closed loop of streams that share nothing.
Evaluation harnesses, synthetic-data and rollout workers of a BASE model: a
fixed pool of concurrent streams against one replica, each sending its next
prompt when its last continuation has ended and a think time has passed. Such
callers hold concurrency, not a rate: the load is the number of sessions.

Set-up (before the window, part of ``setup_s``): ``serving.warm_up`` sends one
request a prefill bucket the prompts reach (the traffic file's ``warmup``),
repeated while the compile cache still grows. ``preroll_s`` before the window
the sessions start, each at an instant drawn uniformly from the first
``session_start_spread_s``.

A turn: a prompt of unique ids from its first token (nothing is shared with
any other turn), a continuation of ``max_tokens`` drawn from the traffic
file, greedy, streamed. Everything a session sends comes from ``(seed,
session index)``: the same seed gives, session by session, the same turns in
the same order; only their interleaving follows the server.

Counted are the turns SENT inside the window. ``due`` of a record is the
instant the turn was sent (a closed loop has no schedule to be late on).
Sessions keep taking turns until every counted turn has ended, so no counted
turn finishes on an emptying server; then the rest is cancelled.
"""

from __future__ import annotations

import asyncio

import numpy as np
import tokenizer
from generators import serving
from generators.serving import now


def sizes(ctx) -> dict:
    """The traffic file's numbers, or the rehearsal's tiny ones."""
    t = ctx.traffic
    if not ctx.rehearsal:
        return {"sessions": t["sessions"], "prompt": t["prompt_tokens"],
                "answer": t["max_tokens"], "preroll_s": t["preroll_s"],
                "spread_s": t["session_start_spread_s"]}
    r = t["rehearsal"]
    scale = ctx.rehearsal["length_scale"]
    return {"sessions": r["sessions"], "prompt": serving.scaled(t["prompt_tokens"], scale),
            "answer": serving.scaled(t["max_tokens"], scale), "preroll_s": r["preroll_s"],
            "spread_s": r["preroll_s"] / 2}


def session_turns(seed: int, session: int, sz: dict, traffic: dict, vocab: int):
    """The endless sequence of one session's turns: (prompt ids, max_tokens,
    think seconds after the continuation). A prompt of ``n`` tokens is the
    server's bos id and ``n - 1`` drawn ids."""
    rng = np.random.default_rng([seed, 0x57E4, session])
    think = traffic["think_s"]
    while True:
        n = int(serving.draw_lengths(rng, sz["prompt"], 1)[0])
        yield (serving.draw_ids(rng, n - 1, vocab),
               int(serving.draw_lengths(rng, sz["answer"], 1)[0]),
               float(min(rng.exponential(think["mean"]), think["max"])))


async def drive(ctx, port, vocab, open_window, read_counters, state):
    sz = sizes(ctx)
    start = now() + 0.05
    t0 = start + sz["preroll_s"]
    t1 = t0 + ctx.seconds
    records: list[dict] = []
    stop = asyncio.Event()
    starts = np.random.default_rng([ctx.seed, 0x57A7]).uniform(
        0.0, sz["spread_s"], sz["sessions"])

    async def session(i: int):
        await asyncio.sleep(max(0.0, start + float(starts[i]) - now()))
        for ids, max_tokens, think in session_turns(ctx.seed, i, sz, ctx.traffic, vocab):
            if stop.is_set():
                return
            sent = now()
            rec = serving.new_record(len(ids) + 1, max_tokens, sent,
                                     counted=bool(t0 <= sent < t1), session=i)
            records.append(rec)
            await serving.complete(port, tokenizer.text_of(ids), rec)
            await asyncio.sleep(think)

    tasks = [asyncio.ensure_future(session(i)) for i in range(sz["sessions"])]
    await asyncio.sleep(max(0.0, t0 - now()))
    state["counters0"] = await read_counters()
    state["window_open_late_s"] = open_window() - t0
    state["t0"] = t0
    await asyncio.sleep(max(0.0, t1 - now()))
    state["counters1"] = await read_counters()
    state["cache1"] = ctx.cache_entries()
    # the counted turns end under the load they were sent under
    deadline = now() + ctx.traffic["drain_s"]
    while now() < deadline and any(r["counted"] and r["done"] is None for r in records):
        await asyncio.sleep(0.05)
    stop.set()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for r in records:
        r["window_s"] = ctx.seconds
    return records, {"loop": "closed", "sessions": sz["sessions"], "turns_sent": len(records)}


def run(ctx) -> dict:
    return serving.run_serving(ctx, drive)
