"""Generator ``open_loop``: independent users. Requests are sent on a
schedule drawn from the seed whether or not earlier ones have finished, and
each is timed from the instant it was DUE, so a stall is charged to every
request it delays.

The traffic file gives the rate, the length distributions and the sharing.
Arrivals are a Poisson process conditioned on its count: exactly
``rate_per_s x span`` instants, uniform over the span, so that every seed
offers the same number of requests. ``preroll_s`` of the same traffic before
the window (part of set-up) brings the batch to its steady occupancy, and
``postroll_s`` after it keeps the load on while the window's last requests
finish; neither is counted.
"""

from __future__ import annotations

import asyncio

import numpy as np
import tokenizer
from generators import serving
from generators.serving import now


def schedule(traffic: dict, seed: int, seconds: float, vocab: int,
             length_scale: float = 1.0) -> list[dict]:
    """The run's requests, in order of their due instant (seconds from the
    window's opening; negative in the pre-roll)."""
    rng = np.random.default_rng(seed)
    pre, post = traffic["preroll_s"], traffic["postroll_s"]
    span = pre + seconds + post
    n = int(round(traffic["rate_per_s"] * span))
    due = np.sort(rng.uniform(-pre, seconds + post, n))
    prompt = serving.draw_lengths(rng, serving.scaled(traffic["prompt_tokens"], length_scale), n)
    out = serving.draw_lengths(rng, serving.scaled(traffic["max_tokens"], length_scale), n)
    reqs = []
    for i in range(n):
        # The server prepends one bos token: a prompt of p tokens is p-1 pieces.
        ids = serving.draw_ids(rng, max(1, int(prompt[i]) - 1), vocab)
        reqs.append({"due_s": float(due[i]), "ids": ids,
                     "prompt_tokens": len(ids) + 1, "max_tokens": int(out[i]),
                     "counted": bool(0.0 <= due[i] < seconds)})
    return reqs


async def drive(ctx, port, vocab, open_window, read_counters, state):
    scale = ctx.rehearsal["length_scale"] if ctx.rehearsal else 1.0
    reqs = schedule(ctx.traffic, ctx.seed, ctx.seconds, vocab, scale)
    texts = [tokenizer.text_of(r["ids"]) for r in reqs]
    pre = ctx.traffic["preroll_s"]
    start = now() + 0.05
    t0 = start + pre
    records, tasks = [], []
    opened = False

    async def close_window():
        state["counters1"] = await read_counters()
        state["cache1"] = ctx.cache_entries()

    for r, text in zip(reqs, texts):
        due = t0 + r["due_s"]
        if not opened and r["due_s"] >= 0:
            await asyncio.sleep(max(0.0, t0 - now()))
            state["counters0"] = await read_counters()
            t0_actual = open_window()
            # The schedule is anchored at t0; the window opens within a
            # millisecond or two of it (reading the counters took that).
            state["window_open_late_s"] = t0_actual - t0
            state["t0"] = t0
            opened = True
        await asyncio.sleep(max(0.0, due - now()))
        if opened and "counters1" not in state and r["due_s"] >= ctx.seconds:
            await close_window()
        rec = serving.new_record(r["prompt_tokens"], r["max_tokens"], due,
                                 counted=r["counted"])
        records.append(rec)
        tasks.append(asyncio.ensure_future(serving.complete(port, text, rec)))
    if "counters1" not in state:
        await asyncio.sleep(max(0.0, t0 + ctx.seconds - now()))
        await close_window()
    # Wait for the counted requests; the post-roll's are cut off.
    counted = [t for t, r in zip(tasks, records) if r["counted"]]
    if counted:
        await asyncio.wait(counted, timeout=ctx.traffic["drain_s"])
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for r in records:
        r["window_s"] = ctx.seconds
    return records, {"loop": "open", "rate_per_s": ctx.traffic["rate_per_s"]}


def run(ctx) -> dict:
    return serving.run_serving(ctx, drive)
