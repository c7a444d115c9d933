"""Generator ``doc_sessions``: a closed loop of sessions that ask short
questions about a few long documents. Analysts and agents of a long-context
deployment: each takes its next turn when its last answer has ended and a
think time has passed, so the load is the number of sessions, not a rate.

Set-up (before the window, part of ``setup_s``): every document is prefilled
ONCE through the server's HTTP path (chunked prefill; its pages are published
to the prefix cache and stay), then a few warm turns compile the question's
prefill program and the decode program, repeated while the compile cache still
grows. ``preroll_s`` before the window the sessions start, each at an instant
drawn uniformly from the first ``session_start_spread_s``.

A turn: one document drawn uniformly + a question of unique ids (so the
prompt shares the document's whole pages and nothing else), an answer of
``max_tokens`` drawn from the traffic file, greedy, streamed. Everything a
session sends comes from ``(seed, session index)``: the same seed gives the
same documents and, session by session, the same turns in the same order;
only their interleaving follows the server.

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
from harness import BenchFailure, log


def sizes(ctx) -> dict:
    """The traffic file's numbers, or the rehearsal's tiny ones."""
    t = ctx.traffic
    if not ctx.rehearsal:
        return {"doc_tokens": t["doc_tokens"], "documents": t["documents"],
                "sessions": t["sessions"], "question": t["question_tokens"],
                "answer": t["max_tokens"], "preroll_s": t["preroll_s"],
                "spread_s": t["session_start_spread_s"]}
    r = t["rehearsal"]
    scale = ctx.rehearsal["length_scale"]
    return {"doc_tokens": r["doc_tokens"], "documents": r["documents"],
            "sessions": r["sessions"], "question": serving.scaled(t["question_tokens"], scale),
            "answer": serving.scaled(t["max_tokens"], scale), "preroll_s": r["preroll_s"],
            "spread_s": r["preroll_s"] / 2}


def documents(seed: int, n: int, doc_tokens: int, vocab: int) -> list[str]:
    """The documents' texts: ``doc_tokens - 1`` ids each (the server prepends
    its bos id), drawn from the seed."""
    rng = np.random.default_rng([seed, 0xD0C5])
    return [tokenizer.text_of(serving.draw_ids(rng, doc_tokens - 1, vocab)) for _ in range(n)]


def session_turns(seed: int, session: int, sz: dict, traffic: dict, vocab: int):
    """The endless sequence of one session's turns: (document index, question
    ids, max_tokens, think seconds after the answer)."""
    rng = np.random.default_rng([seed, 0x5E55, session])
    think = traffic["think_s"]
    while True:
        q = int(serving.draw_lengths(rng, sz["question"], 1)[0])
        yield (int(rng.integers(0, sz["documents"])),
               serving.draw_ids(rng, q, vocab),
               int(serving.draw_lengths(rng, sz["answer"], 1)[0]),
               float(min(rng.exponential(think["mean"]), think["max"])))


async def prefill_documents(ctx, port: int, docs: list[str], sz: dict) -> list[float]:
    """Each document once, one after the other; seconds each took."""
    took = []
    for i, text in enumerate(docs):
        rec = serving.new_record(sz["doc_tokens"], 1, None)
        await serving.complete(port, text, rec)
        if not (serving.request_ok(rec) or serving.empty_stop(rec)):
            raise BenchFailure(f"document {i} was not prefilled: {rec}")
        took.append(rec["done"] - rec["sent"])
        log(f"document {i}: {sz['doc_tokens']} tokens prefilled in {took[-1]:.1f}s")
    return took


async def warm_turns(ctx, port: int, docs: list[str], sz: dict, vocab: int) -> int:
    """A short and a long question behind the first document, a few answer
    tokens each, while the compile cache still grows (at most three rounds)."""
    rng = np.random.default_rng([ctx.seed, 0x3A43])
    sent = 0
    for round_no in range(3):
        before = ctx.cache_entries()
        for n in (sz["question"]["min"], sz["question"]["max"]):
            ids = serving.draw_ids(rng, n, vocab)
            rec = serving.new_record(sz["doc_tokens"] + n, 8, None)
            await serving.complete(port, docs[0] + " " + tokenizer.text_of(ids), rec)
            sent += 1
            if not (serving.request_ok(rec) or serving.empty_stop(rec)):
                raise BenchFailure(f"warm turn failed: {rec}")
        grew = ctx.cache_entries() - before
        log(f"warm turns, round {round_no + 1}: {grew} new programs in the compile cache")
        if grew == 0:
            break
    return sent


async def drive(ctx, port, vocab, open_window, read_counters, state):
    sz = sizes(ctx)
    docs = documents(ctx.seed, sz["documents"], sz["doc_tokens"], vocab)
    doc_prefill_s = await prefill_documents(ctx, port, docs, sz)
    warm = await warm_turns(ctx, port, docs, sz, vocab)

    start = now() + 0.05
    t0 = start + sz["preroll_s"]
    t1 = t0 + ctx.seconds
    records: list[dict] = []
    stop = asyncio.Event()
    starts = np.random.default_rng([ctx.seed, 0x57A7]).uniform(
        0.0, sz["spread_s"], sz["sessions"])

    async def session(i: int):
        await asyncio.sleep(max(0.0, start + float(starts[i]) - now()))
        for doc, ids, max_tokens, think in session_turns(ctx.seed, i, sz, ctx.traffic, vocab):
            if stop.is_set():
                return
            sent = now()
            rec = serving.new_record(sz["doc_tokens"] + len(ids), max_tokens, sent,
                                     counted=bool(t0 <= sent < t1), session=i, document=doc)
            records.append(rec)
            await serving.complete(port, docs[doc] + " " + tokenizer.text_of(ids), rec)
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
    return records, {"loop": "closed", "sessions": sz["sessions"],
                     "documents": sz["documents"], "doc_tokens": sz["doc_tokens"],
                     "doc_prefill_s": doc_prefill_s, "warm_turns": warm,
                     "turns_sent": len(records)}


def run(ctx) -> dict:
    return serving.run_serving(ctx, drive)
