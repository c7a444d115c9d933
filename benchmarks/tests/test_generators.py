"""Generators: deterministic in the seed, inside their clips, sharing what
the traffic file says and nothing else; the open loop times from the due
instant."""
import json
import os

import numpy as np
import pytest
from conftest import BENCH

from generators import open_loop, serving

VOCAB = 151936


def traffic(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_open_loop_is_deterministic_in_the_seed():
    t = traffic("chat-steady")
    a, b, c = (open_loop.schedule(t, s, 30, VOCAB) for s in (7, 7, 2**31 + 8))
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all((x["ids"] == y["ids"]).all() for x, y in zip(a, b))
    assert [r["due_s"] for r in a] != [r["due_s"] for r in c]


def test_open_loop_offers_a_fixed_count_inside_its_clips():
    t = traffic("chat-steady")
    reqs = open_loop.schedule(t, 3, 30, VOCAB)
    span = t["preroll_s"] + 30 + t["postroll_s"]
    assert len(reqs) == round(t["rate_per_s"] * span)
    assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
    assert all(-t["preroll_s"] <= r["due_s"] < 30 + t["postroll_s"] for r in reqs)
    assert all(r["counted"] == (0 <= r["due_s"] < 30) for r in reqs)
    p, m = t["prompt_tokens"], t["max_tokens"]
    assert all(p["min"] <= r["prompt_tokens"] <= p["max"] for r in reqs)
    assert all(m["min"] <= r["max_tokens"] <= m["max"] for r in reqs)
    med = np.median([r["prompt_tokens"] for r in reqs])
    assert 0.7 * p["median"] < med < 1.4 * p["median"]


def test_open_loop_shares_nothing():
    reqs = open_loop.schedule(traffic("chat-steady"), 5, 30, VOCAB)
    heads = [tuple(r["ids"][:4]) for r in reqs]
    assert len(set(heads)) == len(heads)
    specials = set(serving.tokenizer.SPECIALS)
    assert not any(specials & set(r["ids"].tolist()) for r in reqs)


def test_chat_steady_7b_keeps_chat_steadys_lengths_at_four_fifths_of_its_own_knee():
    import math

    a, b = traffic("chat-steady"), traffic("chat-steady-7b")
    for k in ("generator", "prompt_tokens", "max_tokens", "sharing", "preroll_s",
              "postroll_s", "tokenizer", "warmup"):
        assert a[k] == b[k], k
    assert b["server_args"] == a["server_args"] + ["--pages", "720"]
    knee = b["knee"]
    assert knee["cell_rate_per_s"] == b["rate_per_s"] == math.floor(
        0.8 * knee["knee_rate_per_s"] / 0.5) * 0.5
    col = knee["columns"].index
    first = knee["rows"][0]
    assert first[col("rate_per_s")] == 1.0  # the run the limits were derived from
    assert b["limits"] == {
        "ttft_ms": math.ceil(1.5 * first[col("ttft_p95_ms")] / 50) * 50,
        "tpot_ms": math.ceil(1.5 * first[col("tpot_p95_ms")] / 5) * 5, "attainment": 0.9}
    held = [r for r in knee["rows"] if r[col("attained")] >= 0.9 and not r[col("backlog_grows")]]
    assert max(r[col("rate_per_s")] for r in held) == knee["knee_rate_per_s"]


def test_lengths_reject_an_unknown_distribution():
    with pytest.raises(ValueError):
        serving.draw_lengths(np.random.default_rng(0), {"dist": "zipf", "min": 1, "max": 2}, 3)


def record(due, sent, first, last, n_first, n_out, ok=True):
    return {"due": due, "sent": sent, "first": first, "last": last, "done": last, "ok": ok,
            "n_first": n_first, "n_out": n_out, "status": 200 if ok else 503,
            "finished": ok, "finish_reason": "length" if ok else None, "error": None,
            "prompt_tokens": 10, "max_tokens": 64, "counted": True}


def test_ttft_counts_from_the_due_instant_and_a_failure_counts_as_the_window():
    from harness import load_module

    def reader(section, name):
        return load_module(os.path.join(BENCH, section, f"{name}.py"))

    # Due at 0, sent 0.3 s late, first token at 0.5: a user waited 0.5 s.
    run = {"window_s": 30.0, "requests": [record(0.0, 0.3, 0.5, 1.5, 1, 11)] * 19
           + [record(0.0, 0.3, None, None, 0, 0, ok=False)]}
    assert reader("layer_metrics", "ttft_p50_ms").read(run) == pytest.approx(500.0)
    assert reader("layer_metrics", "ttft_client_p95_ms").read(run) > 500.0  # the failure: 30,000 ms
    assert reader("layer_metrics", "generator_late_p95_ms").read(run) == pytest.approx(300.0)
    # 10 tokens arrived in the second after the first event.
    assert reader("end_to_end", "tpot_p50_ms").read(run) == pytest.approx(100.0)


def empty(finish_reason="stop"):
    return dict(record(0.0, 0.0, None, None, 0, 0), finish_reason=finish_reason)


@pytest.mark.parametrize("empties,others,failed", [
    (1, 199, 0),    # one end-of-text first token in two hundred: a correct answer
    (3, 197, 3),    # more than a hundredth of the window: every one of them fails
    (1, 50, 1),     # a window too small to allow one
])
def test_an_answer_of_no_token_is_correct_only_as_a_rare_stop(empties, others, failed):
    reqs = [empty() for _ in range(empties)] + [
        record(0.0, 0.0, 0.5, 1.5, 1, 11) for _ in range(others)]
    assert len(serving.judge(reqs)) == failed
    assert sum(not r["ok"] for r in reqs) == failed


def test_an_empty_answer_that_does_not_say_stop_and_an_overlong_one_fail():
    reqs = [empty(None), empty("length"), record(0.0, 0.0, 0.5, 1.5, 1, 65)] + [
        record(0.0, 0.0, 0.5, 1.5, 1, 11) for _ in range(400)]
    assert serving.judge(reqs) == reqs[:3]


def test_a_refused_empty_answer_is_charged_the_window_by_ttft():
    from harness import load_module

    reqs = [empty() for _ in range(40)] + [record(0.0, 0.0, 0.5, 1.5, 1, 11) for _ in range(160)]
    serving.judge(reqs)
    ttft = load_module(os.path.join(BENCH, "layer_metrics", "ttft_client_p95_ms.py"))
    assert ttft.read({"window_s": 30.0, "requests": reqs}) == pytest.approx(30_000.0)


def test_pool_shares_split_the_pool_into_live_cached_and_free():
    from harness import load_module

    run = {"counters": [{"pages_total": 1000}, {"pages_total": 1000}],
           "polls": [{"pages_free": 900, "pages_cached": 40},
                     {"pages_free": 700, "pages_cached": 220},
                     {"pages_free": None, "pages_cached": None}]}  # a poll that failed

    def reader(name):
        return load_module(os.path.join(BENCH, "layer_metrics", f"{name}.py"))

    assert reader("pool_live_share_chat").read(run) == pytest.approx(7.0)    # (60 + 80) / 2
    assert reader("pool_cached_share_chat").read(run) == pytest.approx(13.0)  # (40 + 220) / 2
    assert reader("pool_live_share_chat").read(dict(run, polls=[])) is None
