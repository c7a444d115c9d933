"""``spread.py``: sub-windows by the due instant, the readers' own p95, and
the driver's three spreads on a hand-made set."""
import pytest

import spread


def request(due, ttft_s, tpot_s=0.010):
    return {"due": due, "first": due + ttft_s, "last": due + ttft_s + 10 * tpot_s,
            "n_first": 1, "n_out": 11, "ok": True}


def long_run():
    """Three 10 s sub-windows of 100 requests each; in window i every request
    waits (i + 1) x 100 ms but the last five of each, which wait a second."""
    reqs = []
    for i in range(3):
        for k in range(100):
            reqs.append(request(1000.0 + 10 * i + k * 0.1,
                                1.0 if k >= 95 else 0.1 * (i + 1)))
    return {"window_s": 35.0, "window_t0": 1000.0, "requests": reqs}


def test_a_run_is_cut_into_whole_sub_windows_by_the_due_instant():
    wins = spread.windows(long_run(), 10.0)
    assert [len(w["requests"]) for w in wins] == [100, 100, 100]  # 35 // 10: the rest is dropped
    assert all(w["window_s"] == 10.0 for w in wins)
    whole = spread.windows(long_run(), None)
    assert len(whole) == 1 and len(whole[0]["requests"]) == 300
    # a record from before window_t0: the first request's due instant stands in
    old = {k: v for k, v in long_run().items() if k != "window_t0"}
    assert [len(w["requests"]) for w in spread.windows(old, 10.0)] == [100, 100, 100]


def test_each_sub_window_reads_its_own_known_percentiles():
    rows = {(r["seconds"], r["metric"]): r for r in spread.table([long_run()] * 2, [10.0])}
    p95 = rows[(10.0, "ttft_client_p95_ms")]["values"]
    # 100 samples, linear interpolation at 94.05: 0.05 of the way from 100(i+1) to 1,000
    assert p95 == pytest.approx([100 * (i + 1) + 0.05 * (1000 - 100 * (i + 1)) for i in range(3)] * 2)
    assert rows[(10.0, "ttft_p50_ms")]["values"] == pytest.approx([100.0, 200.0, 300.0] * 2)
    assert rows[(10.0, "tpot_p50_ms")]["values"] == pytest.approx([10.0] * 6)
    assert rows[(10.0, "ttft_client_p95_ms")]["n"] == 6 and rows[(10.0, "ttft_client_p95_ms")]["requests"] == 100


def test_the_drivers_spreads_of_a_hand_made_set():
    values = [100.0, 104.0, 98.0, 102.0, 130.0, 96.0]  # one far-off run
    s = spread.spreads(values)
    assert s["median"] == 101.0 and s["n"] == 6
    assert spread.nearest(values) == [100.0, 104.0, 98.0, 102.0, 96.0]
    assert s["rng-1"] == pytest.approx((104.0 - 96.0) / 101.0)  # range of five of six
    # statistics.quantiles, exclusive: quartiles of six at positions 1.75 and 5.25
    assert s["iqr"] == pytest.approx((104.0 + 0.25 * 26.0 - (96.0 + 0.75 * 2.0)) / 101.0)
    assert s["iqr-1"] == pytest.approx((103.0 - 97.0) / 100.0)  # over the median of the five
    assert s["iqr-1"] < s["iqr"] and s["iqr-1"] < s["rng-1"]


def test_sets_are_six_windows_in_the_order_given():
    assert spread.sets_of_six(list(range(14))) == [list(range(6)), list(range(6, 12))]
    assert spread.sets_of_six(list(range(9))) == [list(range(6)), [6, 7, 8]]
