"""The reducer on events whose numbers are known by construction, on a trace
the CPU profiler writes here, and on the trace recorded on the chip."""
import glob
import os

import pytest
import reduce_trace as rt
from conftest import BENCH

MS = 1_000_000  # ns


def test_interval_arithmetic():
    assert rt.union([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    assert rt.measure([[0, 3], [5, 7]]) == 5
    assert rt.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5], [7, 10]]
    assert rt.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert rt.subtract([[0, 4]], []) == [[0, 4]]


def test_self_time_subtracts_nested_children():
    # a while of 10 ms holding two fusions of 3 ms each
    timed = rt.self_times([["while", 0, 10 * MS], ["fusion.1", 1 * MS, 3 * MS],
                           ["fusion.2", 5 * MS, 3 * MS]])
    assert timed == [("while", 4.0 * MS, False), ("fusion.1", 3.0 * MS, True),
                     ("fusion.2", 3.0 * MS, True)]


def known_events():
    """Two devices over a 100 ms window. Device 0: matmul 0-40, an all-gather
    40-60 of which 50-60 runs beside a fusion, idle 60-80, matmul 80-100.
    Device 1: busy 0-100 with one matmul."""
    return {
        "devices": {
            "0": [["matmul", 0, 40 * MS], ["all-gather.1", 40 * MS, 20 * MS],
                  ["fusion.7", 50 * MS, 10 * MS], ["matmul", 80 * MS, 20 * MS]],
            "1": [["matmul", 0, 100 * MS]],
        },
        # the trace clock starts 1,000 s after the wall clock's zero
        "clock": [[10 * MS, 1_000_010 * MS], [20 * MS, 1_000_020 * MS]],
    }


def test_known_idle_share_top_operation_and_exposed_collective():
    spans = [{"name": "engine.decode", "t0": 1000.065, "dur_s": 0.010},
             {"name": "server.request", "t0": 1000.0, "dur_s": 0.1}]
    r = rt.reduce(known_events(), spans)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx((0.080 + 0.100) / 2)
    assert r["idle_share"] == pytest.approx(0.10)
    assert next(iter(r["op_self_s"])) == "matmul"
    assert r["op_self_s"]["matmul"] == pytest.approx((0.060 + 0.100) / 2)
    # the fusion nests inside the all-gather's interval: 10 of its 20 ms are exposed
    assert r["collective_s"] == pytest.approx(0.020 / 2)
    assert r["collective_exposed_s"] == pytest.approx(0.010 / 2)
    # the one gap, 60-80 ms, has its middle inside the 65-75 ms decode span
    assert r["gaps_longest"] == [["engine.decode", pytest.approx(0.020)]]
    b = rt.breakdown(r)
    assert b["device_ops"][0][0] == "matmul" and len(b["idle_gaps"]) == 1


def test_a_gap_no_span_covers_is_unattributed_and_no_device_is_an_error():
    r = rt.reduce(known_events(), [])
    assert r["gaps_longest"][0][0] == "unattributed"
    with pytest.raises(ValueError):
        rt.reduce({"devices": {}, "clock": []})


def test_dump_round_trip_and_cut(tmp_path):
    path = str(tmp_path / "t.json.gz")
    rt.dump(known_events(), path, 0.0, 0.045)
    back = rt.load(path)
    assert [e[0] for e in back["devices"]["0"]] == ["matmul", "all-gather.1"]
    # device 1's matmul starts inside the cut and is kept whole
    assert rt.reduce(back)["window_s"] == pytest.approx(0.100)


def test_reads_what_the_profiler_writes_here(tmp_path):
    """An .xplane.pb from the CPU profiler: the clock mark is found, and with
    the rehearsal switch the CPU client's threads stand in for a device."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(rt.CLOCK_MARK, wall_ns=time.time_ns()):
        pass
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = rt.load(path)
    assert events["devices"] == {}  # no TPU plane in a CPU trace
    assert len(events["clock"]) == 1 and abs(
        rt.clock_offset_ns(events["clock"]) / 1e9 - time.time()) < 60
    r = rt.reduce(rt.load(path, cpu_rehearsal=True))
    assert r["busy_s"] > 0 and 0 <= r["idle_share"] < 1


RECORDED = os.path.join(BENCH, "tests", "data", "fsdp4_loss_block.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_the_recorded_chip_trace_reduces_to_its_known_numbers():
    import json

    with open(os.path.join(BENCH, "tests", "data", "fsdp4_loss_block.expected.json")) as f:
        want = json.load(f)
    r = rt.reduce(rt.load(RECORDED))
    assert r["devices"] == want["devices"]
    assert r["idle_share"] == pytest.approx(want["idle_share"], rel=1e-6)
    assert r["collective_exposed_s"] == pytest.approx(want["collective_exposed_s"], rel=1e-6)
    assert next(iter(r["op_self_s"])) == want["top_op"]
