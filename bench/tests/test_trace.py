"""The reduction from a profiler trace to the device metrics."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def _ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def _pd(ops, spans):
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="Steps", events=[])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=spans)])
    return NS(planes=[host, dev])


def test_busy_kernels_gaps_by_hand():
    ops = [_ev("fusion.1", 0, 100_000),
           _ev("fusion.2", 50_000, 100_000),            # overlaps the first
           _ev("custom-call.3", 400_000, 200_000,
               [("long_name", "sla2_decode_paged_none_kv_none(...)")]),
           _ev("custom-call.3", 1_000_000, 100_000),
           _ev("fusion.1", 1_100_000, 10_000)]
    spans = [_ev("bench.step", 0, 390_000), _ev("bench.submit", 390_000,
                                                500_000),
             _ev("bench.sync", 890_000, 300_000)]
    r = trace.reduce(_pd(ops, spans), kernels=["sla2_decode_paged"])
    # union: [0, 150k] + [400k, 600k] + [1000k, 1110k]
    assert r["busy_s"] == pytest.approx((150_000 + 200_000 + 110_000) * 1e-9)
    assert r["span_s"] == pytest.approx(1_110_000 * 1e-9)
    k = r["kernels"]["sla2_decode_paged"]
    assert k["count"] == 2 and k["time_s"] == pytest.approx(300_000 * 1e-9)
    # gaps: [150k, 400k] mostly under bench.step, [600k, 1000k] under submit
    assert r["idle_gaps"] == [["bench.submit", pytest.approx(400e-6)],
                              ["bench.step", pytest.approx(250e-6)]]
    assert r["device_ops"][0][0] == "custom-call.3"


def test_no_device_plane():
    pd = NS(planes=[NS(name="/host:CPU", lines=[])])
    assert trace.reduce(pd)["devices"] == 0


def test_recorded_tpu_trace():
    """bench/testdata/tiny.xplane.pb: bench/tests/record_trace.py on one
    TPU v5e chip: a matmul and a Pallas kernel named sla2_sparse_fwd_int8,
    three times, each inside a bench.step span with a 2 ms bench.submit
    pause after it."""
    r = trace.reduce_file(str(TESTDATA / "tiny.xplane.pb"),
                          kernels=["sla2_sparse_fwd"])
    assert r["devices"] == 1
    k = r["kernels"]["sla2_sparse_fwd"]
    assert k["count"] == 3 and k["time_s"] > 0
    assert 0 < r["busy_s"] <= r["span_s"]
    assert r["device_ops"][1][0] == "sla2_sparse_fwd_int8.1"
    labels = {g[0] for g in r["idle_gaps"]}
    assert "bench.submit" in labels
    longest = max(g[1] for g in r["idle_gaps"])
    assert 1.5e-3 < longest < 50e-3
