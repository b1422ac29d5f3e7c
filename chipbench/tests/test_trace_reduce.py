"""trace_reduce on a trace recorded on a TPU v5 lite: three GPT-2 M train
steps through `ElasticTrainer.step`, each followed by a 20 ms host sleep
inside a `bench.host_idle` span."""
from pathlib import Path

import pytest

from chipbench import trace_reduce

DATA = Path(__file__).parent / "data" / "probe_m_3steps.xplane.pb.gz"


@pytest.fixture(scope="module")
def pd():
    return trace_reduce.load(DATA)


def test_planes_and_spans(pd):
    ops = trace_reduce.device_ops(pd)
    assert list(ops) == [0]
    assert len(ops[0]) > 10_000
    names = [n for n, _, _ in trace_reduce.host_spans(pd)]
    assert names.count("bench.step") == 3
    assert names.count("bench.host_idle") == 3


def test_merge_and_self_times():
    assert trace_reduce.merge([(0, 5), (3, 8), (10, 12), (11, 11)], 1, 11) \
        == [[1, 8], [10, 11]]
    own = trace_reduce.self_times([("%while.1 = x", 0, 10e9),
                                   ("%fusion.2 = y", 2e9, 5e9),
                                   ("%fusion.2 = y", 6e9, 7e9)])
    assert own == pytest.approx({"while.1": 6.0, "fusion.2": 4.0})


def test_reduce(pd):
    spans = trace_reduce.host_spans(pd)
    lo = min(s for n, s, _ in spans if n == "bench.step")
    hi = max(e for n, _, e in spans if n == "bench.host_idle")
    red = trace_reduce.reduce(pd, window=(lo, hi))
    # Three steps of about 0.37 s device time each in about 1.2 s.
    assert red["window_s"] == pytest.approx(1.195, abs=0.01)
    assert 1.05 < red["busy_s"][0] < 1.15
    assert red["idle_share"] == pytest.approx(1 - red["busy_s"][0]
                                              / red["window_s"])
    # The sleeps are the longest gaps, and are named by their span.
    gaps = red["idle_gaps"]
    assert [g[0] for g in gaps[:3]] == ["host_idle"] * 3
    assert all(0.02 < g[1] < 0.03 for g in gaps[:3])
    # Nesting is subtracted: the layer-scan loops (0.73 s over the three
    # steps with their bodies) keep little own time, and own times add up
    # to no more than the busy time.
    own = trace_reduce.self_times(trace_reduce.device_ops(pd)[0])
    assert own["while.8"] < 0.1
    assert red["device_ops"][0][0].startswith("fusion")
    assert sum(t for _, t in red["device_ops"]) <= red["busy_s"][0] + 1e-6


def test_window_span_required(pd):
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(pd)
