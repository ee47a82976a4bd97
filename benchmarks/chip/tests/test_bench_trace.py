"""The trace reduction, on hand-made intervals and on a small trace of
the served path recorded on a TPU v5e (``data/served_small.xplane.pb``:
two drains of 2 x (64, 8192) c2c plus 2 pulsar blocks of 64 x 2^14)."""
import pytest

from conftest import CHIP

RECORDED = CHIP / "tests" / "data" / "served_small.xplane.pb"


def _reduced(tracemod):
    ops = {"/device:TPU:0": [("fusion.1", 10, 20), ("fft", 15, 30),
                             ("copy", 50, 60), ("fft", 90, 120)]}
    host = [("bench.window", 0, 100), ("bench.drain", 6, 70),
            ("service.execute", 40, 65), ("bench.submit", 70, 100)]
    return tracemod.Reduced(ops=ops, host=host)


def test_busy_is_the_union_of_op_intervals(tracemod):
    red = _reduced(tracemod)
    lo, hi = tracemod.window(red)
    assert (lo, hi) == (0, 100)
    assert tracemod.merged(red.ops["/device:TPU:0"], lo, hi) == [
        (10, 30), (50, 60), (90, 100)]
    assert tracemod.busy_s(red, lo, hi) == pytest.approx(40e-9)


def test_op_seconds_and_top_ops(tracemod):
    red = _reduced(tracemod)
    assert tracemod.op_seconds(red, 0, 100) == pytest.approx(45e-9)
    assert tracemod.op_seconds(red, 0, 100, lambda n: n == "fft") == \
        pytest.approx(25e-9)
    assert tracemod.top_ops(red, 0, 100)[0] == ["fft", pytest.approx(25e-9)]


def test_idle_gaps_go_to_the_innermost_host_span(tracemod):
    red = _reduced(tracemod)
    gaps = dict(tracemod.idle_gaps(red, 0, 100))
    # Idle: [0,10) in no span but the window, [30,50) in drain (its middle
    # 40 is where execute opens), [60,90) middle 75 in submit.
    assert gaps == {"no span": pytest.approx(10e-9),
                    "service.execute": pytest.approx(20e-9),
                    "bench.submit": pytest.approx(30e-9)}


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace(tracemod):
    red = tracemod.load(str(RECORDED))
    assert red.n_devices == 1
    lo, hi = tracemod.window(red)
    window_s = (hi - lo) / 1e9
    busy = tracemod.busy_s(red, lo, hi)
    assert 0 < busy < window_s
    names = {n for n, _, _ in red.host}
    assert {"bench.submit", "bench.drain", "service.batch",
            "service.execute"} <= names
    top = tracemod.top_ops(red, lo, hi)
    assert top and all(s > 0 for _, s in top)
    gaps = tracemod.idle_gaps(red, lo, hi, k=100)
    assert sum(s for _, s in gaps) == pytest.approx(window_s - busy,
                                                    rel=1e-6)
    assert tracemod.op_seconds(red, lo, hi,
                               lambda n: "dedisp" in n.lower()) > 0
