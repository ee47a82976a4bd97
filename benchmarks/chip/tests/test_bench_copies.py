"""The readers of the service's copy steps: ``copy_bytes_x`` on
hand-made spans, ``copy_idle_share`` on a hand-made trace with known
overlaps."""
from types import SimpleNamespace

import pytest

from conftest import CHIP


def _reader(run, name):
    return run.load_module(CHIP / "metrics" / f"{name}.py",
                           f"chipbench_metric_{name}")


def _view(run, tracemod, *, spans=(), red=None):
    bounds = tracemod.window(red) if red is not None else None
    return run.RunView(cell=None, record={}, spans=list(spans), trace=red,
                       bounds=bounds, peak=None, work=None,
                       tracemod=tracemod)


def _span(name, **attrs):
    return SimpleNamespace(name=name, attrs=attrs)


@pytest.mark.parametrize("cell", ["batch", "pulsar"])
def test_copy_bytes_x_sums_each_counter_where_it_is_set(run, tracemod,
                                                        cell):
    read = _reader(run, f"copy_bytes_x.{cell}").read
    spans = [
        _span("submit", payload_bytes=100, h2d_bytes=100),
        _span("submit", payload_bytes=300, h2d_bytes=0),
        _span("pull", batch_id=0, d2h_bytes=400),
        _span("h2d", batch_id=0, h2d_bytes=512),
        # inherits its parent's counter, which it does not move
        _span("compute", batch_id=0, h2d_bytes=512),
        _span("batch", batch_id=0, rows=4),
    ]
    assert read(_view(run, tracemod, spans=spans)) == pytest.approx(
        (100 + 400 + 512) / 400)
    # a program without the counters (an older service) reads nothing
    assert read(_view(run, tracemod, spans=[_span("batch")])) is None
    assert read(_view(run, tracemod)) is None


def _copy_trace(tracemod, devices=1):
    ops = [("fusion", 10, 20), ("fft_pallas", 50, 60)]
    host = [("bench.window", 0, 100), ("bench.submit", 0, 30),
            ("service.submit", 0, 30),      # idle 0-10 and 20-30: 20
            ("bench.drain", 30, 100), ("service.drain", 30, 100),
            ("service.batch", 38, 95),
            ("service.stack", 40, 55),      # with pad: 40-57, idle 40-50
            ("service.pull", 42, 48),       # inside stack, not again
            ("service.pad", 50, 57),
            ("service.execute", 57, 80),
            ("service.h2d", 58, 70),        # idle 60-70: 10
            ("service.compute", 70, 80)]
    return tracemod.Reduced(
        ops={f"/device:TPU:{i}": ops for i in range(devices)}, host=host)


@pytest.mark.parametrize("cell", ["batch", "pulsar"])
@pytest.mark.parametrize("devices", [1, 2])
def test_copy_idle_share_is_the_idle_overlap_of_copy_steps(run, tracemod,
                                                           cell, devices):
    read = _reader(run, f"copy_idle_share.{cell}").read
    view = _view(run, tracemod, red=_copy_trace(tracemod, devices))
    assert read(view) == pytest.approx(40.0)
    assert read(view) <= view.idle_share()


def test_copy_idle_share_reads_nothing_without_copy_spans(run, tracemod):
    read = _reader(run, "copy_idle_share.batch").read
    red = _copy_trace(tracemod)
    red.host = [h for h in red.host
                if h[0] in ("bench.window", "bench.drain",
                            "service.batch", "service.execute")]
    assert read(_view(run, tracemod, red=red)) is None
    assert read(_view(run, tracemod)) is None
