"""The readers of the multibeam cell on hand-made spans and traces of four
chips: ``d2d_bytes_x`` (chip-to-chip bytes over submitted bytes),
``overlap_x`` (chips busy at once), ``imbalance_x`` (busiest chip over
the mean) and ``idle_share``, each where the chips work one at a time,
all at once, and as 13 beams spread 4-3-3-3."""
from types import SimpleNamespace

import pytest

from conftest import CHIP
from test_bench_correct import _candidate_dropped, _run, _stat_altered


def _reader(run, name):
    return run.load_module(CHIP / "metrics" / f"{name}.multibeam.py",
                           f"chipbench_metric_{name}.multibeam")


def _view(run, tracemod, *, spans=(), red=None):
    bounds = tracemod.window(red) if red is not None else None
    return run.RunView(cell=None, record={}, spans=list(spans), trace=red,
                       bounds=bounds, peak=None, work=None,
                       tracemod=tracemod)


def _trace(tracemod, busy):
    """Four chips; chip i runs one op of beam length per (start, beams)
    in ``busy[i]``, inside a 1300 ns window."""
    ops = {f"/device:TPU:{i}": [("pulsar", 100 * t, 100 * (t + n))
                                for t, n in spans]
           for i, spans in enumerate(busy)}
    return tracemod.Reduced(ops=ops, host=[("bench.window", 0, 1300)])


#: (chips' busy spans as (start, beams), overlap_x, imbalance_x,
#: idle_share): one chip at a time, all four at once, 13 beams 4-3-3-3.
CASES = {
    "serial": ([[(0, 4)], [(4, 3)], [(7, 3)], [(10, 3)]],
               1.0, 4 / 3.25, 75.0),
    "overlapped": ([[(0, 4)]] * 4, 4.0, 1.0, 100 * (1 - 4 / 13)),
    "4-3-3-3": ([[(0, 2), (2, 2)], [(0, 2), (2, 1)], [(0, 2), (2, 1)],
                 [(0, 2), (2, 1)]], 3.25, 4 / 3.25, 75.0),
}


@pytest.mark.parametrize("case", CASES)
def test_device_readers_on_four_chips(run, tracemod, case):
    busy, overlap, imbalance, idle = CASES[case]
    view = _view(run, tracemod, red=_trace(tracemod, busy))
    assert _reader(run, "overlap_x").read(view) == pytest.approx(overlap)
    assert _reader(run, "imbalance_x").read(view) == pytest.approx(
        imbalance)
    assert _reader(run, "idle_share").read(view) == pytest.approx(idle)


@pytest.mark.parametrize("name", ["overlap_x", "imbalance_x", "idle_share"])
def test_device_readers_read_nothing_without_device_ops(run, tracemod,
                                                        name):
    read = _reader(run, name).read
    assert read(_view(run, tracemod)) is None
    empty = tracemod.Reduced(ops={}, host=[("bench.window", 0, 1300)])
    assert read(_view(run, tracemod, red=empty)) is None


def _span(name, **attrs):
    return SimpleNamespace(name=name, attrs=attrs)


def test_d2d_bytes_x_counts_chip_to_chip_copies(run, tracemod):
    read = _reader(run, "d2d_bytes_x").read
    submits = [_span("submit", payload_bytes=512, worker=w)
               for w in range(4)]
    routed = submits + [_span("stack", d2d_bytes=0),
                        _span("h2d", h2d_bytes=0, d2d_bytes=0),
                        _span("batch", rows=1)]
    assert read(_view(run, tracemod, spans=routed)) == 0.0
    stolen = routed + [_span("stack", d2d_bytes=0),
                       _span("h2d", h2d_bytes=0, d2d_bytes=1024)]
    assert read(_view(run, tracemod, spans=stolen)) == pytest.approx(0.5)
    # a service that counts no chip-to-chip bytes reads nothing
    older = submits + [_span("stack"), _span("h2d", h2d_bytes=0)]
    assert read(_view(run, tracemod, spans=older)) is None
    assert read(_view(run, tracemod)) is None


CELL = "pulsar_htru_13beam_4chip"


@pytest.mark.parametrize("fault", [_stat_altered, _candidate_dropped],
                         ids=lambda f: f.__name__[1:])
def test_multibeam_fault_is_not_correct(run, monkeypatch, fault):
    """The pulsar faults of ``test_bench_correct``, in the multibeam
    cell's rehearsal: an altered statistic or a dropped candidate in any
    beam reads ``correct: false``."""
    from repro.serving.cache import PlanSweepCache
    build = PlanSweepCache._build

    def broken_build(self, key, **kw):
        entry = build(self, key, **kw)
        entry.fn = fault(entry.fn)
        return entry

    monkeypatch.setattr(PlanSweepCache, "_build", broken_build)
    res = _run(run, CELL)
    assert not res["correct"], res["checks"]
