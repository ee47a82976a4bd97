"""The multibeam deployment: 13 beams' filterbanks searched over four
one-chip workers (four forced CPU devices in a subprocess, as
``test_distributed`` runs them).

Routing puts each payload on the device of the worker that runs it, the
coalescer cuts each worker's beams into power-of-two batches under the
Eq. 6 cap (no pad row), and the dispatcher launches every worker's next
batch before it waits on any.  The served candidates are held to the
float64 reference of the benchmark (``benchmarks/chip/reference.py``)
and to a one-worker service bit for bit.
"""
import json
import os

import jax
import numpy as np
import pytest

from repro.core.hardware import TPU_V5E
from repro.core.workloads import COMPLEX_BYTES
from repro.obs import Tracer
from repro.serving import FFTRequest, FFTService, coalesce
from test_distributed import REPO, run_with_devices

#: A small beam: (nchan, ntime) float32, and the search over it.
NCHAN, NTIME = 64, 16384
SEARCH = dict(kind="pulsar", dm_trials=8, templates=5, n_harmonics=4)
BEAM_BYTES = NCHAN * NTIME * 4
#: The deployment's Eq. 6 cap: three beams a batch.
BATCH_BYTES = 3.5 * BEAM_BYTES

_SERVE = f"""
    import importlib.util, json
    import jax, numpy as np
    from repro.core.hardware import TPU_V5E
    from repro.obs import Tracer
    from repro.serving import FFTService

    spec = importlib.util.spec_from_file_location(
        "reference", {os.path.join(REPO, "benchmarks", "chip",
                                   "reference.py")!r})
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    NCHAN, NTIME, KW = {NCHAN}, {NTIME}, {SEARCH!r}
    geo = dict(nchan=NCHAN, f_lo=1300.0, f_hi=1500.0, tsamp=6.4e-5,
               dm_trials=KW["dm_trials"], dm_spacing=4.0,
               n_templates=KW["templates"])
    freqs = ref.channel_freqs(NCHAN, geo["f_lo"], geo["f_hi"])
    dms = ref.trial_dms(geo["dm_trials"], geo["f_lo"], geo["f_hi"],
                        geo["tsamp"], geo["dm_spacing"])
    drifts = ref.template_drifts(geo["n_templates"])
    injected = {{0: [(2, 3, 1500), (6, 1, 3900)], 5: [(4, 2, 2400)],
                10: [(7, 4, 3100)]}}
    rng = np.random.default_rng(13)
    beams = [ref.inject_filterbank(
        rng, NCHAN, NTIME, freqs, geo["f_hi"], geo["tsamp"],
        [(dms[d], b, drifts[t], 0.03) for d, t, b in injected.get(i, [])])
        for i in range(13)]

    def serve(devices, payloads, **kw):
        tracer = Tracer()
        svc = FFTService(TPU_V5E, devices=devices, tracer=tracer,
                         batch_bytes={BATCH_BYTES}, **kw)
        reqs = [svc.submit(p, **KW) for p in payloads]
        svc.drain()
        recs = [svc.receipt(r) for r in reqs]
        return svc, reqs, recs, tracer.spans

    def spans_of(spans):
        return [dict(name=s.name, parent=s.parent, **{{k: v for k, v in
                     s.attrs.items() if k in ("worker", "batch_id", "rows",
                     "pad_rows", "d2d_bytes", "payload_bytes")}})
                for s in spans]

    devs = jax.devices()
    out = {{}}
    svc, reqs, recs, spans = serve(devs[:4], beams)
    one = serve(devs[:1], beams)[2]
    found = []
    for i, (req, rec) in enumerate(zip(reqs, recs)):
        got = np.asarray(rec.result).reshape(-1, 5)
        got = got[got[:, 0] >= 0]
        want = ref.candidate_stats(beams[i], got[:, :4], **geo)
        gap = np.abs(got[:, 4] - want) / np.maximum(np.abs(want), 1e-30)
        found.append(dict(
            worker=rec.worker, status=rec.status,
            on_worker=req.x.devices() == {{devs[rec.worker]}},
            result_on_worker=rec.result.devices() == {{devs[rec.worker]}},
            cells=[[int(v) for v in c[:3]] for c in got],
            gap=float(gap.max(initial=0.0)),
            same_bits=np.asarray(rec.result).tobytes()
            == np.asarray(one[i].result).tobytes()))
    out["beams"] = found
    out["injected"] = {{str(k): v for k, v in injected.items()}}
    out["spans"] = spans_of(spans)
    out["steals"] = svc.dispatcher.steals

    # A forced steal: every payload already on the first device, so the
    # other workers, finding their queues empty, steal from its queue.
    held = [jax.device_put(b, devs[0]) for b in beams[:4]]
    svc, reqs, recs, spans = serve(devs[:4], held)
    out["steal"] = dict(
        spans=spans_of(spans), steals=svc.dispatcher.steals,
        workers=[r.worker for r in recs],
        results_on_worker=[r.result.devices() == {{devs[r.worker]}}
                           for r in recs])

    # Which payloads each submit waited for, by request index.
    from jax._src import array as jarray
    ready, waited = jarray.ArrayImpl.block_until_ready, []

    def spy(self):
        waited.append(self)
        return ready(self)

    jarray.ArrayImpl.block_until_ready = spy
    svc = FFTService(TPU_V5E, devices=devs[:4], batch_bytes={BATCH_BYTES})
    reqs, copies = [], []
    for p in beams + ["drain", beams[0]]:
        if isinstance(p, str):
            svc.drain()
            continue
        del waited[:]
        reqs.append(svc.submit(p, **KW))
        copies.append(dict(home=reqs[-1].home, waited=[
            i for i, r in enumerate(reqs) if any(w is r.x for w in waited)]))
    jarray.ArrayImpl.block_until_ready = ready
    svc.drain()
    out["copies"] = copies
    print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def served():
    out = run_with_devices(_SERVE, n_devices=4)
    line = next(l for l in out.splitlines() if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def _total(spans, names, key):
    return sum(s.get(key, 0) for s in spans if s["name"] in names)


def test_thirteen_beams_match_the_reference_and_one_worker(served):
    beams = served["beams"]
    assert all(b["status"] == "served" for b in beams)
    assert max(b["gap"] for b in beams) <= 1.2e-6
    for i, cells in served["injected"].items():
        found = {tuple(c) for c in beams[int(i)]["cells"]}
        assert {tuple(c) for c in cells} <= found, (i, found)
    assert all(b["same_bits"] for b in beams)


def test_every_payload_sits_on_the_chip_that_runs_it(served):
    beams = served["beams"]
    assert all(b["on_worker"] and b["result_on_worker"] for b in beams)
    # round robin: 4-3-3-3 beams over the four workers, no steal
    assert [b["worker"] for b in beams] == [i % 4 for i in range(13)]
    assert served["steals"] == 0
    spans = served["spans"]
    assert any("d2d_bytes" in s for s in spans)
    assert _total(spans, ("stack", "h2d"), "d2d_bytes") == 0
    submits = [s for s in spans if s["name"] == "submit"]
    assert [s["worker"] for s in submits] == [i % 4 for i in range(13)]


def test_each_chip_has_one_host_copy_in_flight(served):
    copies = served["copies"]
    last = {}
    for i, c in enumerate(copies[:13]):
        want = [last[c["home"]]] if c["home"] in last else []
        assert c["waited"] == want, (i, c)
        last[c["home"]] = i
    # a drain starts afresh: the next tick's first copy waits for none
    assert copies[13] == {"home": 0, "waited": []}


def test_a_stolen_batch_counts_its_bytes(served):
    steal = served["steal"]
    assert steal["steals"] >= 1 and all(steal["results_on_worker"])
    moved = _total(steal["spans"], ("stack", "h2d"), "d2d_bytes")
    stolen = sum(s["rows"] for s in steal["spans"]
                 if s["name"] == "collect" and s["worker"] != 0)
    assert stolen >= 1 and moved == stolen * BEAM_BYTES
    # a device payload is homed at the worker on whose device it is
    assert all(s["worker"] == 0 for s in steal["spans"]
               if s["name"] == "submit")


def test_every_worker_launches_before_any_is_waited_on(served):
    rounds = [(s["name"], s["worker"]) for s in served["spans"]
              if s["parent"] == "drain"]
    one_round = ([("batch", w) for w in range(4)]
                 + [("collect", w) for w in range(4)])
    assert rounds == one_round * 2


def test_thirteen_beams_leave_no_pad_row(served):
    pads = [s for s in served["spans"] if s["name"] == "pad"]
    assert len(pads) == 8 and _total(pads, ("pad",), "pad_rows") == 0
    rows = {}
    for s in served["spans"]:
        if s["name"] == "batch":
            rows.setdefault(s["worker"], []).append(s["rows"])
    assert rows == {0: [2, 2], 1: [2, 1], 2: [2, 1], 3: [2, 1]}


def test_three_beams_on_one_worker_are_cut_two_and_one():
    rng = np.random.default_rng(3)
    tracer = Tracer()
    svc = FFTService(TPU_V5E, devices=jax.devices()[:1], tracer=tracer,
                     batch_bytes=BATCH_BYTES)
    beams = [rng.standard_normal((NCHAN, NTIME), dtype=np.float32)
             for _ in range(3)]
    reqs = [svc.submit(b, **SEARCH) for b in beams]
    svc.drain()
    assert all(svc.receipt(r).status == "served" for r in reqs)
    assert [s.attrs["rows"] for s in tracer.spans
            if s.name == "batch"] == [2, 1]
    assert [s.attrs["pad_rows"] for s in tracer.spans
            if s.name == "pad"] == [0, 0]


@pytest.mark.parametrize("rows, cap, want", [
    ([1, 1, 1], 3, [[1, 1], [1]]),            # 3 -> 2 + 1, no pad row
    ([3, 3], 6, [[3], [3]]),                  # each padded to 4 <= 6
    ([1] * 7, 7, [[1] * 4, [1] * 3]),         # padded to 4 + 4 <= 7
    ([8192] * 4, 61035, [[8192] * 4]),        # the FFT cell: 32768 fits
    ([2, 5, 2], 4, [[2], [5], [2]]),          # oversized stays whole
], ids=["three-ones", "three-threes", "seven-ones", "fft-cell",
        "oversized"])
def test_bucketed_batches_pad_within_the_cap(rows, cap, want):
    n = 64
    reqs = [FFTRequest(x=np.zeros((b, n), np.complex64)) for b in rows]
    budget = cap * n * COMPLEX_BYTES["fp32"]
    batches = coalesce(reqs, device_name="d", batch_bytes=budget,
                       pow2_rows=True)
    assert [[r.batch for r in b.requests] for b in batches] == want
    for b in batches:
        if len(b.requests) > 1:
            assert 1 << (b.n_transforms - 1).bit_length() <= cap


def test_multibeam_rehearsal_is_correct_on_every_worker():
    out = run_with_devices(f"""
        import collections, importlib.util, json, sys
        spec = importlib.util.spec_from_file_location(
            "chipbench_run", {os.path.join(REPO, "benchmarks", "chip",
                                           "run.py")!r})
        run = sys.modules["chipbench_run"] = importlib.util.module_from_spec(
            spec)
        spec.loader.exec_module(run)
        from repro.serving import service

        workers = collections.Counter()
        store = service.FFTService._store

        def counting(self, receipt, **kw):
            workers[receipt.worker] += 1
            return store(self, receipt, **kw)

        service.FFTService._store = counting
        cell = run.load_cell("pulsar_htru_13beam_4chip", rehearse=True)
        res = run.run_cell(cell, seed=4_000_000_007, seconds=1.0,
                           trace=False, rehearse=True)
        print("RESULT", json.dumps(dict(correct=res["correct"],
                                        checks=res["checks"],
                                        workers=sorted(workers))))
    """, n_devices=4)
    line = next(l for l in out.splitlines() if l.startswith("RESULT "))
    res = json.loads(line[len("RESULT "):])
    assert res["correct"], res["checks"]
    assert res["workers"] == [0, 1, 2, 3]
