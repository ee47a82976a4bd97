"""Closed-loop blocks of the real-time pulsar search.

Each drain submits ``filterbanks`` filterbanks of (nchan, ntime) float32,
made on the host from the seed at set-up and reused: unit noise plus
dispersed, accelerated tones injected at a (DM trial, template, bin) of
the search grid, as ``chip_smoke.py`` injects them.  Bins scale with the
block length and move by up to ``bin_jitter`` bins, drawn from the seed;
the amplitude scales as 1/sqrt(channels x samples), which holds each
pulsar's expected power fixed.  The window ends when the last drain
started within ``--seconds`` completes; ``realtime_x`` is the seconds of
observation searched per second of window.

``correct``, once the window has closed, over every drain's candidates:

* ``stat_rel_gap``: the widest relative gap between a served candidate's
  statistic and the float64 reference's statistic at the same (DM trial,
  template, bin, harmonic level);
* ``sift_false``: served candidates that the configuration's sift, run on
  the reference's statistic, would not put out (limit 0): more than
  ``max_candidates`` in a block, a cell served twice, a best statistic
  under ``sift_threshold``, or a stronger cell within ``sift_dm_tol`` DM
  trials whose bin is related (adjacent or harmonic) and so absorbs it.
  A stronger cell is always in the sift's pool when the weaker one is,
  so the reference's planes at the candidate's DM trial and its
  neighbours decide it.  "Under" and "stronger" are by more than the
  ``stat_rel_gap`` limit, so rounding never decides;
* ``injected_missed``: injected pulsars absent from the served
  candidates (limit 0).
"""
from __future__ import annotations

import time


def _geometry(ctx):
    c = ctx.config
    return dict(nchan=c["nchan"], f_lo=c["f_lo_mhz"], f_hi=c["f_hi_mhz"],
                tsamp=c["tsamp_s"], dm_trials=c["dm_trials"],
                dm_spacing=float(c["dm_step_samples"]),
                n_templates=c["templates"])


def _injections(ctx, rng):
    """Per filterbank: [(dm trial, template, bin), ...]."""
    t, ntime = ctx.traffic, ctx.config["ntime"]
    scale = ntime // t["injected_ntime"]
    jitter = t["bin_jitter"]
    out = []
    for inj in t["injected"][:t["filterbanks"]]:
        out.append([(d, tm, b * scale
                     + int(rng.integers(-jitter, jitter + 1)))
                    for d, tm, b in inj])
    return out


def _filterbanks(ctx, rng, injected):
    np, ref, c = ctx.np, ctx.reference, ctx.config
    g = _geometry(ctx)
    nchan, ntime = c["nchan"], c["ntime"]
    amp = ctx.traffic["amp_at_64x16384"] * np.sqrt(64 * 2**14
                                                   / (nchan * ntime))
    freqs = ref.channel_freqs(nchan, g["f_lo"], g["f_hi"])
    dms = ref.trial_dms(g["dm_trials"], g["f_lo"], g["f_hi"], g["tsamp"],
                        g["dm_spacing"])
    drifts = ref.template_drifts(g["n_templates"])
    return [ref.inject_filterbank(
        rng, nchan, ntime, freqs, g["f_hi"], g["tsamp"],
        [(dms[d], b, drifts[tm], amp) for d, tm, b in inj])
        for inj in injected]


def _submit_kw(ctx):
    c = ctx.config
    return dict(kind="pulsar", precision=c["precision"],
                dm_trials=c["dm_trials"], templates=c["templates"],
                n_harmonics=c["n_harmonics"])


def _serve(ctx, payloads):
    svc, kw = ctx.svc, _submit_kw(ctx)
    with ctx.annotate("bench.submit"):
        reqs = [svc.submit(p, **kw) for p in payloads]
    with ctx.annotate("bench.drain"):
        svc.drain()
    return [svc.receipt(r) for r in reqs]


def prepare(ctx):
    rng = ctx.np.random.default_rng(ctx.seed)
    injected = _injections(ctx, rng)
    payloads = _filterbanks(ctx, rng, injected)
    recs = _serve(ctx, payloads)
    ctx.jax.block_until_ready([r.result for r in recs])
    return {"payloads": payloads, "injected": injected}


def window(ctx, state):
    jax, c = ctx.jax, ctx.config
    payloads = state["payloads"]
    drains = failed = 0
    host_s = 0.0
    results, drain_s = [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        h0 = time.perf_counter()
        recs = _serve(ctx, payloads)
        host_s += time.perf_counter() - h0
        failed += sum(1 for r in recs
                      if r is None or r.status != "served")
        with ctx.annotate("bench.wait"):
            jax.block_until_ready([r.result for r in recs])
        results.append([r.result for r in recs])
        drain_s.append(time.perf_counter() - h0)
        drains += 1
    elapsed = time.perf_counter() - t0
    observed = drains * len(payloads) * c["ntime"] * c["tsamp_s"]
    return {"attempted": drains * len(payloads), "failed": failed,
            "e2e": {"realtime_x": observed / elapsed},
            "window_s": elapsed, "host_s": host_s, "drains": drains,
            "drain_s": drain_s,
            "filterbanks": drains * len(payloads), "results": results}


def _served(ctx, record):
    """[(filterbank, [(d, t, b, lev, stat), ...]), ...] over all drains."""
    np = ctx.np
    out = []
    for drain in record["results"]:
        for i, res in enumerate(drain):
            cands = np.asarray(res).reshape(-1, 5)
            out.append((i, [tuple(float(v) for v in c) for c in cands
                            if c[0] >= 0]))
    return out


def _gap(ctx, state, record, control: bool) -> float:
    np, ref = ctx.np, ctx.reference
    g = _geometry(ctx)
    worst = 0.0
    cache: dict = {}
    for i, cands in _served(ctx, record):
        cells = [tuple(int(v) for v in c[:4]) for c in cands]
        todo = [cell for cell in cells if (i, cell) not in cache]
        if todo:
            want = ref.candidate_stats(state["payloads"][i], todo, **g)
            for cell, w in zip(todo, want):
                cache[(i, cell)] = [w, None]
        if control:
            todo = [cell for cell in cells if cache[(i, cell)][1] is None]
            if todo:
                got = ref.candidate_stats(state["payloads"][i], todo,
                                          control=True, **g)
                for cell, v in zip(todo, got):
                    cache[(i, cell)][1] = v
        for cell, c in zip(cells, cands):
            want, low = cache[(i, cell)]
            got = low if control else c[4]
            worst = max(worst, abs(got - want) / max(abs(want), 1e-30))
    return worst


def _missed(ctx, state, record) -> int:
    missed = 0
    for i, cands in _served(ctx, record):
        found = {(int(c[0]), int(c[1]), int(c[2])) for c in cands}
        missed += sum(1 for inj in state["injected"][i]
                      if tuple(inj) not in found)
    return missed


def _sift_false(ctx, state, record) -> int:
    np, ref, c = ctx.np, ctx.reference, ctx.config
    g = _geometry(ctx)
    rounding = ctx.traffic["limits"]["stat_rel_gap"]
    planes: dict = {}

    def plane(i, d):
        if (i, d) not in planes:
            planes[(i, d)] = ref.stat_plane(
                state["payloads"][i], d, n_harmonics=c["n_harmonics"],
                **g)[0]
        return planes[(i, d)]

    false = 0
    for i, cands in _served(ctx, record):
        false += max(0, len(cands) - c["max_candidates"])
        seen = set()
        for cand in cands:
            d, t, b = (int(v) for v in cand[:3])
            want = plane(i, d)[t, b]
            margin = rounding * abs(want)
            near = ref.related_bins(b, plane(i, d).shape[-1],
                                    c["sift_bin_tol"], c["n_harmonics"])
            dms = range(max(0, d - c["sift_dm_tol"]),
                        min(c["dm_trials"], d + c["sift_dm_tol"] + 1))
            stronger = max(float(np.max(plane(i, dd)[:, near]))
                           for dd in dms)
            false += ((d, t, b) in seen
                      or want < c["sift_threshold"] - margin
                      or stronger > want + margin)
            seen.add((d, t, b))
    return false


def check(ctx, state, record):
    lim = ctx.traffic["limits"]
    return [{"name": "stat_rel_gap",
             "value": _gap(ctx, state, record, False),
             "limit": lim["stat_rel_gap"]},
            {"name": "sift_false",
             "value": float(_sift_false(ctx, state, record)),
             "limit": lim["sift_false"]},
            {"name": "injected_missed",
             "value": float(_missed(ctx, state, record)),
             "limit": lim["injected_missed"]}]


def control(ctx, state, record):
    """The control's reading at the served cells: the same statistic
    computed in float32 with every product one matmul precision below the
    configuration's."""
    ctx.reference.control_precision(ctx.config["matmul_precision"])
    return {"stat_rel_gap": _gap(ctx, state, record, True)}
