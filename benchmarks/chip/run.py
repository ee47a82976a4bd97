#!/usr/bin/env python3
"""On-chip benchmark of the FFT service: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Every run drives the served path, ``repro.serving.FFTService`` submit ->
drain -> receipt, built as the cell's configuration says, with the cell's
traffic.  It makes its inputs from ``--seed``, warms every shape the
window will use (set-up), measures for ``--seconds``, then checks what
the window served against a plain reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, last, ``checks``:
each number compared with its limit.  The same numbers end standard error.

Everything is found by name from ``BENCHMARK.json``: the cell gives its
configuration (``configs/<name>.json``) and its traffic mix
(``traffic/<name>.json``); the mix names its driver
(``traffic/<driver>.py``); each per-layer metric is read by
``metrics/<name>.py``.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  ``--rehearse-cpu`` runs the cell at the tiny sizes of
its traffic file's ``rehearse`` block on any backend and prints only a
rehearsal line, never a metric line.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
#: jax.monitoring events: a program built (compiled, or read back from
#: the persistent cache), and of those, the ones read back.
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """The machine lacks what the cell needs."""


def load_module(path: Path, name: str):
    """Import a benchmark file by path, under a name of its own."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_module(stem: str):
    return load_module(HERE / f"{stem}.py", f"chipbench_{stem}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _for_cell(metrics: list[dict], cell: str) -> list[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, *, rehearse: bool = False) -> Cell:
    """The cell ``name`` from BENCHMARK.json, with its files."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    if rehearse:
        config = {**config, **traffic.get("rehearse_config", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


def _jax_setup(config: dict):
    """Import JAX with the persistent cache inside the checkout, and with
    64-bit types as the configuration's precision states, whatever the
    environment's ``JAX_ENABLE_X64`` says."""
    import jax
    jax.config.update("jax_enable_x64", config["precision"] == "fp64")
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # Cache every program: the kernels compile in about a second each,
    # under JAX's default one-second threshold.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def _program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.serving  # noqa: F401  (fails outside a full checkout)
    return repro


class BenchTracer:
    """The service's ``tracer``: a ``repro.obs.Tracer`` on
    ``time.perf_counter`` whose spans are also profiler annotations
    (``service.<name>``), so the trace shows them on the host."""

    def __init__(self, jax):
        from repro.obs.trace import Tracer
        self._jax = jax
        self.inner = Tracer(timer=time.perf_counter)

    @property
    def spans(self):
        return self.inner.spans

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        with self._jax.profiler.TraceAnnotation(f"service.{name}"):
            with self.inner.span(name, **attrs) as s:
                yield s


@dataclasses.dataclass
class Ctx:
    """What a traffic driver gets: the service, its inputs and helpers."""

    jax: Any
    np: Any
    svc: Any
    cell: Cell
    seed: int
    seconds: float
    reference: Any

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def annotate(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader gets."""

    cell: Cell
    record: dict
    spans: list
    trace: Any            # trace.Reduced, or None
    bounds: tuple | None  # the window on the trace clock (ns)
    peak: dict | None
    work: Any
    tracemod: Any

    def span_seconds(self, name: str) -> float:
        return sum(s.duration for s in self.spans if s.name == name)

    def host_share(self) -> float | None:
        """Percent of the client's submit + drain time outside the
        service's ``execute`` spans."""
        host = self.record.get("host_s", 0.0)
        if not self.spans or host <= 0:
            return None
        return 100.0 * (1.0 - self.span_seconds("execute") / host)

    def idle_share(self) -> float | None:
        if self.trace is None or not self.trace.ops:
            return None
        lo, hi = self.bounds
        busy = self.tracemod.busy_s(self.trace, lo, hi)
        if busy <= 0:
            return None
        return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))

    def op_seconds(self, match=lambda name: True) -> float:
        if self.trace is None:
            return 0.0
        return self.tracemod.op_seconds(self.trace, *self.bounds, match)


def _device_info(jax, devices, *, traced=None) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak,
            "x64": bool(jax.config.jax_enable_x64)}
    if traced is not None:
        info.update(traced)
    return info


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, control: bool = False) -> dict:
    """One run of ``cell``; returns the result (and, with ``control``,
    the control's readings on the same samples under ``"control"``)."""
    jax = _jax_setup(cell.config)
    devices = jax.devices()
    if not rehearse:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
        if len(devices) < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} chips, JAX sees "
                         f"{len(devices)}")
    devices = devices[:cell.chips]
    import numpy as np
    _program()
    from repro.core.hardware import TPU_V5E, spec_for_device_kind
    from repro.serving import FFTService

    work = bench_module("work")
    tracemod = bench_module("trace")
    reference = bench_module("reference")
    driver = load_module(HERE / "traffic" / f"{cell.traffic['driver']}.py",
                         f"chipbench_driver_{cell.traffic['driver']}")
    peak = None if rehearse else work.peaks(devices[0].device_kind)

    builds: collections.Counter = collections.Counter()

    def on_event(event: str, *args, **kwargs):
        if event in (BUILD_EVENT, CACHE_HIT_EVENT):
            builds[event] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)

    spec = TPU_V5E if rehearse else spec_for_device_kind(
        devices[0].device_kind)
    tracer = BenchTracer(jax) if trace else None
    cfg = cell.config
    svc = FFTService(spec, devices=devices, batch_bytes=cfg["batch_bytes"],
                     coalesce_requests=cfg["coalesce_requests"],
                     bucket_batches=cfg["bucket_batches"],
                     max_retained_receipts=cfg["max_retained_receipts"],
                     tracer=tracer)
    ctx = Ctx(jax=jax, np=np, svc=svc, cell=cell, seed=seed,
              seconds=seconds, reference=reference)
    state = driver.prepare(ctx)
    if tracer is not None:
        tracer.inner.spans.clear()
    log_dir = TRACE_DIR / cell.name
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    setup_s = time.perf_counter() - T_PROCESS
    setup_builds = dict(builds)
    built0 = builds[BUILD_EVENT]
    cpu0 = time.process_time()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            record = driver.window(ctx, state)
    finally:
        if trace:
            jax.profiler.stop_trace()
    record["window_cpu_s"] = time.process_time() - cpu0
    record["window_builds"] = builds[BUILD_EVENT] - built0
    spans = list(tracer.spans) if tracer is not None else []

    red = bounds = None
    traced = None
    if trace:
        red = tracemod.load(tracemod.find_xplane(str(log_dir)))
        bounds = tracemod.window(red)
        traced = {"busy_s": tracemod.busy_s(red, *bounds),
                  "window_s": (bounds[1] - bounds[0]) / 1e9}
        shutil.rmtree(log_dir, ignore_errors=True)
    device = _device_info(jax, devices, traced=traced)

    # The reference runs once the window is over and the service is gone.
    ctx.svc = svc = None
    gc.collect()
    t_check = time.perf_counter()
    checks = driver.check(ctx, state, record)
    check_s = time.perf_counter() - t_check
    correct = (record["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks))

    if trace:
        view = RunView(cell=cell, record=record, spans=spans, trace=red,
                       bounds=bounds, peak=peak, work=work,
                       tracemod=tracemod)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 f"chipbench_metric_{m['name']}")
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = {**record["e2e"], "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": metrics,
              "device": device}
    if trace and red is not None and red.ops:
        result["breakdown"] = {
            "device_ops": tracemod.top_ops(red, *bounds),
            "idle_gaps": tracemod.idle_gaps(red, *bounds)}
    result["setup_builds"] = {k.rsplit("/", 1)[-1]: v
                              for k, v in setup_builds.items()}
    result["check_s"] = check_s
    result["window"] = {"drain_s": record["drain_s"],
                        "cpu_s": record["window_cpu_s"],
                        "wall_s": record["window_s"],
                        "builds": record["window_builds"]}
    result["checks"] = {c["name"]: {"value": float(c["value"]),
                                    "limit": float(c["limit"])}
                        for c in checks}
    if control:
        result["control"] = driver.control(ctx, state, record)
        result["setup_s"] = setup_s
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on any backend; prints no metrics")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, rehearse=args.rehearse_cpu)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace),
                          rehearse=args.rehearse_cpu)
    except NoChip as e:
        print(f"run.py: {e}; nothing was measured", file=sys.stderr)
        return 2
    print(f"set-up programs built: {result['setup_builds']}",
          file=sys.stderr)
    w = result.pop("window")
    print(f"window: {w['wall_s']:.3f} s wall, {w['cpu_s']:.3f} s of process "
          f"CPU, {w['builds']} programs built; each drain's seconds: "
          f"{[round(s, 3) for s in w['drain_s']]}", file=sys.stderr)
    print(f"reference check took {result['check_s']:.2f} s", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": args.workload,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "checks": result["checks"]}), flush=True)
    else:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # Import this directory's files by path only: ``trace.py`` would
    # shadow the standard library's module of that name.
    if sys.path and Path(sys.path[0]).resolve() == HERE:
        sys.path.pop(0)
    sys.exit(main())
