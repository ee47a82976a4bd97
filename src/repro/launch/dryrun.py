"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces a JSON artifact with:
  * memory_analysis (proves the cell fits 16 GB/chip),
  * cost_analysis FLOPs / bytes (per-device, partitioned module),
  * collective bytes parsed from the compiled HLO,
  * MODEL_FLOPS (6*N*D accounting) for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all          # every cell, both meshes
"""
import argparse
import os
import gzip
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import model_flops_for
from repro.configs import ARCHS, get_arch, get_shape, shapes_for
from repro.launch.mesh import force_host_devices, make_production_mesh
from repro.launch.specs import fix_tree, input_specs
from repro.models.api import build_model
from repro.obs.log import get_logger

log = get_logger("dryrun")

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _state_sds(model):
    """ShapeDtypeStructs of the full TrainState without allocating."""
    from repro.optim.adamw import AdamWState
    from repro.train.step import TrainState
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return TrainState(
        params=params,
        opt=AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32),
                       m=jax.tree.map(f32, params),
                       v=jax.tree.map(f32, params)),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )


def _strip_data_axis(spec_tree):
    """TP-only weights: remove the ZeRO/FSDP 'data' axis from param specs.

    §Perf optimisation for serving cells: at decode there is no optimizer
    state to shard and weights are read every step, so FSDP-style weight
    sharding only buys an all-gather per matmul.  Replicating over 'data'
    (keeping TP over 'model') removes that collective for +P*2/16 bytes of
    HBM per device.
    """
    def fix(s):
        parts = []
        for e in s:
            if e == "data":
                parts.append(None)
            elif isinstance(e, tuple):
                t = tuple(a for a in e if a != "data")
                parts.append(t if t else None)
            else:
                parts.append(e)
        return P(*parts)
    return jax.tree.map(fix, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _strip_data_axis_nonmoe(spec_tree):
    """serve_tp_only for MoE giants: expert tables stay 2-D sharded (they
    do not fit replicated over 'data'); everything else goes TP-only."""
    if isinstance(spec_tree, dict):
        return {k: (v if k == "moe" else _strip_data_axis_nonmoe(v))
                for k, v in spec_tree.items()}
    if isinstance(spec_tree, list):
        return [_strip_data_axis_nonmoe(v) for v in spec_tree]
    return _strip_data_axis(spec_tree)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               opts: tuple[str, ...] = ()):
    from repro.models import common as cm
    cm.PERF_OPTS.clear()
    cm.PERF_OPTS.update(opts)
    cfg = get_arch(arch)
    if "moe_group_128" in opts and cfg.moe is not None:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, group_size=128))
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    specs = input_specs(cfg, shape, mesh)

    if shape.kind == "train":
        from repro.train.step import make_train_step, train_state_specs
        step_fn = make_train_step(model)
        state_sds = _state_sds(model)
        sspecs = train_state_specs(model)
        if "attn_tp_only" in opts:
            # §Perf: attention weights TP-only (no ZeRO sharding) — trades
            # +attn_params*10/16 bytes of optimizer memory per device for
            # removing the per-layer FSDP weight all-gathers.
            import dataclasses as _dc
            def _fix_tree_part(t):
                if isinstance(t, dict):
                    return {k: (_strip_data_axis(v) if k == "attn"
                                else _fix_tree_part(v))
                            for k, v in t.items()}
                if isinstance(t, list):
                    return [_fix_tree_part(v) for v in t]
                return t
            sspecs = _dc.replace(
                sspecs,
                params=_fix_tree_part(sspecs.params),
                opt=_dc.replace(sspecs.opt,
                                m=_fix_tree_part(sspecs.opt.m),
                                v=_fix_tree_part(sspecs.opt.v)))
        state_sh = fix_tree(state_sds, sspecs, mesh)
        in_sh = (state_sh, specs["inputs"][1], specs["labels"][1])
        args = (state_sds, specs["inputs"][0], specs["labels"][0])
        jitted = jax.jit(step_fn, in_shardings=in_sh,
                         out_shardings=(state_sh, None), donate_argnums=(0,))
    elif shape.kind == "prefill":
        params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs = model.param_specs()
        if "serve_tp_only" in opts:
            pspecs = _strip_data_axis(pspecs)
        params_sh = fix_tree(params_sds, pspecs, mesh)
        cache_sh = _shardings(
            mesh, jax.tree.map(lambda x: x[1].spec if isinstance(x, tuple)
                               else x, model.cache_specs(),
                               is_leaf=lambda x: isinstance(x, P)))
        jitted = jax.jit(model.prefill,
                         in_shardings=(params_sh, specs["inputs"][1]),
                         out_shardings=None)
        args = (params_sds, specs["inputs"][0])
    else:  # decode
        params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs = model.param_specs()
        if "serve_tp_only" in opts:
            pspecs = _strip_data_axis(pspecs)
        params_sh = fix_tree(params_sds, pspecs, mesh)
        cache_sds = jax.tree.map(lambda t: t[0], specs["cache"],
                                 is_leaf=lambda t: isinstance(t, tuple))
        cache_sh = jax.tree.map(lambda t: t[1], specs["cache"],
                                is_leaf=lambda t: isinstance(t, tuple))
        jitted = jax.jit(model.decode,
                         in_shardings=(params_sh, cache_sh,
                                       specs["token"][1]),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,))
        args = (params_sds, cache_sds, specs["token"][0])

    from repro.models.common import activation_sharding
    from repro.launch.mesh import batch_axes

    t0 = time.monotonic()
    with activation_sharding(mesh, batch_axes(mesh)):
        lowered = jitted.lower(*args)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    hlo = analyze_hlo(hlo_text)

    chips = mesh.devices.size
    # Scan-carry residency estimate (the part of TPU temp memory that does
    # not disappear with buffer reuse): per-layer hidden saved for backward,
    # sharded per the SP activation constraint (batch x seq over the mesh).
    if shape.kind == "train":
        shards = chips
        carry_est = (cfg.n_layers * shape.global_batch * shape.seq_len
                     * cfg.d_model * 2) / shards
    else:
        carry_est = 0.0
    args_bytes = int(mem.argument_size_in_bytes)
    out_bytes = int(mem.output_size_in_bytes)
    # train state / decode cache outputs are DONATED (alias their input
    # buffers), so arguments + scan carries bound the persistent footprint.
    fits = (args_bytes + carry_est) * 1.15 < 16e9
    artifact = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(chips),
        "kind": shape.kind,
        # trip-count-aware HLO analysis (see repro.analysis.hlo): the CPU
        # backend's cost_analysis counts while bodies once, so raw values
        # are recorded separately below.
        "flops_per_device": float(hlo["flops"]),
        "hbm_bytes_per_device": float(hlo["bytes"]),
        "collective_bytes_per_device": float(hlo["collective_bytes"]),
        "collective_breakdown": hlo["collectives"],
        "raw_cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "model_flops": model_flops_for(cfg, shape),
        "memory": {
            "argument_bytes": args_bytes,
            "output_bytes": out_bytes,
            # CPU buffer assignment does not reuse across loop iterations
            # the way the TPU assigner does; recorded for completeness.
            "temp_bytes_cpu_backend": int(mem.temp_size_in_bytes),
            "scan_carry_estimate": int(carry_est),
            "fits_16gb": bool(fits),
        },
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    return artifact, hlo_text


def run_one(arch, shape_name, multi_pod, out_dir, opts=()):
    art, hlo_text = lower_cell(arch, shape_name, multi_pod=multi_pod,
                               opts=tuple(opts))
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
    if opts:
        art["opts"] = sorted(opts)
        tag += "__" + "+".join(sorted(opts))
    path = os.path.join(out_dir, tag + ".json")
    with gzip.open(os.path.join(out_dir, tag + ".hlo.txt.gz"), "wt") as f:
        f.write(hlo_text)
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    log.info("lowered", tag=tag,
             args_gb=art["memory"]["argument_bytes"] / 1e9,
             fits=art["memory"]["fits_16gb"],
             flops_per_dev=art["flops_per_device"],
             coll_per_dev=art["collective_bytes_per_device"],
             compile_s=art["compile_s"])
    return path


def run_all(out_dir: str, multi_pod_only: bool = False):
    """Loop every cell in a fresh subprocess (isolated device state)."""
    cells = []
    for cfg in ARCHS.values():
        for shp in shapes_for(cfg):
            for mp in ((True,) if multi_pod_only else (False, True)):
                cells.append((cfg.name, shp.name, mp))
    failures = []
    for arch, shp, mp in cells:
        tag = f"{arch}__{shp}__{'2x16x16' if mp else '16x16'}"
        if os.path.exists(os.path.join(out_dir, tag + ".json")):
            log.info("cached-skip", tag=tag)
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shp, "--out", out_dir]
        if mp:
            cmd.append("--multi-pod")
        r = subprocess.run(cmd)
        if r.returncode != 0:
            failures.append(tag)
            log.error("cell-failed", tag=tag)
    log.info("done", n_failures=len(failures),
             failures=",".join(failures) or "-")
    return failures


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS))
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    ap.add_argument("--opt", action="append", default=[],
                    help="enable a named §Perf optimisation (repeatable)")
    args = ap.parse_args()
    if args.all:
        failures = run_all(args.out)
        sys.exit(1 if failures else 0)
    run_one(args.arch, args.shape, args.multi_pod, args.out, args.opt)


if __name__ == "__main__":
    main()
