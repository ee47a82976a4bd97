"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \\
      --reduced --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Wires together: config -> model -> sharded train state -> synthetic data
-> fault-tolerant loop (checkpoint/restart) -> DVFS clock plan.

The DVFS integration is the paper's Sec. 5.3 made first-class: after the
step is compiled, its roofline profile decides the energy-optimal TPU
clock; on hardware the runtime would lock/unlock around dispatch (NVML
analogue), here the plan and its predicted savings are reported alongside
training metrics.
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.core.dvfs import sweep
from repro.core.hardware import TPU_V5E
from repro.core.workloads import roofline_workload
from repro.data.synthetic import SyntheticTokens
from repro.models.api import build_model
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.fault import FaultTolerantDriver
from repro.train.step import (init_train_state, make_train_step,
                              train_state_specs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh", default="1x1",
                    help="data x model mesh, e.g. 4x2 (needs devices)")
    ap.add_argument("--dvfs-report", action="store_true",
                    help="print the energy-optimal clock plan for the step")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)

    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = jax.make_mesh((d, m), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    state = init_train_state(model, jax.random.PRNGKey(0))
    specs = train_state_specs(model)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, shardings)

    step_fn = jax.jit(
        make_train_step(model, microbatches=args.microbatches,
                        peak_lr=args.lr),
        in_shardings=(shardings, NamedSharding(mesh, P("data", None)),
                      NamedSharding(mesh, P("data", None))),
        donate_argnums=(0,),
    )

    ds = SyntheticTokens(cfg.vocab, args.seq, args.batch)

    def data(i):
        b = jnp.asarray(ds.batch(i))
        return b[:, :-1], b[:, 1:]

    driver = FaultTolerantDriver(
        train_step=step_fn, state=state, data_iter_fn=data,
        ckpt=CheckpointManager(args.ckpt_dir), ckpt_every=args.ckpt_every,
    )
    final_state, log, restarts = driver.run(args.steps)
    for mrow in log[:: max(len(log) // 20, 1)]:
        print(f"step {mrow['step']:5d}  loss {float(mrow['loss']):.4f}  "
              f"lr {float(mrow['lr']):.2e}  wall {mrow['wall']*1e3:.1f} ms")
    print(f"[train] done: {args.steps} steps, {restarts} restarts, "
          f"final loss {float(log[-1]['loss']):.4f}")

    if args.dvfs_report:
        # Roofline profile of the compiled step -> energy-optimal clock.
        lowered = step_fn.lower(state, *data(0))
        compiled = lowered.compile()
        from repro.analysis.hlo import analyze_hlo
        h = analyze_hlo(compiled.as_text())
        prof = roofline_workload(
            f"train-{cfg.name}", TPU_V5E, hlo_flops=h["flops"],
            hbm_bytes=h["bytes"], collective_bytes=h["collective_bytes"],
            issue_efficiency=0.8)
        res = sweep(prof, TPU_V5E)
        print(f"[dvfs] bound={prof.regime(TPU_V5E)!r} "
              f"optimal={res.optimal.f:.0f} MHz "
              f"({100*res.optimal.f/TPU_V5E.f_max:.0f}% of boost), "
              f"power cut {100*res.power_reduction:.0f}%, "
              f"slowdown {100*res.slowdown:.1f}%, I_ef {res.i_ef_boost:.2f}")
    return final_state


if __name__ == "__main__":
    main()
