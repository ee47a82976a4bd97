"""Dry-run of the paper's OWN workload on the production mesh: the
distributed pencil FFT (batch x 32M-point transforms, n1 sharded over the
model axis) lowered + compiled on 16x16 and 2x16x16, with the same
roofline artifact as the LM cells.

  PYTHONPATH=src python -m repro.launch.fft_dryrun [--multi-pod]
"""
import argparse
import os
import gzip
import json
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import analyze_hlo
from repro.configs.fft_bench import CONFIG
from repro.fft.distributed import pencil_collective_bytes, pencil_fft
from repro.launch.mesh import force_host_devices, make_production_mesh

ART = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                   "artifacts", "dryrun")


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ART))
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    c = CONFIG
    n1, n2, b = c.pencil_n1, c.pencil_n2, c.pencil_batch
    n = n1 * n2

    x = jax.ShapeDtypeStruct((b, n1, n2), jnp.complex64)
    sharding = NamedSharding(
        mesh, P(("pod", "data") if "pod" in mesh.axis_names else "data",
                "model", None))

    fn = jax.jit(
        lambda v: pencil_fft(v, mesh, n1=n1, n2=n2, axis="model"),
        in_shardings=(sharding,), out_shardings=sharding)
    t0 = time.monotonic()
    lowered = fn.lower(x)
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    hlo_text = compiled.as_text()
    hlo = analyze_hlo(hlo_text)
    mem = compiled.memory_analysis()
    chips = mesh.devices.size
    model_flops = 5.0 * n * math.log2(n) * b
    # analytic all_to_all check (model axis = 16 devices regardless of pod)
    coll_pred = pencil_collective_bytes(b, n1, n2, 16) / (chips / 16)

    art = {
        "arch": "fft-pencil", "shape": f"c2c_{n1}x{n2}_b{b}",
        "mesh": "2x16x16" if args.multi_pod else "16x16",
        "chips": int(chips), "kind": "fft",
        "flops_per_device": float(hlo["flops"]),
        "hbm_bytes_per_device": float(hlo["bytes"]),
        "collective_bytes_per_device": float(hlo["collective_bytes"]),
        "collective_breakdown": hlo["collectives"],
        "collective_bytes_analytic": coll_pred,
        "model_flops": model_flops,
        "memory": {"argument_bytes": int(mem.argument_size_in_bytes),
                   "fits_16gb": bool(mem.argument_size_in_bytes < 16e9)},
        "compile_s": round(t_compile, 2),
    }
    tag = f"fft-pencil__{art['shape']}__{art['mesh']}"
    os.makedirs(args.out, exist_ok=True)
    with gzip.open(os.path.join(args.out, tag + ".hlo.txt.gz"), "wt") as f:
        f.write(hlo_text)
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(art, f, indent=1)
    print(f"[fft-dryrun] {tag}: coll/dev={art['collective_bytes_per_device']:.3e} "
          f"(analytic {coll_pred:.3e}) args={mem.argument_size_in_bytes/1e9:.2f}GB "
          f"compile={t_compile:.1f}s")


if __name__ == "__main__":
    main()
