"""Brute-force incoherent dedispersion (many-DM shift-and-sum).

  dedisp_kernel  pl.pallas_call body: lane-rotation shifts driven by an
                 SMEM delay table, summed over channel slabs into a
                 VMEM-resident (D, N) output block
  ops            public wrapper (guards, batch tiling, lead-dim plumbing)
  ref            gather-based pure-jnp oracle the tests assert against
"""
from repro.kernels.dedisp.ops import dedisperse_kernel
from repro.kernels.dedisp.ref import dedisperse_ref

__all__ = ["dedisperse_kernel", "dedisperse_ref"]
