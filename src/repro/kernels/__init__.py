"""Pallas TPU kernels for the paper's compute hot-spots.

Three kernels (each: <name>.py kernel + ops.py wrapper + ref.py oracle):
  poisson_encode — fused xorshift32 PRNG + comparator (paper Fig. 2)
  lif_step       — fused T-step integrate→leak→fire→reset (paper Fig. 1)
  fused_snn      — encode→LIF megakernel: the whole window in one launch,
                   spikes never written to HBM (paper §V-B locality)

Validated in interpret mode on CPU; BlockSpecs target TPU VMEM tiling.
"""

from . import fused_snn, lif_step, ops, poisson_encode, ref

__all__ = ["fused_snn", "lif_step", "ops", "poisson_encode", "ref"]
