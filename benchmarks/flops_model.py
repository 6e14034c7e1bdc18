"""Closed-form flop / HBM-byte counts of the three Pallas kernels.

`benchmarks.figures.fig_kernels` places each kernel tier on the TPU v5e
roofline with these counts (peaks from `benchmarks/chip/peaks.json`), and
tests/test_roofline.py checks them against `cost_analysis()`.  The chip
benchmark counts the same bytes again in `benchmarks/chip/kernel_cost.py`;
this module goes with the CPU figures when they are retired.

The counts are of what actually runs: the gaussian kernel counts its padded
8-lane matmul decomposition (not the 3-component textbook distance), m2l
counts the unrolled mode-product FMAs, and msp counts the fused elementwise
chain.  exp() counts as one flop.
"""
from __future__ import annotations

from typing import Dict


def kernel_cost_gaussian_nbody(n: int, m: int) -> Dict[str, float]:
    """Tiled exact attraction: (n,3) targets x (m,3) weighted sources."""
    lanes = 8                        # positions padded 3 -> 8 lanes
    flops = float(n) * m * (2.0 * lanes   # cross term matmul
                            + 6.0)        # d2 combine, max, scale, exp, mac
    bytes_ = 4.0 * (n * lanes + m * lanes + m   # padded t, s + weights read
                    + n)                        # output write
    return {"flops": flops, "hbm_bytes": bytes_}


def kernel_cost_m2l(b: int, p: int = 4) -> Dict[str, float]:
    """Separable M2L series over b box pairs at order p (k = p^3 coeffs)."""
    k = p ** 3
    recur = 3.0 * (2 * p - 2) * 4.0          # per-dim Hermite recurrence
    modes = 3.0 * 2.0 * p ** 4               # three (p x p) mode products
    reduce_ = 2.0 * k                        # final coeff contraction
    flops = float(b) * (recur + modes + reduce_)
    bytes_ = 4.0 * b * (k + k + 8            # moms, herm, padded y read
                        + 1)                 # series write
    return {"flops": flops, "hbm_bytes": bytes_}


def kernel_cost_msp_update(n: int) -> Dict[str, float]:
    """Fused phase-1 neuron update over n neurons."""
    flops = 12.0 * n                         # decay, input, draw, refrac, ca
    bytes_ = 4.0 * (5 * n                    # x, refrac, ca, syn, u read
                    + 4 * n)                 # x', refrac', spike, ca' write
    return {"flops": flops, "hbm_bytes": bytes_}
