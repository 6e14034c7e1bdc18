"""Pallas TPU kernel: tiled direct Gaussian n-body attraction.

The paper's `direct_calculation` (and its O(n^2) baseline) evaluates

    u(t_i) = sum_j w_j exp(-||t_i - s_j||^2 / delta)

over all target/source pairs.  A naive implementation is HBM-bound: every
(t_i, s_j) pair re-reads both points.  The TPU-native formulation is the
flash-attention schedule:

  * targets are tiled over the grid's parallel dimension — one (BT, 8) block
    resident in VMEM per program;
  * sources stream through the grid's arbitrary (reduction) dimension as
    lane-major (8, BS) blocks, with the lane-major (1, BT) accumulator
    revisited in place;
  * the distance matrix uses the matmul decomposition
        d^2 = |t|^2 + |s|^2 - 2 t.s,
    so the (BT, 8) x (8, BS) cross term runs on the MXU and the arithmetic
    intensity grows with the tile area instead of O(1);
  * positions are padded from 3 to 8 components (zeros) so the contraction
    is a legal MXU shape; the padding contributes 0 to every dot product.

Every block keeps a lane dimension that is a multiple of 128 (or the full
8-wide component axis): the weights are a (1, BS) row and the output a
(1, BT) row, which the weighted sum produces directly as w @ K^T.  Both
matmuls run at f32 precision — the cross term cancels against |t|^2 + |s|^2,
so a bf16 pass would cost whole percent of accuracy at 1000 um coordinates.

Block sizes default to (256, 512): VMEM footprint =
256*8*4 + 8*512*4 + 512*4 + 256*512*4 (K tile) ~ 0.56 MB << 16 MB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BT = 256     # target block (grid parallel dim)
DEFAULT_BS = 512     # source block (reduction dim)
_F32 = jax.lax.Precision.HIGHEST


def _kernel(t_ref, s_ref, w_ref, o_ref, *, inv_delta: float):
    j = pl.program_id(1)

    t = t_ref[...]                                     # (BT, 8)
    s = s_ref[...]                                     # (8, BS)
    w = w_ref[...]                                     # (1, BS)

    t2 = jnp.sum(t * t, axis=1, keepdims=True)         # (BT, 1)
    s2 = jnp.sum(s * s, axis=0, keepdims=True)         # (1, BS)
    cross = jnp.dot(t, s, precision=_F32,
                    preferred_element_type=jnp.float32)  # (BT, BS) on the MXU
    d2 = jnp.maximum(t2 + s2 - 2.0 * cross, 0.0)
    k = jnp.exp(-d2 * inv_delta)                       # (BT, BS)
    part = jax.lax.dot_general(
        w, k, (((1,), (1,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)            # (1, BT)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


def _pad_to(x: jnp.ndarray, size: int, axis: int) -> jnp.ndarray:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("delta", "bt", "bs", "interpret"))
def gaussian_nbody(targets: jnp.ndarray, sources: jnp.ndarray,
                   weights: jnp.ndarray, delta: float,
                   bt: int = DEFAULT_BT, bs: int = DEFAULT_BS,
                   interpret: bool = False) -> jnp.ndarray:
    """u(t_i) = sum_j w_j exp(-||t_i - s_j||^2/delta); Pallas-tiled.

    targets (N, 3), sources (M, 3), weights (M,) -> (N,).
    N and M are padded to the block sizes; padded sources get weight 0 and
    padded targets are sliced off.  bt and bs must be multiples of 128.
    """
    n, m = targets.shape[0], sources.shape[0]
    npad = ((n + bt - 1) // bt) * bt
    mpad = ((m + bs - 1) // bs) * bs

    t = _pad_to(_pad_to(targets.astype(jnp.float32), 8, 1), npad, 0)
    s = _pad_to(_pad_to(sources.astype(jnp.float32), 8, 1), mpad, 0).T
    w = _pad_to(weights.astype(jnp.float32), mpad, 0)[None, :]

    grid = (npad // bt, mpad // bs)
    out = pl.pallas_call(
        functools.partial(_kernel, inv_delta=1.0 / delta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, 8), lambda i, j: (i, 0)),
            pl.BlockSpec((8, bs), lambda i, j: (0, j)),
            pl.BlockSpec((1, bs), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bt), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(t, s, w)
    return out[0, :n]
