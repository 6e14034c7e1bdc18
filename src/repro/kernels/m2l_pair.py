"""Pallas TPU kernel: separable M2L pair evaluation (the Taylor tier).

For a batch of (source-box, target-box) pairs the traversal needs

    series(pair) = sum_{alpha,beta} sign_alpha A_alpha (moms_beta / beta!)
                   * prod_d H_{alpha_d + beta_d}(y_d)

with y the scaled center offset (the exp(-||y||^2) envelope is applied
outside, in log space).  The translation tensor factorises per dimension, so
the kernel computes, per pair, three (p x p) Hankel matrices from the per-dim
Hermite-polynomial recurrence and applies three mode products — O(3 p^4)
instead of the dense O(p^6) (see expansions.box_mass_taylor_log).

TPU layout: the PAIR axis fills whole vector registers.  The wrapper lays
the coefficient tensors out as (k, 8, B/8) — coefficient index leading,
pairs folded onto 8 sublanes x B/8 lanes — so every coefficient of a block
is one dense (8, L) tile, read by an index on the untiled leading axis.
The mode products are then unrolled over the coefficient indices as
(8, L)-tile FMAs: no reshape, lane shuffle or gather inside the kernel.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import multi_index as mi

DEFAULT_BP = 2048          # pairs per block: 8 sublanes x 256 lanes
P = 4                      # expansion order per dim (paper: alpha <= (3,3,3))
K = P ** 3


def _kernel(moms_ref, herm_ref, y_ref, out_ref, *, p: int):
    big_p = 2 * p - 1
    k = p ** 3
    shape = (p, p, p)
    # moms arrives pre-divided by beta!, herm pre-multiplied by sign_alpha
    # (folded in by the wrapper so the kernel captures no constants).
    t = [moms_ref[c] for c in range(k)]                # k x (8, L), (b1 b2 b3)

    # Per-dim Hermite polynomials H_0..H_{2p-2} of y_d, by recurrence.
    hs = []
    for d in range(3):
        yd = y_ref[d]                                  # (8, L)
        cols = [jnp.ones_like(yd)]
        if big_p > 1:
            cols.append(2.0 * yd)
        for nn in range(1, big_p - 1):
            cols.append(2.0 * yd * cols[-1] - 2.0 * nn * cols[-2])
        hs.append(cols)

    # Three mode products over the row-major (n1, n2, n3) coefficient index:
    # contract index `dim` with G[a, b] = H_{a+b}(y_dim), writing a in place.
    def mode_product(tensor, dim, cols):
        out = []
        for flat in range(k):
            idx = list(np.unravel_index(flat, shape))
            a_i = idx[dim]
            acc = None
            for b_i in range(p):
                idx[dim] = b_i
                term = cols[a_i + b_i] * tensor[np.ravel_multi_index(idx,
                                                                     shape)]
                acc = term if acc is None else acc + term
            out.append(acc)
        return out

    for d in range(3):
        t = mode_product(t, d, hs[d])

    acc = herm_ref[0] * t[0]
    for c in range(1, k):
        acc = acc + herm_ref[c] * t[c]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("p", "bp", "interpret"))
def m2l_separable(moms: jnp.ndarray, herm: jnp.ndarray, y: jnp.ndarray,
                  p: int = P, bp: int = DEFAULT_BP,
                  interpret: bool = False) -> jnp.ndarray:
    """Batched separable M2L series.  moms/herm (B, p^3), y (B, 3) -> (B,).

    bp: pairs per grid step, a multiple of 1024 (8 sublanes x 128 lanes).
    """
    b = moms.shape[0]
    bpad = ((b + bp - 1) // bp) * bp
    k = p ** 3
    fact = jnp.asarray(np.asarray(mi.multi_factorial(p), np.float32))
    sign = jnp.asarray(np.asarray(mi.sign_table(p), np.float32))
    # (B, c) -> (c, 8, B/8): coefficient-major, pairs on sublanes x lanes.
    tiles = lambda x: jnp.pad(x, ((0, bpad - b), (0, 0))).T.reshape(
        x.shape[1], 8, bpad // 8)
    moms_t = tiles(moms.astype(jnp.float32) / fact)
    herm_t = tiles(herm.astype(jnp.float32) * sign)
    y_t = tiles(y.astype(jnp.float32))

    lanes = bp // 8
    spec = lambda c: pl.BlockSpec((c, 8, lanes), lambda i: (0, 0, i))
    out = pl.pallas_call(
        functools.partial(_kernel, p=p),
        grid=(bpad // bp,),
        in_specs=[spec(k), spec(k), spec(y.shape[1])],
        out_specs=pl.BlockSpec((8, lanes), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, bpad // 8), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(moms_t, herm_t, y_t)
    return out.reshape(bpad)[:b]
