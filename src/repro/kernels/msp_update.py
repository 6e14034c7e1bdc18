"""Pallas TPU kernel: fused MSP phase-1/2 neuron update.

The 500 000-step outer loop applies, per neuron: membrane decay + input,
spike draw, refractory bookkeeping, and the calcium trace.  Unfused, that is
6+ HBM round-trips of (n,)-arrays per step; fused it is one read + one write
per array — the step becomes bandwidth-minimal.  (XLA usually fuses these
too; the kernel makes the schedule explicit, streams each array through
VMEM in blocks, and is the anchor point for the multi-step in-VMEM variant noted in
EXPERIMENTS.md §Perf.)

All model constants are baked in as compile-time scalars (they never change
within a run).  int32 refractory counters and a float spike mask keep every
block a plain (BN,) vector op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Neurons per block.  The arrays stay in HBM (`_call`), so the kernel's time
# is its DMAs': on a v5e, 131,072 neurons take ~6.8 us a step in blocks of
# 65,536 (256 KiB a DMA) and ~22 us in blocks of 2,048 (8 KiB a DMA).
DEFAULT_BN = 65536


def _kernel(x_ref, refrac_ref, ca_ref, syn_ref, u_ref,
            x_out, refrac_out, spk_out, ca_out, *,
            x0, tau_x, background, w_syn, beta_ca, tau_ca, refractory):
    x = x_ref[...]
    refrac = refrac_ref[...]
    ca = ca_ref[...]

    # Divide (not multiply by a reciprocal): ref.msp_update and
    # msp.step_neurons divide, and the ulp difference of 1/tau_x would flip
    # marginal spike draws (u < x) — the engine-level parity contract is
    # bitwise on the spike stream (DESIGN.md §11).
    x_new = x + (x0 - x) / tau_x + background + w_syn * syn_ref[...]
    spiked = (u_ref[...] < x_new) & (refrac <= 0)
    spk_f = spiked.astype(x.dtype)

    x_out[...] = x_new
    refrac_out[...] = jnp.where(spiked, refractory,
                                jnp.maximum(refrac - 1, 0))
    spk_out[...] = spk_f
    ca_out[...] = ca * (1.0 - tau_ca) + beta_ca * spk_f


@functools.partial(jax.jit, static_argnames=(
    "x0", "tau_x", "background", "w_syn", "beta_ca", "tau_ca", "refractory",
    "bn", "interpret"))
def msp_update(x, refrac, calcium, syn_input, uniform, *,
               x0, tau_x, background, w_syn, beta_ca, tau_ca, refractory,
               bn: int = DEFAULT_BN, interpret: bool = False):
    """Fused neuron update.  All inputs (n,); returns (x', refrac', spiked_f32,
    calcium').

    Under `vmap` (K replicas, core/ensemble.py) the update stays one (K*n,)
    call of the same kernel: it is per neuron and its constants are shared,
    so the replica axis folds into the neuron axis.  Batching the
    `pallas_call` itself would block each replica's row as
    (Squeezed, bn), which Mosaic refuses (its last two block dimensions
    must tile by 8 x 128)."""
    kernel = functools.partial(
        _kernel, x0=x0, tau_x=tau_x, background=background, w_syn=w_syn,
        beta_ca=beta_ca, tau_ca=tau_ca, refractory=refractory)

    @custom_batching.custom_vmap
    def update(*args):
        return _call(kernel, args, bn, interpret)

    @update.def_vmap
    def _replicas(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        outs = update(*(a.reshape(-1) for a in args))
        return tuple(o.reshape(axis_size, -1) for o in outs), (True,) * 4

    return update(x, refrac, calcium, syn_input, uniform)


def _call(kernel, args, bn: int, interpret: bool):
    x, refrac, calcium = args[:3]
    n = x.shape[0]
    # As many blocks as bn asks for, each whole 1024-lane tiles: a block
    # pads less than one tile.
    grid = -(-n // bn)
    bn = -(-n // (grid * 1024)) * 1024
    npad = grid * bn
    pad = lambda a: jnp.pad(a, (0, npad - n))
    ins = [pad(a) for a in args]
    out_shape = [jax.ShapeDtypeStruct((npad,), dt) for dt in
                 (x.dtype, refrac.dtype, x.dtype, calcium.dtype)]
    if not interpret:
        # Operands and results are held in HBM, and the kernel's own block
        # pipeline streams them through VMEM: left free, XLA's memory-space
        # assignment may stage them in VMEM with copies outside the kernel,
        # which then hide the kernel's traffic from its device time.
        ins = [pltpu.with_memory_space_constraint(a, pltpu.HBM) for a in ins]
        out_shape = [pltpu.HBM(o.shape, o.dtype) for o in out_shape]
    spec = pl.BlockSpec((bn,), lambda i: (i,))
    outs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[spec] * 5,
        out_specs=[spec] * 4,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*ins)
    return tuple(o[:n] for o in outs)
