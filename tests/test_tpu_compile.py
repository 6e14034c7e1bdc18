"""The three Pallas kernels compile for a TPU v5e at engine widths.

A chip is described, not attached: the TPU compiler refuses what the
interpreter accepts (block layouts, unsupported shape casts, VMEM), so these
compiles guard the native path without a chip.  Each kernel module is
called with interpret=False and the compiled program must hold the Mosaic
kernel (`tpu_custom_call`).

The topology is described inside a module fixture only: only one process at
a time may load the TPU library, and under several pytest workers a
description made at import would make the other workers fail to collect.
"""

import os
import re

import pytest
import jax
import jax.numpy as jnp

from repro.core.msp import MSPConfig
from repro.kernels import gaussian_nbody as gk
from repro.kernels import m2l_pair
from repro.kernels import msp_update as mk

N = 131_072  # chip_smoke.py's engine width
M2L_PAIRS = 262_144  # pairs at the deepest octree level of N neurons
# The sweep's cell: K replicas of N_REPLICA neurons under vmap, and the
# Taylor-tier pairs of one replica's deepest level with expansions.
K, N_REPLICA, M2L_PAIRS_REPLICA = 8, 16_384, 32_768


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # Keep the TPU library's logs out of the shared temp directory.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _kernel_cases():
    f32, i32 = jnp.float32, jnp.int32
    cfg = MSPConfig.calibrated(speedup=100.0)
    kw = dict(
        x0=cfg.x0,
        tau_x=cfg.tau_x,
        background=cfg.background,
        w_syn=cfg.w_syn,
        beta_ca=cfg.beta_ca,
        tau_ca=cfg.tau_ca,
        refractory=cfg.refractory,
    )
    return {
        "gaussian_nbody": (
            lambda t, s, w: gk.gaussian_nbody(t, s, w, 750.0**2, interpret=False),
            [((4096, 3), f32), ((8192, 3), f32), ((8192,), f32)],
        ),
        "m2l_separable": (
            lambda a, h, y: m2l_pair.m2l_separable(a, h, y, interpret=False),
            [((M2L_PAIRS, 64), f32), ((M2L_PAIRS, 64), f32), ((M2L_PAIRS, 3), f32)],
        ),
        "msp_update": (
            lambda *a: mk.msp_update(*a, interpret=False, **kw),
            [((N,), f32), ((N,), i32), ((N,), f32), ((N,), f32), ((N,), f32)],
        ),
    }


def _batched_cases():
    """The kernels under vmap over the replica axis, as the sweep runs them."""
    f32, i32 = jnp.float32, jnp.int32
    kernel = _kernel_cases()
    msp, _ = kernel["msp_update"]
    m2l, _ = kernel["m2l_separable"]
    per = (K, N_REPLICA)
    pairs = (K, M2L_PAIRS_REPLICA)
    return {
        "msp_update": (
            jax.vmap(msp),
            [(per, f32), (per, i32), (per, f32), (per, f32), (per, f32)],
        ),
        "m2l_separable": (
            jax.vmap(m2l),
            [(pairs + (64,), f32), (pairs + (64,), f32), (pairs + (3,), f32)],
        ),
    }


@pytest.mark.parametrize("name", ["gaussian_nbody", "m2l_separable", "msp_update"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.mark.parametrize("name", ["m2l_separable", "msp_update"])
def test_batched_kernel_compiles_for_v5e(one_chip, name):
    """Under vmap over K = 8 replicas the kernels still compile for the chip
    (a batched `pallas_call` of a 1-D block is refused by Mosaic), and the
    program holds the Mosaic kernel."""
    fn, shapes = _batched_cases()[name]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), name


def _custom_call_memory(text):
    """The HLO types of the Mosaic kernel's result and of each operand."""
    types = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (.+?) \S+\(", text, re.M))
    call = next(l for l in text.splitlines() if '"tpu_custom_call"' in l)
    result, operands = re.match(r"\s*%\S+ = (.+?) custom-call\(([^)]*)\)",
                                call).groups()
    return [result] + [types[o.strip()] for o in operands.split(",")]


@pytest.mark.parametrize("batched", [False, True])
def test_msp_update_operands_stay_in_hbm(one_chip, batched):
    """The neuron update reads and writes its arrays in HBM (no `S(1)`,
    XLA's mark for VMEM) between ops that XLA would otherwise stage in
    VMEM, so its device time holds its whole traffic, under vmap too."""
    fn, shapes = (_batched_cases() if batched else _kernel_cases())["msp_update"]

    def around(x, *rest):
        return [jnp.sin(o.astype(jnp.float32)) for o in fn(jnp.cos(x), *rest)]

    text = _compile(around, one_chip, *shapes).as_text()
    for t in _custom_call_memory(text):
        assert "S(1)" not in t, t
