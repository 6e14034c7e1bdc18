"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import numpy as np
import pytest
import jax.numpy as jnp

from _hypothesis_compat import given, settings, strategies as st

from repro.core.msp import MSPConfig
from repro.kernels import gaussian_nbody as gk
from repro.kernels import m2l_pair
from repro.kernels import msp_update as mk
from repro.kernels import ops, ref

DELTA = 750.0 ** 2


@pytest.mark.parametrize("n,m", [(1, 1), (7, 513), (256, 512), (300, 1000),
                                 (1024, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_gaussian_nbody_shapes(n, m, dtype):
    rng = np.random.default_rng(n * 1000 + m)
    t = jnp.array(rng.uniform(0, 3000, (n, 3)), dtype)
    s = jnp.array(rng.uniform(0, 3000, (m, 3)), dtype)
    w = jnp.array(rng.uniform(0, 5, (m,)), dtype)
    got = gk.gaussian_nbody(t, s, w, DELTA, interpret=True)
    want = ref.gaussian_nbody(t, s, w, DELTA)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("bt,bs", [(128, 128), (256, 512)])
def test_gaussian_nbody_block_sweep(bt, bs):
    rng = np.random.default_rng(0)
    t = jnp.array(rng.uniform(0, 2000, (200, 3)), jnp.float32)
    s = jnp.array(rng.uniform(0, 2000, (300, 3)), jnp.float32)
    w = jnp.array(rng.uniform(0, 5, (300,)), jnp.float32)
    got = gk.gaussian_nbody(t, s, w, DELTA, bt=bt, bs=bs, interpret=True)
    want = ref.gaussian_nbody(t, s, w, DELTA)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_msp_update_kernel_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 700))
    x = jnp.array(rng.uniform(0, 0.2, n), jnp.float32)
    refrac = jnp.array(rng.integers(0, 5, n), jnp.int32)
    ca = jnp.array(rng.uniform(0, 1, n), jnp.float32)
    syn = jnp.array(rng.integers(0, 4, n), jnp.float32)
    u = jnp.array(rng.uniform(0, 1, n), jnp.float32)
    cfg = MSPConfig()
    a = ops.msp_update(x, refrac, ca, syn, u, cfg, use_pallas=True)
    b = ops.msp_update(x, refrac, ca, syn, u, cfg, use_pallas=False)
    for ai, bi in zip(a, b):
        np.testing.assert_allclose(np.asarray(ai, np.float32),
                                   np.asarray(bi, np.float32),
                                   rtol=1e-6, atol=1e-7)


def test_msp_update_kernel_bitwise():
    """The fused kernel must be BITWISE equal to the reference phase-1 math
    (same division by tau_x, same blend order): the engine-level parity
    contract (tests/test_backend_parity.py, DESIGN.md §11) rides on the
    spike draw `u < x` never flipping between backends.

    Both paths run under jit, as the engine always invokes them — eager
    op-by-op execution skips XLA's fused-expression FMA contraction and
    differs from EITHER jitted path in the last ulp."""
    import jax
    rng = np.random.default_rng(11)
    n = 1000
    x = jnp.array(rng.uniform(0, 0.2, n), jnp.float32)
    refrac = jnp.array(rng.integers(0, 5, n), jnp.int32)
    ca = jnp.array(rng.uniform(0, 1, n), jnp.float32)
    syn = jnp.array(rng.integers(0, 4, n), jnp.float32)
    u = jnp.array(rng.uniform(0, 1, n), jnp.float32)
    cfg = MSPConfig.calibrated(speedup=100.0)
    run = lambda use_pallas: jax.jit(
        lambda *a: ops.msp_update(*a, cfg, use_pallas=use_pallas)
    )(x, refrac, ca, syn, u)
    for ai, bi in zip(run(True), run(False)):
        np.testing.assert_array_equal(np.asarray(ai), np.asarray(bi))


def test_msp_update_kernel_batched_bitwise():
    """Under vmap the kernel folds the replica axis into the neuron axis
    (one call over K*n neurons, as the sweep runs it on a TPU): each
    replica's rows equal the reference update of that replica alone,
    bitwise, for an n that is not a multiple of the block."""
    import jax
    k, n = 3, 1000
    assert n % mk.DEFAULT_BN
    rng = np.random.default_rng(12)
    x = jnp.array(rng.uniform(0, 0.2, (k, n)), jnp.float32)
    refrac = jnp.array(rng.integers(0, 5, (k, n)), jnp.int32)
    ca = jnp.array(rng.uniform(0, 1, (k, n)), jnp.float32)
    syn = jnp.array(rng.integers(0, 4, (k, n)), jnp.float32)
    u = jnp.array(rng.uniform(0, 1, (k, n)), jnp.float32)
    cfg = MSPConfig.calibrated(speedup=100.0)
    batched = jax.jit(jax.vmap(
        lambda *a: ops.msp_update(*a, cfg, use_pallas=True)))(x, refrac, ca, syn, u)
    one = jax.jit(lambda *a: ops.msp_update(*a, cfg, use_pallas=False))
    for r in range(k):
        want = one(x[r], refrac[r], ca[r], syn[r], u[r])
        for got, w in zip(batched, want):
            np.testing.assert_array_equal(np.asarray(got[r]), np.asarray(w))


@pytest.mark.parametrize("b", [1, 63, 512, 700])
def test_m2l_kernel_shapes(b):
    rng = np.random.default_rng(b)
    moms = jnp.array(rng.uniform(0, 1, (b, 64)), jnp.float32)
    herm = jnp.array(rng.uniform(-1, 1, (b, 64)), jnp.float32)
    y = jnp.array(rng.uniform(-1.5, 1.5, (b, 3)), jnp.float32)
    got = m2l_pair.m2l_separable(moms, herm, y, interpret=True)
    want = ref.m2l_separable(moms, herm, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ops_dispatch_reference_on_cpu():
    """use_pallas=None on CPU must run the reference (no interpret slowdown)."""
    rng = np.random.default_rng(5)
    t = jnp.array(rng.uniform(0, 100, (8, 3)), jnp.float32)
    s = jnp.array(rng.uniform(0, 100, (9, 3)), jnp.float32)
    w = jnp.ones((9,), jnp.float32)
    got = ops.gaussian_nbody(t, s, w, DELTA)          # auto -> ref on CPU
    want = ref.gaussian_nbody(t, s, w, DELTA)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
