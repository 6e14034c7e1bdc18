"""The Pallas kernels' analytic flop / byte counts (benchmarks/flops_model.py)
against `cost_analysis()` and the TPU v5e roofline."""
import json
import os
import sys

import pytest
import jax
import jax.numpy as jnp

BENCHMARKS = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
sys.path.insert(0, BENCHMARKS)


def _hlo_flops(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.cost_analysis()["flops"]


def test_kernel_costs_scale_linearly():
    import flops_model as FM
    for fn, small, big in [
        (lambda s: FM.kernel_cost_gaussian_nbody(s, 4 * s), 128, 256),
        (lambda s: FM.kernel_cost_m2l(s), 1024, 2048),
        (lambda s: FM.kernel_cost_msp_update(s), 4096, 8192),
    ]:
        a, b = fn(small), fn(big)
        assert a["flops"] > 0 and a["hbm_bytes"] > 0
        # gaussian is quadratic in total (n*m with m = 4n) — compare at
        # fixed ratio, so flops scale with the product
        ratio = b["flops"] / a["flops"]
        assert ratio in (2.0, 4.0), ratio
        assert b["hbm_bytes"] / a["hbm_bytes"] == pytest.approx(2.0, rel=0.01)


def test_m2l_cost_matches_hlo():
    """The separable-M2L flop model vs cost_analysis of the ref oracle —
    the schedules match (same mode products), so the counts should too."""
    import flops_model as FM
    import numpy as np
    from repro.kernels import ref
    b = 2048
    rng = np.random.default_rng(0)
    moms = jnp.array(rng.uniform(0, 1, (b, 64)), jnp.float32)
    herm = jnp.array(rng.uniform(-1, 1, (b, 64)), jnp.float32)
    y = jnp.array(rng.uniform(-1.5, 1.5, (b, 3)), jnp.float32)
    hlo = _hlo_flops(lambda *a: ref.m2l_separable(*a), moms, herm, y)
    est = FM.kernel_cost_m2l(b)["flops"]
    assert est == pytest.approx(hlo, rel=0.25), (est, hlo)


def test_gaussian_cost_counts_lane_padding():
    """The model counts the kernel's padded 8-lane matmul schedule; the
    logical math (the ref oracle's HLO) uses 3 components — the model must
    sit between 1x and the 8/3 cross-term inflation of that count."""
    import flops_model as FM
    import numpy as np
    from repro.kernels import ref
    n, m = 256, 1024
    rng = np.random.default_rng(0)
    t = jnp.array(rng.uniform(0, 1000, (n, 3)), jnp.float32)
    s = jnp.array(rng.uniform(0, 1000, (m, 3)), jnp.float32)
    w = jnp.array(rng.uniform(0, 5, (m,)), jnp.float32)
    hlo = _hlo_flops(lambda *a: ref.gaussian_nbody(*a, 750.0 ** 2), t, s, w)
    est = FM.kernel_cost_gaussian_nbody(n, m)["flops"]
    assert hlo <= est <= 2.5 * hlo, (est, hlo)


def test_kernel_roofline_classification():
    """Against the TPU-v5e peaks the attraction kernel must land
    compute-bound and the fused neuron update bandwidth-bound — the whole
    point of fusing it (kernels/msp_update.py)."""
    import flops_model as FM
    with open(os.path.join(BENCHMARKS, "chip", "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    g = FM.kernel_cost_gaussian_nbody(2048, 8192)
    msp = FM.kernel_cost_msp_update(262_144)
    # flops/byte at the roofline knee
    ridge = v5e["bf16_flops_per_s"] / v5e["hbm_bytes_per_s"]
    assert g["flops"] / g["hbm_bytes"] > ridge
    assert msp["flops"] / msp["hbm_bytes"] < 1.0 < ridge
