"""Roofline methodology validation (EXPERIMENTS.md §Roofline).

1. Demonstrates the scan-undercount that forces analytic accounting:
   cost_analysis() counts a while body once.
2. Validates the analytic forward-flop estimator against cost_analysis()
   on probe configs whose scans have trip count 1 (no undercount).
"""
import dataclasses
import os
import sys

import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))

from repro import configs
from repro.models import model as M
from repro.launch import steps as S
from repro.models.config import ShapeConfig


def test_cost_analysis_counts_scan_body_once():
    def body(x, w):
        return jnp.tanh(x @ w), None

    def with_scan(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    def unrolled(x, ws):
        for i in range(ws.shape[0]):
            x = jnp.tanh(x @ ws[i])
        return x

    x = jnp.zeros((64, 128))
    ws = jnp.zeros((8, 128, 128))
    f_scan = jax.jit(with_scan).lower(x, ws).compile().cost_analysis()["flops"]
    f_unr = jax.jit(unrolled).lower(x, ws).compile().cost_analysis()["flops"]
    assert f_unr == pytest.approx(8 * f_scan, rel=0.05)


@pytest.mark.parametrize("arch,tol", [("qwen3-8b", 0.05),
                                      ("mamba2-1.3b", 0.05),
                                      ("deepseek-v2-lite-16b", 0.10),
                                      ("hubert-xlarge", 0.08)])
def test_analytic_forward_flops_match_hlo(arch, tol):
    import flops_model as FM
    base = configs.get(arch)
    kw = {"num_layers": 1}
    if base.family == "moe":
        kw["first_dense_layers"] = 0
    cfg = dataclasses.replace(base, **kw)
    params = S.abstract_params(cfg)
    b, s = 4, 512
    if cfg.family == "audio":
        inputs = jax.ShapeDtypeStruct((b, s, cfg.frontend_dim), jnp.float32)
    else:
        inputs = jax.ShapeDtypeStruct((b, s), jnp.int32)
    compiled = jax.jit(lambda p, x: M.forward_train(p, x, cfg)) \
        .lower(params, inputs).compile()
    hlo_flops = compiled.cost_analysis()["flops"]
    est = FM.cell_cost(cfg, ShapeConfig("probe", s, b, "prefill"), 1)
    assert est.flops == pytest.approx(hlo_flops, rel=tol), \
        (est.flops, hlo_flops)


def test_param_count_analytic_vs_tree():
    import flops_model as FM
    for arch in ("yi-6b", "llama4-maverick-400b-a17b"):
        cfg = configs.get(arch)
        pc = FM.param_count(cfg)
        assert pc["total"] > pc["active"] if cfg.num_experts \
            else pc["total"] == pc["active"]
        # llama4's census should land near its nameplate
        if arch.startswith("llama4"):
            assert 3.4e11 < pc["total"] < 4.8e11, pc["total"]
            assert 1.2e10 < pc["active"] < 2.4e10, pc["active"]


# ---------------------------------------------------------------------------
# Pallas kernel cost functions (benchmarks.figures.fig_kernels legs)
# ---------------------------------------------------------------------------

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
    import roofline as RL
    g = FM.kernel_cost_gaussian_nbody(2048, 8192)
    msp = FM.kernel_cost_msp_update(262_144)
    ridge = RL.PEAK_FLOPS / RL.HBM_BW        # flops/byte at the roofline knee
    assert g["flops"] / g["hbm_bytes"] > ridge
    assert msp["flops"] / msp["hbm_bytes"] < 1.0 < ridge
