"""Engine-level backend parity: Pallas kernels vs the pure-jnp reference.

The contract (DESIGN.md §11): with `EngineConfig(backend="pallas")` — which
on CPU runs every kernel in interpret mode, numerically identical to the
TPU lowering — a full `PlasticityEngine.simulate` reproduces
`backend="reference"` for every search method.  The kernels were written to
be BITWISE equal to the reference phase-1 update (same division, same
blend order), so the spike stream never diverges and we can assert exact
equality on the integer synapse-count trajectories and tight allclose
(rtol=1e-6; empirically bitwise on this container) on the float records.
"""
import dataclasses

import numpy as np
import pytest
import jax

from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig

N = 64
STEPS = 2000
MSP_CFG = MSPConfig.calibrated(speedup=100.0)


def _positions():
    rng = np.random.default_rng(42)
    return rng.uniform(0, 1000.0, (N, 3)).astype(np.float32)


def _run(engine_cfg, fmm_cfg, steps=STEPS, key=0):
    eng = PlasticityEngine(_positions(), MSP_CFG, fmm_cfg, engine_cfg)
    st, recs = eng.simulate(eng.init_state(), jax.random.key(key), steps)
    jax.block_until_ready(recs.calcium_mean)
    return st, recs


def _assert_parity(recs_ref, recs_pal, label):
    np.testing.assert_array_equal(np.asarray(recs_ref.num_synapses),
                                  np.asarray(recs_pal.num_synapses),
                                  err_msg=label)
    np.testing.assert_allclose(np.asarray(recs_ref.calcium_mean),
                               np.asarray(recs_pal.calcium_mean),
                               rtol=1e-6, err_msg=label)
    np.testing.assert_allclose(np.asarray(recs_ref.spike_rate),
                               np.asarray(recs_pal.spike_rate),
                               rtol=1e-6, err_msg=label)


@pytest.fixture(scope="module")
def parity_runs():
    """reference + pallas runs per method, shared across the assertions."""
    out = {}
    fmm_cfg = FMMConfig(c1=8, c2=8)
    for method in ["fmm", "barnes_hut", "direct"]:
        out[method] = {
            backend: _run(EngineConfig(method=method, backend=backend),
                          fmm_cfg)
            for backend in ["reference", "pallas"]
        }
    return out


def test_simulate_parity_all_methods(parity_runs):
    for method, runs in parity_runs.items():
        _assert_parity(runs["reference"][1], runs["pallas"][1], method)


def test_parity_runs_are_nontrivial(parity_runs):
    """The runs the parity is asserted on must actually form synapses and
    spike — an all-zero trajectory would make the equality vacuous."""
    for method, runs in parity_runs.items():
        recs = runs["pallas"][1]
        assert int(np.asarray(recs.num_synapses)[-1]) > 0, method
        assert float(np.asarray(recs.spike_rate).mean()) > 0, method


def test_taylor_tier_parity():
    """Force tier_mode="taylor" at a depth where expansions are valid, so the
    m2l_pair kernel demonstrably executes inside the descent."""
    fmm_cfg = FMMConfig(c1=8, c2=8, tier_mode="taylor")
    base = EngineConfig(method="fmm", depth=2)
    _, recs_ref = _run(dataclasses.replace(base, backend="reference"),
                       fmm_cfg)
    _, recs_pal = _run(dataclasses.replace(base, backend="pallas"), fmm_cfg)
    _assert_parity(recs_ref, recs_pal, "taylor tier")
    assert int(np.asarray(recs_pal.num_synapses)[-1]) > 0


def test_hermite_tier_parity():
    """Force tier_mode="hermite": the Hermite tier now evaluates through
    the same m2l_pair kernel (box_mass_hermite_log is the M2L series with a
    one-hot zeroth moment — DESIGN.md §11), so backend="pallas" must keep
    engine-level parity with the kernel demonstrably inside the descent."""
    fmm_cfg = FMMConfig(c1=8, c2=8, tier_mode="hermite")
    base = EngineConfig(method="fmm", depth=2)
    _, recs_ref = _run(dataclasses.replace(base, backend="reference"),
                       fmm_cfg)
    _, recs_pal = _run(dataclasses.replace(base, backend="pallas"), fmm_cfg)
    _assert_parity(recs_ref, recs_pal, "hermite tier")
    assert int(np.asarray(recs_pal.num_synapses)[-1]) > 0


def test_auto_backend_on_cpu_matches_reference():
    """backend="auto" on CPU must take the reference path exactly (the
    zero-overhead default for CPU CI)."""
    fmm_cfg = FMMConfig(c1=8, c2=8)
    _, recs_ref = _run(EngineConfig(method="fmm", backend="reference"),
                       fmm_cfg, steps=400)
    _, recs_auto = _run(EngineConfig(method="fmm", backend="auto"),
                        fmm_cfg, steps=400)
    np.testing.assert_array_equal(np.asarray(recs_ref.num_synapses),
                                  np.asarray(recs_auto.num_synapses))
    np.testing.assert_array_equal(np.asarray(recs_ref.calcium_mean),
                                  np.asarray(recs_auto.calcium_mean))


def test_ensemble_threads_backend():
    """EnsembleEngine inherits the knob: a K=2 batched pallas run (vmap over
    the interpret-mode kernels) reproduces sequential pallas runs."""
    from repro.core.ensemble import EnsembleEngine
    ecfg = EngineConfig(method="fmm", backend="pallas")
    eng = PlasticityEngine(_positions(), MSP_CFG, FMMConfig(c1=8, c2=8), ecfg)
    ens = EnsembleEngine(eng)
    k = 2
    keys = jax.random.split(jax.random.key(7), k)
    st_k, recs_k = ens.simulate(ens.init_states(k), keys, 600)
    jax.block_until_ready(recs_k.num_synapses)
    for i in range(k):
        _, recs_1 = eng.simulate(eng.init_state(), keys[i], 600)
        np.testing.assert_array_equal(
            np.asarray(recs_1.num_synapses),
            np.asarray(recs_k.num_synapses)[:, i])
        np.testing.assert_array_equal(
            np.asarray(recs_1.calcium_mean),
            np.asarray(recs_k.calcium_mean)[:, i])


def test_distributed_threads_backend():
    """DistributedPlasticityEngine threads the knob through local_step and
    the sharded find phase; on a 1-device mesh the result must stay bitwise
    equal to the single-device pallas run (the shard-count invariance
    contract, now per backend)."""
    from jax.sharding import Mesh
    from repro.core.distributed import DistributedPlasticityEngine
    ecfg = EngineConfig(method="fmm", backend="pallas")
    fmm_cfg = FMMConfig(c1=8, c2=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    deng = DistributedPlasticityEngine(_positions(), mesh, msp_cfg=MSP_CFG,
                                       fmm_cfg=fmm_cfg, engine_cfg=ecfg)
    _, drecs = deng.simulate(deng.init_state(), jax.random.key(0), 600)
    jax.block_until_ready(drecs.num_synapses)
    # same Morton-sorted positions, single-device engine
    seng = PlasticityEngine(deng.positions_np, MSP_CFG, fmm_cfg, ecfg)
    _, srecs = seng.simulate(seng.init_state(), jax.random.key(0), 600)
    np.testing.assert_array_equal(np.asarray(drecs.num_synapses),
                                  np.asarray(srecs.num_synapses))
    np.testing.assert_array_equal(np.asarray(drecs.calcium_mean),
                                  np.asarray(srecs.calcium_mean))
