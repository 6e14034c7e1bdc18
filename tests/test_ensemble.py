"""Ensemble subsystem: batched K-replica runs == K sequential runs.

The contract (core/ensemble.py): a vmapped ensemble with per-replica keys
[k_0..k_{K-1}] reproduces K sequential PlasticityEngine.simulate runs with
the same keys on the recorded observables — exactly for the integer synapse
counts, to float tolerance for the calcium trajectories.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.ensemble import EnsembleEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig
from repro.launch import sweep

K = 4
STEPS = 1200          # several connectivity updates, synapses present


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(3)
    pos = rng.uniform(0, 1000.0, (200, 3)).astype(np.float32)
    return PlasticityEngine(pos, MSPConfig.calibrated(speedup=100.0),
                            FMMConfig(c1=8, c2=8),
                            EngineConfig(method="fmm"))


@pytest.fixture(scope="module")
def batched_run(engine):
    keys = jax.random.split(jax.random.key(7), K)
    ens = EnsembleEngine(engine)
    states, recs = ens.simulate(ens.init_states(K), keys, STEPS)
    jax.block_until_ready(recs.calcium_mean)
    return ens, keys, states, recs


def test_vmapped_matches_sequential(engine, batched_run):
    _, keys, _, recs = batched_run
    for r in range(K):
        _, rec = engine.simulate(engine.init_state(), keys[r], STEPS)
        np.testing.assert_array_equal(np.asarray(recs.num_synapses[:, r]),
                                      np.asarray(rec.num_synapses))
        np.testing.assert_allclose(np.asarray(recs.calcium_mean[:, r]),
                                   np.asarray(rec.calcium_mean), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(recs.spike_rate[:, r]),
                                   np.asarray(rec.spike_rate), rtol=1e-6)
    # trajectories are non-trivial: synapses actually formed
    assert int(np.asarray(recs.num_synapses)[-1].min()) > 10


def test_chunked_runs_continue_update_schedule(engine, batched_run):
    """A continuation follows the CARRIED step counter, not the local scan
    index.  Starting the second chunk at a step that is NOT a multiple of the
    update interval, connectivity updates must fire at global steps that are
    — a local-index schedule would fire them interval steps after the cut."""
    ens, keys, _, _ = batched_run
    interval = engine.msp_cfg.update_interval
    cut = interval * 6 + interval // 2                   # mid-interval cut
    mid, _ = ens.simulate(ens.init_states(K), keys, cut)
    _, recs_b = ens.simulate(mid, keys, STEPS - cut)
    syn_b = np.asarray(recs_b.num_synapses)
    # synapse counts only change at update steps
    changes = np.nonzero(np.any(syn_b[1:] != syn_b[:-1], axis=1))[0] + 1
    assert len(changes) > 0
    # record index i reflects the state after global step cut + i + 1
    global_steps = cut + changes + 1
    assert np.all(global_steps % interval == 0), global_steps[:5]


def test_replicas_are_independent(batched_run):
    _, _, _, recs = batched_run
    syn = np.asarray(recs.num_synapses)
    assert len({tuple(syn[:, r]) for r in range(K)}) == K


def test_identity_params_match_plain(batched_run):
    ens, keys, _, recs = batched_run
    params = ens.default_params(K)
    _, recs_p = ens.simulate(ens.init_states(K), keys, STEPS, params)
    np.testing.assert_array_equal(np.asarray(recs_p.num_synapses),
                                  np.asarray(recs.num_synapses))
    np.testing.assert_allclose(np.asarray(recs_p.calcium_mean),
                               np.asarray(recs.calcium_mean), rtol=1e-6)


def test_traced_sigma_controls_locality(engine, batched_run):
    """Per-replica sigma must reach the kernel: with identical keys, larger
    sigma draws more distant partners (Eq. 1's length scale)."""
    ens, keys, _, _ = batched_run
    same = jax.vmap(lambda _: keys[0])(jnp.arange(K))
    params = ens.default_params(K)._replace(
        sigma=jnp.asarray([100.0, 300.0, 750.0, 3000.0], jnp.float32))
    states, _ = ens.simulate(ens.init_states(K), same, STEPS, params)
    pos = engine.positions_np
    dist = []
    for r in range(K):
        v = np.asarray(states.edges.valid[r])
        src = np.asarray(states.edges.src[r])[v]
        dst = np.asarray(states.edges.dst[r])[v]
        assert v.sum() > 10
        dist.append(np.linalg.norm(pos[src] - pos[dst], axis=1).mean())
    assert dist[0] < dist[1] < dist[2] < dist[3], dist


def test_traced_inhibitory_fraction(engine, batched_run):
    """The traced fraction reproduces a statically configured inhibitory
    engine (0.25 is exact in binary, so the traced idx < f*n population cut
    matches the static floor(f*n))."""
    ens, keys, _, recs = batched_run
    params = ens.default_params(K)._replace(
        inhibitory_fraction=jnp.asarray([0.0, 0.25, 0.25, 0.0], jnp.float32))
    _, recs_i = ens.simulate(ens.init_states(K), keys, STEPS, params)
    # fraction-0 replicas unchanged (multiplying by an all-ones sign vector)
    np.testing.assert_array_equal(np.asarray(recs_i.num_synapses[:, 0]),
                                  np.asarray(recs.num_synapses[:, 0]))
    static = PlasticityEngine(engine.positions_np, engine.msp_cfg,
                              engine.fmm_cfg,
                              EngineConfig(method="fmm",
                                           inhibitory_fraction=0.25))
    _, rec_s = static.simulate(static.init_state(), keys[1], STEPS)
    np.testing.assert_array_equal(np.asarray(recs_i.num_synapses[:, 1]),
                                  np.asarray(rec_s.num_synapses))
    np.testing.assert_allclose(np.asarray(recs_i.calcium_mean[:, 1]),
                               np.asarray(rec_s.calcium_mean), rtol=1e-6)


def test_sweep_grid_and_pack(engine):
    configs = sweep.grid(sigma=[500.0, 750.0],
                         inhibitory_fraction=[0.0, 0.2])
    assert len(configs) == 4
    assert configs[0] == {"sigma": 500.0, "inhibitory_fraction": 0.0}
    with pytest.raises(ValueError):
        sweep.grid(not_a_knob=[1.0])
    params = sweep.pack_params(engine, configs)
    assert params.sigma.shape == (4,)
    # unswept knobs default to the static config
    np.testing.assert_allclose(np.asarray(params.c1),
                               np.full((4,), engine.fmm_cfg.c1))


def test_run_sweep_end_to_end(engine):
    configs = sweep.grid(sigma=[750.0])
    result = sweep.run_sweep(engine, configs, num_steps=400, seed=0,
                             replicates=2, tail=100)
    assert len(result.configs) == 2
    assert result.calcium_end.shape == (2,)
    rows = sweep.summarize(result)
    assert rows[0]["sigma"] == 750.0 and "calcium_end" in rows[0]
    # replicates use distinct streams
    assert not np.allclose(np.asarray(result.records.calcium_mean[:, 0]),
                           np.asarray(result.records.calcium_mean[:, 1]))


def test_sweep_warns_on_nonconservative_guard(engine):
    with pytest.warns(UserWarning, match="static sigma exceeds"):
        sweep.run_sweep(engine, sweep.grid(sigma=[100.0]), num_steps=1)


@pytest.mark.slow
def test_sharded_matches_unsharded_subprocess():
    """shard_map over 4 forced host devices == plain vmap (bitwise on the
    synapse counts).  Subprocess so the forced device count cannot leak."""
    import os
    import subprocess
    import sys
    script = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.ensemble import EnsembleEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig
from repro.launch.mesh import make_ensemble_mesh

rng = np.random.default_rng(3)
pos = rng.uniform(0, 1000.0, (200, 3)).astype(np.float32)
eng = PlasticityEngine(pos, MSPConfig.calibrated(speedup=100.0),
                       FMMConfig(c1=8, c2=8), EngineConfig(method="fmm"))
k, steps = 8, 600
keys = jax.random.split(jax.random.key(7), k)
plain = EnsembleEngine(eng)
sharded = EnsembleEngine(eng, mesh=make_ensemble_mesh())
_, r0 = plain.simulate(plain.init_states(k), keys, steps)
_, r1 = sharded.simulate(sharded.init_states(k), keys, steps)
assert np.array_equal(np.asarray(r0.num_synapses), np.asarray(r1.num_synapses))
params = plain.default_params(k)
_, r2 = sharded.simulate(sharded.init_states(k), keys, steps, params)
assert np.array_equal(np.asarray(r0.num_synapses), np.asarray(r2.num_synapses))
print("OK")
'''
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout


def test_pallas_backend_matches_sequential_with_traced_knobs(engine):
    """The sweep's chip path in miniature: backend="pallas" (the kernels,
    interpreted on the CPU) under vmap, with sigma and the inhibitory
    fraction traced per replica, over one update interval from a grown
    state, equals K sequential runs of the same engine with each replica's
    knobs — bitwise on the edge table, the neuron state and the records."""
    # Built at the sweep's smallest sigma, whose guard admits expansions on
    # both levels of this octree: the m2l_pair kernel runs in the descent.
    fmm = FMMConfig(sigma=1000.0, c1=8, c2=8)
    pallas = PlasticityEngine(engine.positions_np, engine.msp_cfg, fmm,
                              EngineConfig(method="fmm", backend="pallas"))
    grow = EnsembleEngine(PlasticityEngine(
        engine.positions_np, engine.msp_cfg, fmm, EngineConfig(method="fmm")))
    params = grow.default_params(K)._replace(
        sigma=jnp.asarray([1000.0, 2000.0, 1000.0, 2000.0], jnp.float32),
        inhibitory_fraction=jnp.asarray([0.0, 0.0, 0.25, 0.25], jnp.float32))
    keys = jax.random.split(jax.random.key(11), K)
    grown, _ = grow.simulate(grow.init_states(K), keys, 1100, params)
    assert int(np.asarray(grown.edges.valid).sum(axis=1).min()) > 10
    steps = pallas.msp_cfg.update_interval
    ens = EnsembleEngine(pallas)
    states, recs = ens.simulate(grown, keys, steps, params)
    changed = 0
    for r in range(K):
        one = jax.tree.map(lambda a: a[r], grown)
        p = jax.tree.map(lambda a: a[r], params)
        st, rec = pallas.simulate(one, keys[r], steps, p)
        for got, want in zip(jax.tree.leaves((states, recs)),
                             jax.tree.leaves((st, rec))):
            got = np.asarray(got)
            got = got[r] if got.shape[:1] == (K,) else got[:, r]
            np.testing.assert_array_equal(got, np.asarray(want))
        changed += int(np.sum(np.asarray(st.edges.valid)
                              != np.asarray(one.edges.valid)))
    assert changed > 0  # the update formed or deleted synapses
