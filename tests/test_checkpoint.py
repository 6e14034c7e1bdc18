"""Checkpointing: atomic pytree save/restore, and exact restarts of a simulation."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.core.engine import EngineConfig, PlasticityEngine, StepRecord
from repro.core.ensemble import EnsembleEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": {"c": jnp.ones(4, jnp.bfloat16), "d": jnp.asarray(3, jnp.int32)},
    }
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=2, async_save=False)
    mgr.save(tree, 10)
    template = jax.tree.map(jnp.zeros_like, tree)
    restored, step = mgr.restore(template)
    assert step == 10
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_checkpoint_async_and_retention(tmp_path):
    tree = {"w": jnp.ones(16)}
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=2, async_save=True)
    for s in (1, 2, 3, 4):
        mgr.save(jax.tree.map(lambda a: a * s, tree), s)
    mgr.wait()
    mgr.close()
    assert ckpt.latest_step(str(tmp_path)) == 4
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(kept) == 2
    restored, step = ckpt.restore_pytree(tree, str(tmp_path))
    assert step == 4
    np.testing.assert_allclose(np.asarray(restored["w"]), 4.0)


def test_checkpoint_atomicity(tmp_path):
    """A half-written tmp dir must never shadow the published checkpoint."""
    tree = {"w": jnp.ones(4)}
    ckpt.save_pytree(tree, str(tmp_path), 1)
    os.makedirs(tmp_path / ".tmp_step_000000002")  # simulated crash debris
    assert ckpt.latest_step(str(tmp_path)) == 1
    restored, step = ckpt.restore_pytree(tree, str(tmp_path))
    assert step == 1


N, K, INTERVAL = 128, 2, 10
# The first run stops mid-interval (step 15); the updates at steps 20 and 30
# run after the restart.
A = B = 15


def _with_elements(state):
    """Three synaptic elements of each kind on every neuron, so the first
    update forms synapses and the restart carries a non-empty edge table."""
    neurons = state.neurons._replace(
        ax_elems=jnp.full_like(state.neurons.ax_elems, 3.0),
        den_elems=jnp.full_like(state.neurons.den_elems, 3.0),
    )
    return state._replace(neurons=neurons)


@pytest.mark.parametrize("ensemble", [False, True], ids=["engine", "ensemble-k2"])
@pytest.mark.parametrize("method", ["fmm", "barnes_hut", "direct"])
def test_restart_resumes_exactly(tmp_path, method, ensemble):
    """simulate(a + b) equals simulate(a), save, restore, simulate(b),
    bitwise in the state and the records: each step's key is folded from
    the carried step counter, so a resumed run draws the same streams."""
    pos = np.random.default_rng(0).uniform(0, 1000.0, (N, 3)).astype(np.float32)
    msp_cfg = dataclasses.replace(MSPConfig.calibrated(speedup=100.0), update_interval=INTERVAL)
    engine = PlasticityEngine(pos, msp_cfg, FMMConfig(c1=8, c2=8), EngineConfig(method=method))
    if ensemble:
        ens = EnsembleEngine(engine)
        start = _with_elements(ens.init_states(K))
        key = jax.random.split(jax.random.key(5), K)
        simulate = ens.simulate
    else:
        start = _with_elements(engine.init_state())
        key = jax.random.key(5)
        simulate = engine.simulate

    whole, whole_recs = simulate(start, key, A + B)

    first, first_recs = simulate(start, key, A)
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=2)
    mgr.save(first, A)
    mgr.wait()
    restored, step = mgr.restore(jax.tree.map(jnp.zeros_like, start))
    mgr.close()
    assert step == A
    second, second_recs = simulate(restored, key, B)

    for got, want in zip(jax.tree.leaves(second), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name in StepRecord._fields:
        joined = np.concatenate([getattr(first_recs, name), getattr(second_recs, name)])
        np.testing.assert_array_equal(joined, getattr(whole_recs, name), err_msg=name)
    assert np.asarray(whole_recs.num_synapses)[A:].min() > 0
