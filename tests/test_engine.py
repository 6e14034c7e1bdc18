"""End-to-end MSP simulation behaviour (paper Figs. 1-2 at reduced scale)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig


@pytest.fixture(scope="module")
def short_runs():
    rng = np.random.default_rng(42)
    pos = rng.uniform(0, 1000.0, (400, 3)).astype(np.float32)
    msp_cfg = MSPConfig.calibrated(speedup=100.0)
    out = {}
    for method in ["fmm", "barnes_hut", "direct"]:
        eng = PlasticityEngine(pos, msp_cfg, FMMConfig(c1=8, c2=8),
                               EngineConfig(method=method))
        st, recs = eng.simulate(eng.init_state(), jax.random.key(0), 4000)
        jax.block_until_ready(recs.calcium_mean)
        out[method] = (eng, st, recs)
    return out


def test_synapses_form_and_calcium_rises(short_runs):
    for method, (eng, st, recs) in short_runs.items():
        syn = np.asarray(recs.num_synapses)
        ca = np.asarray(recs.calcium_mean)
        assert syn[-1] > 100, method
        assert ca[-1] > 0.3, method
        assert np.isfinite(ca).all() and (ca >= 0).all(), method
        assert int(st.dropped) == 0, method


def test_methods_agree_statistically(short_runs):
    """FMM vs Barnes-Hut vs direct: same dynamics (paper Figs. 1-2)."""
    ca = {m: float(np.asarray(r.calcium_mean)[-500:].mean())
          for m, (_, _, r) in short_runs.items()}
    syn = {m: float(np.asarray(r.num_synapses)[-500:].mean())
           for m, (_, _, r) in short_runs.items()}
    for m in ["fmm", "barnes_hut"]:
        assert abs(ca[m] - ca["direct"]) / ca["direct"] < 0.1, ca
        assert abs(syn[m] - syn["direct"]) / syn["direct"] < 0.15, syn


def test_edge_list_consistent_with_elements(short_runs):
    """After a connectivity update no neuron holds more synapses than
    synaptic elements (the deletion invariant)."""
    for method, (eng, st, recs) in short_runs.items():
        from repro.core import synapses
        out_deg, in_deg = map(np.asarray, synapses.degrees(st.edges, eng.n))
        ax = np.floor(np.asarray(st.neurons.ax_elems)).astype(int)
        den = np.floor(np.asarray(st.neurons.den_elems)).astype(int)
        # elements keep growing between updates; allow the one-update slack
        assert (out_deg <= ax + eng.engine_cfg.max_requests_per_neuron).all()
        assert (in_deg <= den + eng.engine_cfg.max_requests_per_neuron).all()


def test_determinism(short_runs):
    eng, _, recs = short_runs["fmm"]
    st2, recs2 = eng.simulate(eng.init_state(), jax.random.key(0), 4000)
    np.testing.assert_array_equal(np.asarray(recs.num_synapses),
                                  np.asarray(recs2.num_synapses))
    np.testing.assert_allclose(np.asarray(recs.calcium_mean),
                               np.asarray(recs2.calcium_mean), rtol=1e-6)


def test_step_does_not_rest_on_the_live_prefix(short_runs, monkeypatch):
    """Steps between two updates give bitwise the same records and neuron
    state whether the live synapses sit in a prefix of the edge table or at
    its end: the live prefix only bounds how much the input reads.  (The
    runs stop before an update: deletion keys its priorities by slot.)"""
    from repro.core import synapses
    eng, st, _ = short_runs["fmm"]
    monkeypatch.setattr(synapses, "LIVE_BLOCK", 1024)   # 25 blocks per table
    e = st.edges.valid.shape[0]
    live = np.flatnonzero(np.asarray(st.edges.valid))
    assert 0 < live.size < e // 4

    def laid_out(slots):
        src = np.zeros(e, np.int32)
        dst = np.zeros(e, np.int32)
        valid = np.zeros(e, bool)
        src[slots] = np.asarray(st.edges.src)[live]
        dst[slots] = np.asarray(st.edges.dst)[live]
        valid[slots] = True
        return st._replace(edges=synapses.SynapseState(
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid)))

    steps = eng.msp_cfg.update_interval // 2
    assert int(st.step) % eng.msp_cfg.update_interval == 0
    runs = [eng.simulate(laid_out(slots), jax.random.key(3), steps)
            for slots in (np.arange(live.size), e - live.size + np.arange(live.size))]
    (prefix, prefix_recs), (end, end_recs) = runs
    for a, b in zip(jax.tree.leaves((prefix.neurons, prefix_recs)),
                    jax.tree.leaves((end.neurons, end_recs))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert int(prefix_recs.num_synapses[-1]) == live.size


def test_update_over_live_extent_equals_whole_table(short_runs, monkeypatch):
    """Updates with forced deletion give bitwise the same states and records
    whether each pass of the update runs over the live extent of the edge
    table or over the whole table (the ladder pinned to its top rung), on
    one network and on a K = 2 ensemble."""
    from repro.core import synapses
    from repro.core.ensemble import EnsembleEngine
    eng, st, _ = short_runs["fmm"]
    # Every other neuron loses its elements, so at the next update it
    # deletes its synapses on both sides, leaving holes in the prefix.
    even = jnp.arange(eng.n) % 2 == 0
    forced = st._replace(neurons=st.neurons._replace(
        ax_elems=jnp.where(even, 0.0, st.neurons.ax_elems),
        den_elems=jnp.where(even, 0.0, st.neurons.den_elems)))
    # The second replica keeps its elements: another table from the first
    # update on.
    pair = jax.tree.map(lambda a, b: jnp.stack([a, b]), forced, st)
    keys = jax.random.split(jax.random.key(7), 2)
    steps = 3 * eng.msp_cfg.update_interval

    def runs():  # new engines, so no trace at another ladder is reused
        fresh = lambda: PlasticityEngine(eng.positions_np, eng.msp_cfg,
                                         eng.fmm_cfg, eng.engine_cfg)
        return (fresh().simulate(forced, keys[0], steps),
                EnsembleEngine(fresh()).simulate(pair, keys, steps))

    monkeypatch.setattr(synapses, "LIVE_EXTENTS", ())
    want = runs()
    monkeypatch.setattr(synapses, "LIVE_EXTENTS", (1024, 4096, 16384))
    got = runs()
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    (_, recs), (_, pair_recs) = want
    live = int(np.asarray(st.edges.valid).sum())
    high = int(np.flatnonzero(np.asarray(st.edges.valid)).max()) + 1
    # Deletion fired, and the passes ran below the table's size.
    assert int(recs.num_synapses.min()) < live
    assert int(pair_recs.num_synapses[:, 0].min()) < live
    assert high < 16384 < st.edges.valid.shape[0]
