"""Sharded find phase: owner-span descent + request exchange (DESIGN.md §10).

The connectivity update's find phase runs sharded by default
(`DistributedPlasticityEngine(find_phase="sharded")`): each device scores
only its owned occupied source boxes, resolves leaf partners only for its
owned neuron rows, and the devices exchange the O(n) request vectors instead
of the O(E) edge table.  The contract is BITWISE parity with the replicated
path — and hence with single-device `PlasticityEngine.simulate` — for any
shard count.

These tests run in-process on one device: per-rank descent partials are
computed sequentially and summed, which is arithmetically identical to the
shard_map psum (disjoint integer scatters), and row-sliced resolutions are
concatenated.  Multi-device shard_map coverage (p in {2,4,8}, swept
KernelParams, uneven occupancy, empty-owner shards) runs in the slow
subprocess test at the bottom, on 8 forced host devices.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import octree, synapses, traversal
from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig
from repro.sharding import rules

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORD_FIELDS = ("num_synapses", "calcium_mean", "calcium_std", "spike_rate")


def _sorted_structure(pos, domain=1000.0, depth=None):
    """Morton-sort positions and rebuild — the distributed engine's layout."""
    s0 = octree.build_structure(pos, domain, depth)
    pos = pos[s0.order]
    return pos, octree.build_structure(pos, domain, depth)


def _uniform(n, seed=0, domain=1000.0, depth=None):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, domain, (n, 3)).astype(np.float32)
    return _sorted_structure(pos, domain, depth)


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


# -- occupied-box owner spans ------------------------------------------------

def test_occupied_spans_partition_every_level():
    pos, s = _uniform(256, seed=0)
    for p in (1, 2, 4, 8):
        spans = octree.owner_spans(s, p)
        for level in range(s.depth + 1):
            num_occ = s.occupied_at(level).shape[0]
            start, stop = spans.occ_start[level], spans.occ_stop[level]
            # contiguous partition of the occupied list
            assert start[0] == 0 and stop[-1] == num_occ
            np.testing.assert_array_equal(stop[:-1], start[1:])
            assert (stop >= start).all()
            assert spans.occ_width[level] >= int((stop - start).max())
            assert spans.occ_width[level] >= 1
        # the sharded descent's per-device box count shrinks with p
        assert spans.descent_boxes_per_device \
            == sum(spans.occ_width[1:])
    assert octree.owner_spans(s, 1).descent_boxes_per_device \
        >= octree.owner_spans(s, 8).descent_boxes_per_device


def test_occupied_spans_agree_with_neuron_owner():
    """An occupied box's span rank == the owner of its first member."""
    pos, s = _uniform(200, seed=5)
    spans = octree.owner_spans(s, 4)
    for level in range(s.depth + 1):
        ids = s.box_of(level)
        occ = s.occupied_at(level)
        owner = spans.neuron_owner[level]
        for j, b in enumerate(occ):
            first = int(np.flatnonzero(ids == b)[0])
            d = int(owner[first])
            assert spans.occ_start[level][d] <= j < spans.occ_stop[level][d]


# -- bitwise parity of the sharded descent ------------------------------------

def _emulated_sharded_descend(s, spans, levels, key, cfg, num_shards):
    """Sum of sequentially computed per-rank partials — arithmetically the
    shard_map psum (each box is one owner's value plus integer zeros)."""
    tgt = jnp.where((levels[0].ax_w > 0) & (levels[0].den_w > 0),
                    jnp.zeros((1,), jnp.int32), -1)
    for level in range(1, s.depth + 1):
        fn = jax.jit(lambda r, t, level=level: traversal.descend_level_partial(
            s, spans, r, level, levels[level], t, key, cfg))
        parts = [fn(jnp.int32(r), tgt) for r in range(num_shards)]
        tgt = sum(parts[1:], start=parts[0]) - 1
    return tgt


def _assert_descend_parity(pos, s, num_shards, seed=1, cfg=None):
    rng = np.random.default_rng(seed)
    n = s.n
    cfg = cfg or FMMConfig(c1=8, c2=8)
    ax = jnp.array(rng.integers(0, 3, n), jnp.float32)
    den = jnp.array(rng.integers(0, 3, n), jnp.float32)
    posj = jnp.asarray(pos)
    levels = octree.build_pyramid(s, posj, ax, den, cfg.delta, cfg.p)
    key = jax.random.key(seed)
    ref = jax.jit(lambda lv, k: traversal.descend(s, lv, k, cfg))(levels, key)
    spans = octree.owner_spans(s, num_shards)
    got = _emulated_sharded_descend(s, spans, levels, key, cfg, num_shards)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                  err_msg=f"shards={num_shards}")
    return levels, ax, den, posj, key, spans


@pytest.mark.parametrize("num_shards", [2, 4, 8])
def test_descend_sharded_bitwise_uniform(num_shards):
    pos, s = _uniform(256, seed=3)
    _assert_descend_parity(pos, s, num_shards)


def test_descend_sharded_bitwise_clustered_uneven():
    """Heavily clustered positions: one shard owns most occupied boxes,
    exercising the max-width slice clamping on the occupied lists."""
    rng = np.random.default_rng(7)
    cluster = rng.normal(80.0, 30.0, (200, 3))
    spread = rng.uniform(0, 1000.0, (56, 3))
    pos = np.clip(np.concatenate([cluster, spread]), 0, 999.0
                  ).astype(np.float32)
    pos, s = _sorted_structure(pos, depth=3)
    spans = octree.owner_spans(s, 4)
    w = np.asarray(spans.occ_stop[s.depth]) - np.asarray(
        spans.occ_start[s.depth])
    assert w.max() > 2 * w.min() + 1              # genuinely uneven
    _assert_descend_parity(pos, s, 4)


def test_descend_sharded_bitwise_empty_owner_shards():
    """All neurons in one corner: every occupied box is owned by shard 0;
    the other shards contribute all-zero partials at every level."""
    rng = np.random.default_rng(11)
    pos = (np.array([10.0, 10.0, 10.0], np.float32)
           + rng.uniform(0, 5.0, (64, 3)).astype(np.float32))
    pos, s = _sorted_structure(pos, depth=2)
    spans = octree.owner_spans(s, 4)
    for level in range(s.depth + 1):
        assert (spans.occ_start[level][1:] == spans.occ_stop[level][1:]).all()
    _assert_descend_parity(pos, s, 4)


def test_descend_sharded_bitwise_direct_tier():
    pos, s = _uniform(128, seed=9, depth=2)
    _assert_descend_parity(pos, s, 4, cfg=FMMConfig(tier_mode="direct",
                                                    c1=8, c2=8))


# -- bitwise parity of the row-sliced leaf resolution --------------------------

@pytest.mark.parametrize("num_shards", [2, 4])
def test_resolve_leaf_partners_rows_bitwise(num_shards):
    rng = np.random.default_rng(13)
    pos, s = _uniform(128, seed=13, depth=2)
    n = s.n
    cfg = FMMConfig(c1=8, c2=8)
    ax = jnp.array(rng.integers(0, 3, n), jnp.float32)
    den = jnp.array(rng.integers(0, 3, n), jnp.float32)
    posj = jnp.asarray(pos)
    levels = octree.build_pyramid(s, posj, ax, den, cfg.delta, cfg.p)
    key = jax.random.key(13)
    tgt = jax.jit(lambda lv, k: traversal.descend(s, lv, k, cfg))(levels, key)
    my_tgt = tgt[jnp.asarray(s.leaf_of)]
    full = jax.jit(lambda mt: traversal.resolve_leaf_partners(
        s, posj, ax, den, mt, key, cfg))(my_tgt)
    n_local = n // num_shards
    part = jax.jit(lambda r0, mt: traversal.resolve_leaf_partners(
        s, posj, ax, den, mt, key, cfg, row_start=r0))
    got = jnp.concatenate([
        part(jnp.int32(r * n_local),
             jax.lax.dynamic_slice_in_dim(my_tgt, r * n_local, n_local))
        for r in range(num_shards)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))


# -- bitwise parity of the slot-range-owned commit -----------------------------

@pytest.mark.parametrize("num_shards", [2, 4])
def test_insert_span_matches_insert(num_shards):
    rng = np.random.default_rng(17)
    n, e, k = 64, 256, 4
    state = synapses.SynapseState(
        src=jnp.array(rng.integers(0, n, e), jnp.int32),
        dst=jnp.array(rng.integers(0, n, e), jnp.int32),
        valid=jnp.array(rng.random(e) < 0.8))     # few free slots -> drops
    partner = jnp.array(
        np.where(rng.random(n) < 0.7, rng.integers(0, n, n), -1), jnp.int32)
    accepted = jnp.where(partner >= 0,
                         jnp.array(rng.integers(0, k + 1, n), jnp.int32), 0)
    ref_state, ref_dropped = jax.jit(
        lambda st: synapses.insert(st, partner, accepted, k))(state)
    assert int(ref_dropped) > 0                   # overflow path exercised

    e_local = e // num_shards
    sl = lambda x, r: jax.lax.dynamic_slice_in_dim(x, r * e_local, e_local)
    free = ~np.asarray(state.valid)
    placed_total, news = 0, []
    fn = jax.jit(lambda st, off: synapses.insert_span(
        st, partner, accepted, k, free_offset=off))
    for r in range(num_shards):
        local = synapses.SynapseState(*(sl(x, r) for x in state))
        offset = int(free[:r * e_local].sum())
        new_local, placed, total_new = fn(local, jnp.int32(offset))
        news.append(new_local)
        placed_total += int(placed)
    got = synapses.SynapseState(*(jnp.concatenate(cols)
                                  for cols in zip(*news)))
    for name in ("src", "dst", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(ref_state, name)),
                                      err_msg=name)
    assert int(total_new) - placed_total == int(ref_dropped)


# -- engine end-to-end (1-device mesh, in-process) -----------------------------

@pytest.mark.parametrize("find_phase", ["sharded", "replicated"])
def test_engine_find_phases_match_plain_engine_bitwise(find_phase):
    """Both find phases reproduce the plain engine end to end on a 1-device
    mesh — the replicated legacy path must not rot while sharded is the
    default (multi-device coverage: the slow subprocess test below)."""
    from repro.core.distributed import DistributedPlasticityEngine
    rng = np.random.default_rng(2)
    pos = rng.uniform(0, 1000.0, (128, 3)).astype(np.float32)
    msp_cfg = MSPConfig.calibrated(speedup=100.0)
    fmm_cfg = FMMConfig(c1=8, c2=8)
    ecfg = EngineConfig(method="fmm")
    eng = DistributedPlasticityEngine(pos, _mesh1(), "data", msp_cfg,
                                      fmm_cfg, ecfg, find_phase=find_phase)
    st, recs = eng.simulate(eng.init_state(), jax.random.key(0), 1200)
    seng = PlasticityEngine(eng.positions_np, msp_cfg, fmm_cfg, ecfg)
    ref_st, ref = seng.simulate(seng.init_state(), jax.random.key(0), 1200)
    assert int(np.asarray(recs.num_synapses)[-1]) > 5
    for name in RECORD_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(recs, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=f"{find_phase} {name}")
    np.testing.assert_array_equal(np.asarray(st.edges.valid),
                                  np.asarray(ref_st.edges.valid))


def test_sharded_deletion_path_matches_plain_step():
    """Force the rare any-excess deletion branch (degrees > floor(elements))
    and check one full update step matches the plain engine bitwise."""
    from repro.core.distributed import DistributedPlasticityEngine
    rng = np.random.default_rng(4)
    n = 64
    pos = rng.uniform(0, 1000.0, (n, 3)).astype(np.float32)
    msp_cfg = MSPConfig.calibrated(speedup=100.0)
    fmm_cfg = FMMConfig(c1=8, c2=8)
    ecfg = EngineConfig(method="fmm", edge_capacity_per_neuron=8)
    mesh = _mesh1()
    eng = DistributedPlasticityEngine(pos, mesh, "data", msp_cfg, fmm_cfg,
                                      ecfg, find_phase="sharded")
    seng = PlasticityEngine(eng.positions_np, msp_cfg, fmm_cfg, ecfg)
    state = seng.init_state()
    # ~5 random valid edges per neuron against floor(ax_elems) == 1: excess
    # on both sides, so the deletion cond's gather branch runs.
    e = eng.edge_capacity
    edges = synapses.SynapseState(
        src=jnp.array(rng.integers(0, n, e), jnp.int32),
        dst=jnp.array(rng.integers(0, n, e), jnp.int32),
        valid=jnp.array(rng.random(e) < 0.6))
    neurons = state.neurons._replace(
        ax_elems=jnp.full((n,), 1.7), den_elems=jnp.full((n,), 1.7))
    state = state._replace(edges=edges, neurons=neurons)
    out_deg = np.asarray(synapses.degrees(edges, n)[0])
    assert (out_deg > 1).any()                    # excess genuinely present

    key = jax.random.key(3)
    ref_st, _ = jax.jit(lambda s, k: seng.step(
        s, k, do_update=jnp.bool_(True)))(state, key)
    state_spec, rec_spec = eng._specs()
    dist_step = jax.jit(jax.shard_map(
        lambda s, k: eng.local_step(s, k, do_update=jnp.bool_(True)),
        mesh=mesh, in_specs=(state_spec, P()),
        out_specs=(state_spec, rec_spec), check_vma=False))
    got_st, _ = dist_step(state, key)
    for name in ("src", "dst", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(got_st.edges, name)),
                                      np.asarray(getattr(ref_st.edges, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(got_st.dropped),
                                  np.asarray(ref_st.dropped))


# -- knobs, counters, specs ----------------------------------------------------

def test_find_phase_validation_and_messages():
    from repro.core.distributed import DistributedPlasticityEngine
    rng = np.random.default_rng(2)
    pos = rng.uniform(0, 1000.0, (96, 3)).astype(np.float32)
    with pytest.raises(ValueError, match="find_phase"):
        DistributedPlasticityEngine(pos, _mesh1(), "data",
                                    find_phase="bogus")
    # The divisibility error names the SHARD COUNT as the divisor (the old
    # message had it inverted: "n must divide the neuron axis size").  The
    # check fires before any mesh use, so a stub with the right shape
    # exercises multi-shard validation on a 1-device host.
    class _FakeMesh:
        shape = {"data": 3}
    with pytest.raises(ValueError,
                       match=r"shard count \(3\) must divide the neuron"):
        DistributedPlasticityEngine(
            rng.uniform(0, 1000.0, (97, 3)).astype(np.float32),
            _FakeMesh(), "data")


def test_find_phase_work_counters():
    from repro.core.distributed import DistributedPlasticityEngine
    rng = np.random.default_rng(6)
    pos = rng.uniform(0, 1000.0, (128, 3)).astype(np.float32)
    eng = DistributedPlasticityEngine(pos, _mesh1(), "data")
    rep = eng.find_phase_work("replicated")
    sh = eng.find_phase_work("sharded")
    assert sh["descent_boxes"] <= rep["descent_boxes"]
    assert sh["resolution_rows"] == eng.n // eng.num_shards
    assert rep["resolution_rows"] == eng.n
    # the O(E) edge-table gather dominates the replicated payload and is
    # gone from the sharded common path
    assert rep["payload_elems"] > 3 * eng.edge_capacity
    assert sh["payload_elems"] < rep["payload_elems"]
    assert sh["payload_elems_deletion_path"] == 3 * eng.edge_capacity


def test_find_phase_specs():
    assert rules.descent_map_spec() == P()
    assert rules.find_request_spec() == P("data")
    assert rules.find_request_spec("batch") == P("batch")


def test_sweep_threads_find_phase():
    from repro.core.distributed import DistributedEnsembleEngine
    from repro.launch import sweep
    rng = np.random.default_rng(8)
    pos = rng.uniform(0, 1000.0, (96, 3)).astype(np.float32)
    seng = PlasticityEngine(pos, MSPConfig.calibrated(speedup=100.0),
                            FMMConfig(c1=8, c2=8), EngineConfig())
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("ensemble", "data"))
    ens = sweep.make_ensemble(seng, mesh, find_phase="replicated")
    assert isinstance(ens, DistributedEnsembleEngine)
    assert ens.engine.find_phase == "replicated"
    assert sweep.make_ensemble(seng, mesh).engine.find_phase == "sharded"
    # an already-distributed engine keeps its own knobs; a CONFLICTING
    # explicit value raises instead of being silently ignored
    deng = ens.engine
    assert sweep.make_ensemble(deng, mesh).engine is deng
    assert sweep.make_ensemble(deng, mesh,
                               find_phase="replicated").engine is deng
    with pytest.raises(ValueError, match="find_phase"):
        sweep.make_ensemble(deng, mesh, find_phase="sharded")
    with pytest.raises(ValueError, match="pyramid_partials"):
        sweep.make_ensemble(deng, mesh, pyramid_partials="masked")


# -- multi-device subprocess ---------------------------------------------------

_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.distributed import (DistributedEnsembleEngine,
                                    DistributedPlasticityEngine)
from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig
from repro.launch.mesh import make_sweep_mesh

assert len(jax.devices()) == 8
RECORD_FIELDS = ("num_synapses", "calcium_mean", "calcium_std", "spike_rate")
msp_cfg = MSPConfig.calibrated(speedup=100.0)
fmm_cfg = FMMConfig(c1=8, c2=8)
ecfg = EngineConfig(method="fmm")

def parity(pos, p, steps, tag, ecfg=ecfg, fmm_cfg=fmm_cfg, min_syn=5):
    mesh = Mesh(np.array(jax.devices()[:p]).reshape(p), ("data",))
    eng = DistributedPlasticityEngine(pos, mesh, "data", msp_cfg, fmm_cfg,
                                      ecfg, find_phase="sharded")
    seng = PlasticityEngine(eng.positions_np, msp_cfg, fmm_cfg, ecfg)
    st, recs = eng.simulate(eng.init_state(), jax.random.key(0), steps)
    ref_st, ref = seng.simulate(seng.init_state(), jax.random.key(0), steps)
    assert int(np.asarray(recs.num_synapses)[-1]) > min_syn
    for name in RECORD_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(recs, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=f"{tag} p={p} {name}")
    for name in ("src", "dst", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(st.edges, name)),
                                      np.asarray(getattr(ref_st.edges, name)),
                                      err_msg=f"{tag} p={p} edges.{name}")
    print(f"{tag}_P{p}_OK")

# --- 1. uniform positions, p in {2, 4, 8} -------------------------------
rng = np.random.default_rng(0)
pos = rng.uniform(0, 1000.0, (256, 3)).astype(np.float32)
for p in (2, 4, 8):
    parity(pos, p, 1500, "UNIFORM")

# --- 2. clustered positions: uneven occupied-owner spans ----------------
cluster = rng.normal(80.0, 30.0, (200, 3))
spread = rng.uniform(0, 1000.0, (56, 3))
pos_c = np.clip(np.concatenate([cluster, spread]), 0, 999.0
                ).astype(np.float32)
parity(pos_c, 4, 1000, "CLUSTERED")

# --- 3. empty-owner shards: all neurons in one corner box ---------------
# (this layout bootstraps slowly: first synapses near step ~900)
pos_e = (np.array([10.0, 10.0, 10.0], np.float32)
         + rng.uniform(0, 5.0, (64, 3)).astype(np.float32))
parity(pos_e, 4, 1500, "EMPTYOWNER")

# --- 4. swept KernelParams on a 2-D (ensemble x data) mesh --------------
mesh = make_sweep_mesh(ensemble=2, data=4)
deng = DistributedPlasticityEngine(pos, mesh, "data", msp_cfg,
                                   FMMConfig(c1=8, c2=8, sigma=400.0), ecfg,
                                   find_phase="sharded")
ens = DistributedEnsembleEngine(deng)
seng = PlasticityEngine(deng.positions_np, msp_cfg,
                        FMMConfig(c1=8, c2=8, sigma=400.0), ecfg)
k, steps = 2, 1200
keys = jax.random.split(jax.random.key(7), k)
params = ens.default_params(k)._replace(
    sigma=jnp.asarray([400.0, 750.0], jnp.float32),
    inhibitory_fraction=jnp.asarray([0.0, 0.25], jnp.float32))
_, recp = ens.simulate(ens.init_states(k), keys, steps, params)
for r in range(k):
    pr = jax.tree.map(lambda x: x[r], params)
    _, ref = seng.simulate(seng.init_state(), keys[r], steps, pr)
    for name in RECORD_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(recp, name)[:, r]),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=f"sweep {name} r={r}")
print("SWEPT_2D_OK")
'''


@pytest.mark.slow
def test_find_sharded_multidevice_subprocess():
    """find_phase="sharded" reproduces single-device simulate bitwise for
    p in {2,4,8} forced host devices — records AND the committed edge
    table — including clustered/empty-owner layouts and swept KernelParams
    under DistributedEnsembleEngine (the CI multi-device job runs this)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-3000:]
    for marker in ("UNIFORM_P2_OK", "UNIFORM_P4_OK", "UNIFORM_P8_OK",
                   "CLUSTERED_P4_OK", "EMPTYOWNER_P4_OK", "SWEPT_2D_OK"):
        assert marker in res.stdout
