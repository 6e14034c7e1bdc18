"""The §10 deletion caveat is closed: under the ensemble vmap the rare
any-excess deletion stays a genuine `lax.cond` (DESIGN.md §13).

Two halves:

* lowering — audited via `repro.audit` rule R3 (this test is a consumer of
  the library API that generalized its original hand-rolled jaxpr walker,
  DESIGN.md §15): the jaxpr of the vmapped sharded connectivity update
  contains NO O(K*E) edge-table all_gather outside a cond branch (the
  former caveat: a per-replica predicate lowered the cond to a `select`
  that ran the gather unconditionally on 2-D sweep meshes), while the
  gather is still present INSIDE the branch for the genuine-excess case;
* values — a forced-deletion step under a K=2 ensemble on a 2-D sweep
  mesh stays bitwise equal to independent single-device runs.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.audit import audit_jaxpr
from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig
from repro.core.distributed import (DistributedEnsembleEngine,
                                    DistributedPlasticityEngine)

N = 96
K = 2


def _dist_engine():
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 1000.0, (N, 3)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("ensemble", "data"))
    return DistributedPlasticityEngine(
        pos, mesh, "data", MSPConfig.calibrated(speedup=400.0),
        FMMConfig(c1=8, c2=8), EngineConfig(method="fmm"))


def test_vmapped_update_keeps_deletion_gather_conditional():
    eng = _dist_engine()
    states = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (K,) + x.shape), eng.init_state())
    keys = jax.random.split(jax.random.key(0), K)

    def batched_update(st, ks):
        return jax.vmap(
            lambda s, k: eng._conn_update_sharded(s, kconn=k, params=None)
        )(st, ks)

    state_spec, _ = eng._specs()
    bspec = jax.tree.map(lambda s: P(None, *s), state_spec)
    sharded = jax.shard_map(batched_update, mesh=eng.mesh,
                            in_specs=(bspec, P()), out_specs=bspec,
                            check_vma=False)
    jaxpr = jax.make_jaxpr(sharded)(states, keys)

    # Rule R3 asserts both directions at once: every edge-table-sized
    # all_gather sits under a real cond (nothing lowered to select), and at
    # least one conditional gather exists (the deletion path is present).
    threshold = K * eng.edge_capacity  # the batched edge-table gather
    findings = audit_jaxpr(jaxpr, {"R3": {"min_size": threshold}},
                           entry="test.vmapped_update")
    assert not findings, "\n".join(f.format() for f in findings)


def test_forced_deletion_bitwise_under_2d_ensemble():
    """Grow a network, zero every synaptic element and pin calcium above
    eps, then step through the next update on the 2-D mesh: the massacre
    step's synapse counts (and all records) stay bitwise equal to
    independent single-device runs."""
    eng = _dist_engine()
    dens = DistributedEnsembleEngine(eng)
    seng = PlasticityEngine(
        eng.positions_np, MSPConfig.calibrated(speedup=400.0),
        FMMConfig(c1=8, c2=8), EngineConfig(method="fmm"))

    key = jax.random.key(4)
    grown, recs = seng.simulate(seng.init_state(), key, 600)
    assert int(np.asarray(recs.num_synapses)[-1]) > 50

    neurons = grown.neurons._replace(
        ax_elems=jnp.zeros_like(grown.neurons.ax_elems),
        den_elems=jnp.zeros_like(grown.neurons.den_elems),
        calcium=jnp.full_like(grown.neurons.calcium, 2.0))
    doctored = grown._replace(neurons=neurons)

    steps = seng.msp_cfg.update_interval + 5
    keys = jax.random.split(jax.random.key(9), K)
    batched = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (K,) + x.shape), doctored)
    _, recs_d = dens.simulate(batched, keys, steps)

    syn_d = np.asarray(recs_d.num_synapses)          # (steps, K)
    assert syn_d.min() == 0, "forced deletion never fired"
    for r in range(K):
        _, ref = seng.simulate(doctored, keys[r], steps)
        for name in ref._fields:
            np.testing.assert_array_equal(
                syn_d[:, r] if name == "num_synapses"
                else np.asarray(getattr(recs_d, name))[:, r],
                np.asarray(getattr(ref, name)), err_msg=f"r={r} {name}")
