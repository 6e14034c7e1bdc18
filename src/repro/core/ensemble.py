"""Ensemble subsystem: K independent MSP simulations in ONE compiled program.

Large-scale brain-simulation platforms treat many-configuration sweeps as a
first-class workload (CORTEX, arXiv:2406.03762; the Digital Twin Brain
platform, arXiv:2308.01241): parameter exploration, seed ensembles for
uncertainty bands, and scenario diversity all need many *independent*
replicas of the same network.  The engine's step is a pure function of
(state, key[, params]), so the whole batch is one `jax.vmap`:

  * every `SimState` leaf gains a leading replica axis (K, ...);
  * per-replica RNG keys drive independent stochastic trajectories;
  * per-replica kernel knobs (`engine.KernelParams`: sigma, the Alg. 2 tier
    thresholds c1/c2, and the inhibitory fraction) ride along as traced
    scalars, so one compilation serves K *differently parameterised* brains.

Two scheduling details keep the batched program as cheap as K/devices
sequential ones:

  * the connectivity-update predicate is computed from the UNBATCHED scan
    index and passed into `engine.step` — under vmap a per-replica predicate
    would lower `lax.cond` to a select that runs the expensive update branch
    every step (measured 5x slowdown at n=256);
  * with a mesh, the replica axis is sharded via `shard_map` (specs from
    sharding/rules.ensemble_spec, mesh from launch/mesh.make_ensemble_mesh).
    Replicas never communicate, so each device runs its slice with zero
    collectives — embarrassingly parallel, unlike the neuron-axis
    decomposition in core/distributed.py.

Correctness contract (tests/test_ensemble.py): a K-replica batched run with
keys [k_0..k_{K-1}] reproduces K sequential `PlasticityEngine.simulate`
runs with the same keys on the recorded observables.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.engine import (KernelParams, PlasticityEngine, SimState,
                               StepRecord)
from repro.sharding import rules


def scan_replicas(step_fn, states: SimState, keys: jax.Array,
                  params: Optional[KernelParams], num_steps: int,
                  interval: int, probes=None, probe_states=None, merge=None,
                  extras=None, fold_by_replica_step: bool = False,
                  do_update_fn=None):
    """The K-replica scan shared by EnsembleEngine (replica axis only) and
    distributed.DistributedEnsembleEngine (replica axis x data axis).

    step_fn(state, key, params, do_update) -> (state, record) is vmapped over
    the leading replica axis of (states, keys, params), inside the named
    scope `replicas`: the step's ops read `replicas/vmap(activity/...)` and
    the update's `replicas/vmap()/.../update/...` in a device trace
    (docs/tracing.md).  Two scheduling
    details keep the batched program as cheap as sequential ones:

      * per-replica RNG keys fold by the CARRIED global step (see
        engine.simulate): bitwise the same as folding by the scan index for
        fresh runs, fresh streams for chunked continuations;
      * the connectivity-update predicate is computed from the UNBATCHED
        carried counter — replicas step in lockstep, so replica 0's counter
        stands for all, and an unbatched predicate keeps the update a
        `lax.cond` under vmap (a batched one would lower to a select that
        runs the expensive branch every step).  Sequential step checks
        state.step AFTER the increment; st.step[0] + 1 matches that for any
        starting step (chunked/resumed simulate calls included).

    probes/probe_states/merge: optional core/probes recording — a static
    ProbeSet, its (K,)-leading ProbeState carry, and the engine's data-axis
    reduction for `needs_merge` probes (None off the 2-D mesh).  Recording
    happens inside the per-replica vmapped step, so each replica's rows are
    bitwise identical to a sequential probed run with the same key
    (DESIGN.md §12).  Returns (states, probe_states, records) — the probe
    slot is None when no probes ride along.

    Serving hooks (repro/serve, DESIGN.md §14) — all default-off, the
    lockstep ensemble path above is bitwise untouched:

      * extras: optional (K,)-leading pytree of per-replica scalars (active
        row counts, per-session step targets).  When given, `step_fn` owns
        the whole per-replica step — signature
        (state, key, params, do_upd, extra, probe_state)
        -> (state, probe_state, record) — including probe recording and any
        freeze logic, because a served slot may need to HOLD its state when
        its session finished mid-round.
      * fold_by_replica_step: fold each replica's key by ITS OWN carried
        step counter instead of replica 0's.  Served slots are admitted at
        different times, so their counters disagree — per-replica folding
        reproduces exactly the fold_in(key, step) stream an isolated
        `engine.simulate` of that session would draw.
      * do_update_fn: optional scan-index predicate i -> bool overriding
        the carried-counter connectivity-update schedule.  The service
        admits/restores only at round boundaries with round length a
        multiple of update_interval, so every live slot's counter satisfies
        step ≡ i (mod interval) and the unbatched scan-index predicate is
        correct for all of them — while finished (frozen) slots, whose
        counters have stopped advancing, would poison a carried-counter
        predicate.
    """
    def body(carry, i):
        st, ps = carry
        if fold_by_replica_step:
            ki = jax.vmap(jax.random.fold_in)(keys, st.step)
        else:
            ki = jax.vmap(lambda k: jax.random.fold_in(k, st.step[0]))(keys)
        if do_update_fn is not None:
            do_upd = do_update_fn(i)
        else:
            do_upd = ((st.step[0] + 1) % interval) == 0

        if extras is not None:
            def one_served(s, k, p, e, q):
                return step_fn(s, k, p, do_upd, e, q)
            with jax.named_scope("replicas"):
                if params is None:
                    st, ps, rec = jax.vmap(
                        lambda s, k, e, q: one_served(s, k, None, e, q))(
                            st, ki, extras, ps)
                else:
                    st, ps, rec = jax.vmap(one_served)(st, ki, params,
                                                       extras, ps)
            return (st, ps), rec

        def one(s, k, p, q):
            prev = s
            s, rec = step_fn(s, k, p, do_upd)
            if probes is not None:
                q = probes.record(q, prev, s, rec, merge=merge)
            return s, q, rec

        with jax.named_scope("replicas"):
            if params is None:
                st, ps, rec = jax.vmap(lambda s, k, q: one(s, k, None, q))(
                    st, ki, ps)
            else:
                st, ps, rec = jax.vmap(one)(st, ki, params, ps)
        return (st, ps), rec

    (states, probe_states), recs = jax.lax.scan(
        body, (states, probe_states), jnp.arange(num_steps, dtype=jnp.int32))
    return states, probe_states, recs


class EnsembleEngine:
    """Runs K replicas of one `PlasticityEngine` as a single batched program.

    engine: the single-brain engine (owns the static octree structure, which
            all replicas share — positions are identical across the ensemble;
            only state, keys, and `KernelParams` knobs vary per replica).
    mesh:   optional 1-D device mesh; the replica axis is sharded over
            `mesh.shape[axis]` devices (the axis size must divide K).
    """

    def __init__(self, engine: PlasticityEngine, mesh: Optional[Mesh] = None,
                 axis: str = "ensemble"):
        self.engine = engine
        self.mesh = mesh
        self.axis = axis
        if mesh is not None and axis not in mesh.shape:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh.shape}")

    # -- batched state ------------------------------------------------------
    def init_states(self, num_replicas: int) -> SimState:
        """Fresh (K, ...)-leading state for every replica."""
        base = self.engine.init_state()
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (num_replicas,) + x.shape), base)

    def default_params(self, num_replicas: int) -> KernelParams:
        """(K,) params equal to the engine's static configs (identity sweep)."""
        base = KernelParams.from_configs(self.engine.fmm_cfg,
                                         self.engine.engine_cfg)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (num_replicas,) + x.shape), base)

    # -- batched simulation --------------------------------------------------
    def _sim(self, states: SimState, keys: jax.Array,
             params: Optional[KernelParams], num_steps: int,
             probes=None, probe_states=None):
        step_fn = lambda s, k, p, upd: self.engine.step(s, k, p,
                                                        do_update=upd)
        return scan_replicas(step_fn, states, keys, params, num_steps,
                             self.engine.msp_cfg.update_interval,
                             probes=probes, probe_states=probe_states)

    @functools.partial(jax.jit, static_argnums=(0, 3, 5))
    def simulate(self, states: SimState, keys: jax.Array, num_steps: int,
                 params: Optional[KernelParams] = None,
                 probes=None, probe_states=None):
        """Run all replicas `num_steps` steps.

        states: (K, ...)-leading SimState (init_states).
        keys:   (K,) typed PRNG key array — one independent stream per replica.
        params: optional (K,)-leading KernelParams (launch/sweep.pack_params).
        probes: optional static core/probes.ProbeSet; probe_states the
                (K,)-leading carry (probes.init(n, batch=K); None = fresh).
                Pure observers — (states, records) are bitwise unchanged.
        Returns (final states, StepRecord with (num_steps, K) trajectories),
        plus the final probe states when probes ride along.
        """
        if probes is not None and probe_states is None:
            probe_states = probes.init(self.engine.n,
                                       start_step=states.step,
                                       batch=states.step.shape[0])
        if self.mesh is None:
            states, probe_states, recs = self._sim(
                states, keys, params, num_steps, probes, probe_states)
        else:
            state_spec = rules.ensemble_spec(states, self.axis)
            param_spec = rules.ensemble_spec(params, self.axis)
            probe_spec = rules.ensemble_spec(probe_states, self.axis)
            rec_spec = StepRecord(*(P(None, self.axis),)
                                  * len(StepRecord._fields))
            sharded = jax.shard_map(
                lambda st, k, pr, ps: self._sim(st, k, pr, num_steps,
                                                probes, ps),
                mesh=self.mesh,
                in_specs=(state_spec, P(self.axis), param_spec, probe_spec),
                out_specs=(state_spec, probe_spec, rec_spec),
                check_vma=False)
            states, probe_states, recs = sharded(states, keys, params,
                                                 probe_states)
        if probes is None:
            return states, recs
        return states, recs, probe_states


# -- contract-auditor registry (repro.audit, DESIGN.md §15) -----------------
AUDIT = {
    "collectives_allowed": False,  # replicas must stay independent (§7)
    "entry_points": {
        "ensemble.simulate": {
            "rules": {
                "R1": {},
                # Replica-local phases: a collective over ANY axis here
                # couples replicas and breaks the per-replica contract.
                "R2": {"allowed_axes": ()},
                "R4": {"allowlist": ()},
            },
        },
    },
}
