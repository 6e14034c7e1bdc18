"""Simulation-as-a-service: continuous batching over the ensemble axis.

`SimulationService` turns the single-brain engine into a multi-tenant
server, the way TGI-style LLM servers turn one transformer into a token
service: K *slots* share ONE compiled step program (core/ensemble.py's
`scan_replicas` over the replica axis), and live sessions are packed into
slots as they arrive, evicted to checkpoints when idle, and restored —
possibly into different slots — when they wake up.

Three mechanisms make heterogeneous sessions batchable bitwise-exactly
(DESIGN.md §14):

  * **Padded subdomains**: every slot simulates the service's full position
    pool (n_slot rows), but a session of size n runs with a traced
    `n_active = n` — rows >= n are masked inert in the neuron step and
    contribute exact zeros to every reduction, so a padded session's
    records, edge tables and probe rows bitwise equal an isolated
    `PlasticityEngine(pool[:n])` run.
  * **Counter-mode RNG** (`EngineConfig.rng="counter"`, core/streams.py):
    every random draw is keyed by its logical index (neuron row, edge
    slot, octree box) instead of its position in a size-(n,) batch draw,
    so streams are invariant to the pool width.
  * **Round-based scheduling**: the service steps all slots `round_steps`
    at a time with `round_steps % update_interval == 0`, and admits or
    restores sessions only at round boundaries — every live slot's step
    counter therefore satisfies step ≡ i (mod interval) against the round's
    scan index i, keeping the connectivity-update predicate a single
    unbatched `lax.cond` (the 5x-slowdown rule, core/ensemble.py).
    Sessions whose budget ends mid-round freeze in place: the slot's state
    and probe rows are `where(step < target)`-held until harvest.

The host-side bookkeeping (who is in which slot) lives in
serve/batcher.SlotBatcher, whose invariants are property-tested
independently of the arrays.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.core.engine import (EngineConfig, KernelParams, PlasticityEngine, SimState, StepRecord)
from repro.core.ensemble import scan_replicas
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig
from repro.serve import session as sess
from repro.serve.batcher import SlotBatcher
from repro.sharding import rules


class SlotExtras(NamedTuple):
    """Per-slot traced scalars the served step threads through the scan.

    n_active: () int32 — the occupant session's network size (0 = empty
              slot; the whole slot is then masked inert).
    target:   () int32 — absolute step count at which the occupant's budget
              ends; the slot freezes (state and probes held) once
              state.step reaches it.
    """

    n_active: jnp.ndarray
    target: jnp.ndarray


@dataclasses.dataclass
class SessionResult:
    """Everything a finished session's client gets back."""

    records: StepRecord  # (num_steps,) numpy per field
    final_state: SimState  # full-slot-width, host numpy
    probe_rows: Optional[Dict[str, np.ndarray]]  # name -> (num_steps, ...)
    n_neurons: int


class SimulationService:
    """Session-managed, continuously-batched simulation server.

    positions_pool: (n_pool, 3) float32 — the shared position prefix pool.
        A session of size n simulates positions_pool[:n]; its isolated
        reference is `PlasticityEngine(positions_pool[:n], ...)` with the
        SAME configs (including the pool-resolved octree depth).
    num_slots:   K, the replica-axis width of the compiled round program.
    round_steps: steps per round; must be a positive multiple of
        msp_cfg.update_interval (round-boundary alignment, module docs).
    checkpoint_dir: root for per-session eviction checkpoints.
    probes: optional static core/probes.ProbeSet recorded for every slot;
        sessions opt in per-request (`record_probes`) to have their rows
        harvested.  chunk_size must cover the largest session budget.
    mesh/axis: optional 1-D device mesh sharding the slot axis (the
        divisibility and zero-collective properties of core/ensemble.py).
    """

    def __init__(
        self,
        positions_pool,
        msp_cfg: MSPConfig,
        fmm_cfg: FMMConfig,
        engine_cfg: Optional[EngineConfig] = None,
        *,
        num_slots: int,
        round_steps: int,
        checkpoint_dir: str,
        probes=None,
        mesh=None,
        axis: str = "ensemble",
    ):
        base_cfg = engine_cfg or EngineConfig()
        if round_steps <= 0 or round_steps % msp_cfg.update_interval != 0:
            raise ValueError(
                f"round_steps={round_steps} must be a positive multiple of "
                f"update_interval={msp_cfg.update_interval}"
            )
        # Resolve the octree depth ONCE from the full pool: auto-depth is a
        # function of n, and a session must see the same tree geometry in
        # its padded slot and in its isolated reference engine.
        if base_cfg.depth is None:
            probe_engine = PlasticityEngine(positions_pool, msp_cfg, fmm_cfg, base_cfg)
            base_cfg = dataclasses.replace(base_cfg, depth=int(probe_engine.structure.depth))
        # Counter-mode RNG is what makes draws invariant to the pool width
        # (module docs); the service refuses to serve without it.
        self.engine_cfg = dataclasses.replace(base_cfg, rng="counter")
        self.msp_cfg = msp_cfg
        self.fmm_cfg = fmm_cfg
        self.pool = np.asarray(positions_pool, np.float32)
        self.engine = PlasticityEngine(self.pool, msp_cfg, fmm_cfg, self.engine_cfg)
        self.num_slots = int(num_slots)
        self.round_steps = int(round_steps)
        self.checkpoint_dir = checkpoint_dir
        self.probes = probes
        self.mesh = mesh
        self.axis = axis
        if mesh is not None:
            if axis not in mesh.shape:
                raise ValueError(f"mesh has no {axis!r} axis: {mesh.shape}")
            if self.num_slots % mesh.shape[axis] != 0:
                raise ValueError(
                    f"num_slots={num_slots} must divide over " f"{mesh.shape[axis]} devices"
                )

        self.batcher = SlotBatcher(self.num_slots)
        self.sessions: Dict[str, sess.Session] = {}
        self.round_idx = 0
        self.occupancy_log: List[int] = []  # live slots per executed round

        K = self.num_slots
        base = self.engine.init_state()
        self.states: SimState = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (K,) + x.shape).copy(),
            base,
        )
        self.extras = SlotExtras(
            n_active=jnp.zeros((K,), jnp.int32),
            target=jnp.zeros((K,), jnp.int32),
        )
        # Raw uint32 key data ((K, ...)): trivially checkpointable and
        # slot-updatable; wrapped to typed keys inside the round program.
        self.key_data = jnp.broadcast_to(
            jax.random.key_data(jax.random.key(0)),
            (K,) + jax.random.key_data(jax.random.key(0)).shape,
        ).copy()
        self.params: KernelParams = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (K,) + x.shape).copy(),
            KernelParams.from_configs(fmm_cfg, self.engine_cfg),
        )
        self.probe_states = (probes.init(self.engine.n, batch=K) if probes is not None else None)
        self._round_fn = self._build_round_fn()
        self._managers: Dict[str, CheckpointManager] = {}

    # -- compiled round ------------------------------------------------------
    def _build_round_fn(self):
        engine, probes = self.engine, self.probes
        interval = self.msp_cfg.update_interval
        R = self.round_steps

        def slot_step(s, k, p, upd, e, q):
            keep = s.step < e.target
            prev = s
            s2, rec = engine.step(s, k, p, do_update=upd, n_active=e.n_active)
            if probes is not None:
                q2 = probes.record(q, prev, s2, rec)
                q2 = jax.tree.map(lambda new, old: jnp.where(keep, new, old), q2, q)
            else:
                q2 = q
            s2 = jax.tree.map(lambda new, old: jnp.where(keep, new, old), s2, s)
            return s2, q2, rec

        def round_body(states, key_data, params, extras, probe_states):
            keys = jax.random.wrap_key_data(key_data)
            return scan_replicas(
                slot_step,
                states,
                keys,
                params,
                R,
                interval,
                probe_states=probe_states,
                extras=extras,
                fold_by_replica_step=True,
                do_update_fn=lambda i: ((i + 1) % interval) == 0,
            )

        if self.mesh is None:
            return jax.jit(round_body)

        rec_template = StepRecord(*(0.0,) * len(StepRecord._fields))
        in_specs, out_specs = rules.serve_round_specs(
            self.states,
            self.params,
            self.extras,
            self.probe_states,
            rec_template,
            self.axis,
        )
        sharded = jax.shard_map(
            round_body,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(sharded)

    # -- slot plumbing -------------------------------------------------------
    def _write_slot(
        self,
        slot: int,
        state: SimState,
        key_data,
        n_active: int,
        target: int,
        probe_state=None,
    ):
        self.states = jax.tree.map(lambda b, v: b.at[slot].set(v), self.states, state)
        self.extras = SlotExtras(
            n_active=self.extras.n_active.at[slot].set(n_active),
            target=self.extras.target.at[slot].set(target),
        )
        self.key_data = self.key_data.at[slot].set(key_data)
        if self.probes is not None and probe_state is not None:
            self.probe_states = jax.tree.map(
                lambda b, v: b.at[slot].set(v),
                self.probe_states,
                probe_state,
            )

    def _clear_slot(self, slot: int):
        self._write_slot(
            slot,
            self.engine.init_state(),
            jax.random.key_data(jax.random.key(0)),
            0,
            0,
            self.probes.init(self.engine.n) if self.probes is not None else None,
        )

    def _slice_slot(self, slot: int):
        state = jax.tree.map(lambda x: x[slot], self.states)
        probe = (
            jax.tree.map(lambda x: x[slot], self.probe_states) if self.probes is not None else None
        )
        return state, probe

    def _manager(self, session_id: str) -> CheckpointManager:
        if session_id not in self._managers:
            self._managers[session_id] = CheckpointManager(
                os.path.join(self.checkpoint_dir, session_id),
                keep=2,
                async_save=False,  # durable BEFORE the slot is reused (I2)
            )
        return self._managers[session_id]

    def _ckpt_tree(self, state, probe):
        tree = {"state": state}
        if self.probes is not None:
            tree["probe"] = probe
        return tree

    # -- client API ----------------------------------------------------------
    def submit(self, request: sess.SessionRequest) -> str:
        if request.session_id in self.sessions:
            raise ValueError(f"duplicate session id {request.session_id}")
        if request.n_neurons > self.engine.n:
            raise ValueError(
                f"n_neurons={request.n_neurons} exceeds the pool size " f"{self.engine.n}"
            )
        if request.record_probes:
            if self.probes is None:
                raise ValueError("service has no probe set configured")
            if request.num_steps > self.probes.chunk_size:
                raise ValueError(
                    f"num_steps={request.num_steps} exceeds probe "
                    f"chunk_size={self.probes.chunk_size}"
                )
        self.sessions[request.session_id] = sess.Session(request=request)
        self.batcher.enqueue(request.session_id)
        return request.session_id

    def isolated_engine(self, n_neurons: int) -> PlasticityEngine:
        """The reference engine a session's results must bitwise match:
        the pool prefix of its size, the SAME configs (pool-resolved
        depth, counter RNG)."""
        return PlasticityEngine(self.pool[:n_neurons], self.msp_cfg, self.fmm_cfg, self.engine_cfg)

    # -- scheduling ----------------------------------------------------------
    def _requeue_awake(self):
        for s in self.sessions.values():
            if (
                s.status == sess.EVICTED
                and s.idled
                and self.round_idx >= s.idle_until_round
                and s.remaining > 0
            ):
                self.batcher.enqueue(s.request.session_id, restore=True)
                s.status = sess.QUEUED

    def _admit(self, events: List[str]):
        while (slot_assignment := self.batcher.admit_next()) is not None:
            sid, slot, is_restore = slot_assignment
            s = self.sessions[sid]
            req = s.request
            if is_restore:
                template = self._ckpt_tree(
                    self.engine.init_state(),
                    self.probes.init(self.engine.n) if self.probes is not None else None,
                )
                tree, _ = self._manager(sid).restore(template)
                state = tree["state"]
                probe = tree.get("probe")
                assert int(state.step) == s.steps_done
                events.append(f"restored {sid} slot={slot} " f"step={s.steps_done}")
            else:
                state = self.engine.init_state()
                probe = self.probes.init(self.engine.n) if self.probes is not None else None
                events.append(
                    f"admitted {sid} slot={slot} " f"n={req.n_neurons} steps={req.num_steps}"
                )
            self._write_slot(
                slot,
                state,
                jax.random.key_data(jax.random.key(req.seed)),
                req.n_neurons,
                req.num_steps,
                probe,
            )
            s.status = sess.RUNNING
            s.slot = slot

    def _harvest_round(self, recs: StepRecord, events: List[str]):
        rec_np = jax.tree.map(np.asarray, recs)  # fields (R, K)
        boundary = []
        for sid, slot in self.batcher.live_items():
            s = self.sessions[sid]
            took = min(self.round_steps, s.remaining)
            s.record_chunks.append(jax.tree.map(lambda f: f[:took, slot], rec_np))
            s.steps_done += took
            boundary.append((sid, slot, s))
        for sid, slot, s in boundary:
            req = s.request
            if s.remaining == 0:
                state, probe = self._slice_slot(slot)
                self._finish(s, state, probe)
                self.batcher.release(sid, finished=True)
                self._clear_slot(slot)
                events.append(f"finished {sid} step={s.steps_done}")
            elif (req.idle_after is not None and not s.idled and s.steps_done >= req.idle_after):
                state, probe = self._slice_slot(slot)
                mgr = self._manager(sid)
                mgr.save(self._ckpt_tree(state, probe), s.steps_done)
                self.batcher.release(sid, finished=False)
                self._clear_slot(slot)
                s.status = sess.EVICTED
                s.slot = None
                s.idled = True
                s.idle_until_round = self.round_idx + 1 + req.idle_rounds
                events.append(
                    f"evicted {sid} step={s.steps_done} " f"until_round={s.idle_until_round}"
                )

    def _finish(self, s: sess.Session, state: SimState, probe):
        s.status = sess.FINISHED
        s.slot = None
        s.final_state = jax.tree.map(np.asarray, state)
        if self.probes is not None and s.request.record_probes:
            rows = int(s.steps_done)
            s.probe_rows = {name: np.asarray(buf)[:rows] for name, buf in probe.buffers.items()}
        else:
            s.probe_rows = None

    def run_round(self) -> List[str]:
        """One scheduling round: wake -> admit -> step R -> harvest."""
        events: List[str] = []
        self._requeue_awake()
        self._admit(events)
        if self.batcher.live > 0:
            self.occupancy_log.append(self.batcher.live)
            self.states, self.probe_states, recs = self._round_fn(
                self.states,
                self.key_data,
                self.params,
                self.extras,
                self.probe_states,
            )
            self._harvest_round(recs, events)
        self.round_idx += 1
        return events

    def run_to_completion(self, max_rounds: int = 10_000) -> List[str]:
        """Rounds until every submitted session is FINISHED."""
        events: List[str] = []
        for _ in range(max_rounds):
            if all(s.status == sess.FINISHED for s in self.sessions.values()):
                return events
            events.extend(self.run_round())
        raise RuntimeError(
            f"sessions still unfinished after {max_rounds} rounds: "
            f"{[sid for sid, s in self.sessions.items() if s.status != sess.FINISHED]}"
        )

    # -- results -------------------------------------------------------------
    def result(self, session_id: str) -> SessionResult:
        if session_id not in self.sessions:
            raise KeyError(f"unknown session id {session_id!r}")
        s = self.sessions[session_id]
        if s.status != sess.FINISHED:
            raise RuntimeError(f"{session_id} is {s.status}, not finished")
        records = jax.tree.map(lambda *chunks: np.concatenate(chunks), *s.record_chunks)
        return SessionResult(
            records=records,
            final_state=s.final_state,
            probe_rows=s.probe_rows,
            n_neurons=s.request.n_neurons,
        )

    def close(self):
        for mgr in self._managers.values():
            mgr.close()
        self._managers.clear()


# -- contract-auditor registry (repro.audit, DESIGN.md §15) -----------------
AUDIT = {
    "collectives_allowed": False,  # the round program is slot-local; the
    # optional mesh shards slots, it never reduces across them
    "entry_points": {
        "serve.round": {
            "rules": {
                "R1": {},
                "R2": {"allowed_axes": ()},
                "R4": {"allowlist": ()},
            },
        },
    },
}
