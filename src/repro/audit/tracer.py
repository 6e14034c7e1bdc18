"""Trace registered engine entry points to closed jaxprs (DESIGN.md §15).

The auditable surface is declared next to the code it audits: each hosting
module (`core/engine.py`, `core/distributed.py`, `core/ensemble.py`,
`serve/service.py`) carries a plain-data ``AUDIT`` dict naming its entry
points, the static combos to expand (method x backend x find_phase x
pyramid_exchange), and the rule configs to run.  This module owns the
*builders* — how to construct a small deterministic instance of each entry
point and trace it — and resolves size-dependent knobs (R3 gather
thresholds, R4 padded axis sizes) from the built engines.

Everything here is trace-only: `jax.make_jaxpr` never compiles or executes
device code, so the full registry audits in seconds on CPU.
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Any, Callable, Iterable, Mapping

import numpy as np

import jax
import jax.numpy as jnp

from repro.audit import rules as audit_rules
from repro.audit.report import Finding

# Small deterministic instances: big enough that every phase appears in the
# trace (update interval reached, deletion cond present), small enough that
# tracing stays fast.
_N = 96
_N_ROUTED = 128  # routed exchange needs depth >= 3 for a non-empty deep slab
_K = 2
_SEED = 0
_SPEEDUP = 400.0


@dataclasses.dataclass(frozen=True)
class EntrySpec:
    """One auditable traced program.

    name   -- registry key, e.g. ``distributed.simulate[fmm/sharded/routed]``.
    rules  -- ``{rule_id: config}`` resolved for this instance (thresholds
              and padded sizes already numeric).
    build  -- zero-arg callable returning ``(fn, example_args)`` for
              ``jax.make_jaxpr(fn)(*example_args)``.
    """

    name: str
    rules: Mapping[str, Mapping[str, Any]]
    build: Callable[[], tuple[Callable, tuple]]

    def trace(self):
        fn, args = self.build()
        return jax.make_jaxpr(fn)(*args)


def _positions(n: int) -> np.ndarray:
    rng = np.random.default_rng(_SEED)
    return rng.uniform(0, 1000.0, (n, 3)).astype(np.float32)


def _msp_cfg():
    from repro.core.msp import MSPConfig

    return MSPConfig.calibrated(speedup=_SPEEDUP)


def _fmm_cfg():
    from repro.core.traversal import FMMConfig

    return FMMConfig(c1=8, c2=8)


def _one_device_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("ensemble", "data"))


def _resolve(template: Mapping[str, Any], **numeric) -> dict[str, dict[str, Any]]:
    """Deep-copy a rule template and merge resolved numeric knobs."""
    out: dict[str, dict[str, Any]] = {}
    for rule_id, cfg in template.items():
        merged = dict(cfg or {})
        merged.update(numeric.get(rule_id, {}))
        out[rule_id] = merged
    return out


# -- builders ---------------------------------------------------------------


def _engine(method: str, backend: str, *, rng: str = "batched", n: int = _N):
    from repro.core.engine import EngineConfig, PlasticityEngine

    cfg = EngineConfig(method=method, backend=backend, rng=rng)
    return PlasticityEngine(_positions(n), _msp_cfg(), _fmm_cfg(), cfg)


def _dist_engine(
    method: str,
    find_phase: str,
    pyramid_exchange: str,
    backend: str = "reference",
):
    from repro.core.distributed import DistributedPlasticityEngine
    from repro.core.engine import EngineConfig

    n = _N_ROUTED if pyramid_exchange == "routed" else _N
    depth = 3 if pyramid_exchange == "routed" else None
    cfg = EngineConfig(method=method, backend=backend, depth=depth)
    return DistributedPlasticityEngine(
        _positions(n),
        _one_device_mesh(),
        "data",
        _msp_cfg(),
        _fmm_cfg(),
        cfg,
        find_phase=find_phase,
        pyramid_exchange=pyramid_exchange,
    )


def _build_engine_simulate(method: str, backend: str):
    def build():
        eng = _engine(method, backend)
        state = eng.init_state()
        key = jax.random.key(0)
        steps = eng.msp_cfg.update_interval  # include the connectivity update
        return (lambda st, k: eng.simulate(st, k, steps)), (state, key)

    return build


def _build_engine_simulate_padded():
    def build():
        eng = _engine("fmm", "reference", rng="counter")
        state = eng.init_state()
        key = jax.random.key(0)
        steps = eng.msp_cfg.update_interval
        fn = lambda st, k, na: eng.simulate(st, k, steps, n_active=na)
        return fn, (state, key, jnp.int32(61))

    return build


def _build_dist_simulate(method: str, find_phase: str, pyramid_exchange: str, backend: str):
    def build():
        eng = _dist_engine(method, find_phase, pyramid_exchange, backend)
        state = eng.init_state()
        key = jax.random.key(0)
        steps = eng.msp_cfg.update_interval
        return (lambda st, k: eng.simulate(st, k, steps)), (state, key)

    return build


def _build_dist_update_vmapped():
    """The R3 lowering probe: the *batched* sharded connectivity update.

    Traced directly (not under `simulate`) so the only enclosing cond is
    the deletion cond itself — under the full simulate scan the outer
    do-update cond would make every gather trivially conditional and the
    select-lowering regression invisible.
    """

    def build():
        from jax.sharding import PartitionSpec as P

        eng = _dist_engine("fmm", "sharded", "gathered")
        states = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (_K,) + x.shape), eng.init_state()
        )
        keys = jax.random.split(jax.random.key(0), _K)

        def batched_update(st, ks):
            return jax.vmap(
                lambda s, k: eng._conn_update_sharded(s, kconn=k, params=None)
            )(st, ks)

        state_spec, _ = eng._specs()
        bspec = jax.tree.map(lambda s: P(None, *s), state_spec)
        sharded = jax.shard_map(
            batched_update,
            mesh=eng.mesh,
            in_specs=(bspec, P()),
            out_specs=bspec,
            check_vma=False,
        )
        return sharded, (states, keys)

    return build


def _build_ensemble_simulate():
    def build():
        from repro.core.ensemble import EnsembleEngine

        ens = EnsembleEngine(_engine("fmm", "reference"))
        states = ens.init_states(_K)
        keys = jax.random.split(jax.random.key(0), _K)
        steps = ens.engine.msp_cfg.update_interval
        return (lambda st, ks: ens.simulate(st, ks, steps)), (states, keys)

    return build


def _build_dist_ensemble_simulate():
    def build():
        from repro.core.distributed import DistributedEnsembleEngine

        dens = DistributedEnsembleEngine(_dist_engine("fmm", "sharded", "gathered"))
        states = dens.init_states(_K)
        keys = jax.random.split(jax.random.key(0), _K)
        steps = dens.engine.msp_cfg.update_interval
        return (lambda st, ks: dens.simulate(st, ks, steps)), (states, keys)

    return build


def _build_serve_round():
    def build():
        from repro.serve.service import SimulationService

        service = SimulationService(
            _positions(_N),
            _msp_cfg(),
            _fmm_cfg(),
            num_slots=_K,
            round_steps=_msp_cfg().update_interval,
            checkpoint_dir=os.path.join(tempfile.gettempdir(), "repro_audit_ckpt"),
        )
        fn = lambda st, kd, pr, ex: service._round_fn(st, kd, pr, ex, None)
        args = (service.states, service.key_data, service.params, service.extras)
        return fn, args

    return build


# -- registry ---------------------------------------------------------------


def _module_audits() -> dict[str, Mapping[str, Any]]:
    """Entry-point declarations from the hosting modules' AUDIT dicts."""
    from repro.core import distributed, engine, ensemble
    from repro.serve import service

    declarations: dict[str, Mapping[str, Any]] = {}
    for mod in (engine, distributed, ensemble, service):
        for name, decl in mod.AUDIT["entry_points"].items():
            declarations[name] = decl
    return declarations


def registry() -> list[EntrySpec]:
    """Every auditable entry point, expanded over its declared combos."""
    decls = _module_audits()
    specs: list[EntrySpec] = []

    decl = decls["engine.simulate"]
    for method in decl["combos"]["method"]:
        for backend in decl["combos"]["backend"]:
            specs.append(
                EntrySpec(
                    name=f"engine.simulate[{method}/{backend}]",
                    rules=_resolve(decl["rules"], R4={"padded_sizes": (_N,)}),
                    build=_build_engine_simulate(method, backend),
                )
            )

    decl = decls["engine.simulate_padded"]
    specs.append(
        EntrySpec(
            name="engine.simulate_padded[fmm/counter]",
            rules=_resolve(decl["rules"], R4={"padded_sizes": (_N,)}),
            build=_build_engine_simulate_padded(),
        )
    )

    decl = decls["distributed.simulate"]
    for combo in decl["combos"]:
        method = combo["method"]
        find_phase = combo["find_phase"]
        exchange = combo["pyramid_exchange"]
        backend = combo.get("backend", "reference")
        n = _N_ROUTED if exchange == "routed" else _N
        edge_capacity = 64 * n  # EngineConfig.edge_capacity_per_neuron * n
        label = f"{method}/{find_phase}/{exchange}"
        if backend != "reference":
            label += f"/{backend}"
        specs.append(
            EntrySpec(
                name=f"distributed.simulate[{label}]",
                rules=_resolve(
                    decl["rules"],
                    R3={"min_size": edge_capacity},
                    R4={"padded_sizes": (n,)},
                ),
                build=_build_dist_simulate(method, find_phase, exchange, backend),
            )
        )

    decl = decls["distributed.update_vmapped"]
    specs.append(
        EntrySpec(
            name="distributed.update_vmapped[fmm/sharded/K=2]",
            rules=_resolve(
                decl["rules"],
                R3={"min_size": _K * 64 * _N},
                R4={"padded_sizes": (_N,)},
            ),
            build=_build_dist_update_vmapped(),
        )
    )

    decl = decls["ensemble.simulate"]
    specs.append(
        EntrySpec(
            name="ensemble.simulate[fmm/K=2]",
            rules=_resolve(decl["rules"], R4={"padded_sizes": (_N,)}),
            build=_build_ensemble_simulate(),
        )
    )

    decl = decls["distributed_ensemble.simulate"]
    specs.append(
        EntrySpec(
            name="distributed_ensemble.simulate[fmm/K=2]",
            rules=_resolve(
                decl["rules"],
                R3={"min_size": _K * 64 * _N},
                R4={"padded_sizes": (_N,)},
            ),
            build=_build_dist_ensemble_simulate(),
        )
    )

    decl = decls["serve.round"]
    specs.append(
        EntrySpec(
            name="serve.round[K=2]",
            rules=_resolve(decl["rules"], R4={"padded_sizes": (_N,)}),
            build=_build_serve_round(),
        )
    )

    return specs


def audit_entry(spec: EntrySpec) -> list[Finding]:
    """Trace one entry point and run its configured rules."""
    jaxpr = spec.trace()
    return audit_rules.audit_jaxpr(jaxpr, spec.rules, spec.name)


def audit_entries(names: Iterable[str] | None = None) -> tuple[list[Finding], list[str]]:
    """Audit the registry (optionally filtered by substring match)."""
    selected = []
    for spec in registry():
        if names is None or any(tok in spec.name for tok in names):
            selected.append(spec)
    findings: list[Finding] = []
    for spec in selected:
        findings.extend(audit_entry(spec))
    return findings, [s.name for s in selected]
