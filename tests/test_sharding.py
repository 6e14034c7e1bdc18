"""The simulator's sharding specs fit the arrays they shard, on every layout.

Each spec builder of `repro.sharding.rules` is applied to the shapes the
engines produce (`jax.eval_shape`: nothing is allocated) on each layout the
simulator runs it on: one chip, four chips on "data", four or eight devices on
"ensemble", and the 2-D ("ensemble", "data") sweep.  The meshes are
`AbstractMesh`es, so no device is needed.  Every case checks that each named
axis is declared in `AXIS_CONTRACTS` and present in the layout, that the
spec's rank fits the array, and that each sharded dimension divides by the
size of its axes.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core.engine import PlasticityEngine
from repro.core.ensemble import EnsembleEngine
from repro.core.msp import MSPConfig
from repro.core.probes import CalciumProbe, ProbeSet, SpikeRasterProbe, TurnoverProbe
from repro.core.traversal import FMMConfig
from repro.launch import mesh as meshes
from repro.serve.service import SimulationService
from repro.sharding import rules

N, K, CHUNK = 64, 8, 4

LAYOUTS = {
    "one-chip": AbstractMesh((1, 1), ("ensemble", "data")),
    "four-chips-data": AbstractMesh((4,), ("data",)),
    "four-chips-ensemble": AbstractMesh((4,), ("ensemble",)),
    "eight-replicas": AbstractMesh((8,), ("ensemble",)),
    "sweep-2x2": AbstractMesh((2, 2), ("ensemble", "data")),
}
REPLICA_LAYOUTS = ("one-chip", "four-chips-ensemble", "eight-replicas")
DATA_LAYOUTS = ("one-chip", "four-chips-data", "sweep-2x2")
SWEEP_LAYOUTS = ("one-chip", "sweep-2x2")


@pytest.fixture(scope="module")
def shapes(tmp_path_factory):
    """The engines' and the service's array shapes at N neurons, K replicas."""
    pos = np.random.default_rng(0).uniform(0, 1000.0, (N, 3)).astype(np.float32)
    msp_cfg = MSPConfig.calibrated(speedup=100.0)
    fmm_cfg = FMMConfig(c1=8, c2=8)
    engine = PlasticityEngine(pos, msp_cfg, fmm_cfg)
    ens = EnsembleEngine(engine)
    probes = ProbeSet(
        [SpikeRasterProbe(), CalciumProbe(), TurnoverProbe(np.arange(N) % 2, 2)],
        chunk_size=CHUNK,
    )

    def served(probe_set):
        service = SimulationService(
            pos,
            msp_cfg,
            fmm_cfg,
            num_slots=K,
            round_steps=msp_cfg.update_interval,
            checkpoint_dir=str(tmp_path_factory.mktemp("serve")),
            probes=probe_set,
        )
        ins = (
            service.states,
            service.key_data,
            service.params,
            service.extras,
            service.probe_states,
        )
        return ins, jax.eval_shape(service._round_fn, *ins)

    state = jax.eval_shape(engine.init_state)
    return {
        "depth": engine.structure.depth,
        "positions": jax.eval_shape(lambda: engine.positions),
        "vacancies": state.neurons.ax_elems,
        "states": jax.eval_shape(lambda: ens.init_states(K)),
        "params": jax.eval_shape(lambda: ens.default_params(K)),
        "probe_set": probes,
        "probe_state": jax.eval_shape(lambda: probes.init(N)),
        "probe_states": jax.eval_shape(lambda: probes.init(N, batch=K)),
        "served": served(probes),
        "served_no_probes": served(None),
    }


def _serve_round(served):
    (states, key_data, params, extras, probe_states), outs = served
    template = jax.tree.map(lambda _: 0.0, outs[2])
    in_specs, out_specs = rules.serve_round_specs(states, params, extras, probe_states, template)
    return [(in_specs, served[0]), (out_specs, outs)]


def _probe_state(s, mesh):
    if "ensemble" in mesh.axis_names:
        spec = rules.probe_state_spec(s["probe_set"], ensemble_axis="ensemble")
        return [(spec, s["probe_states"])]
    return [(rules.probe_state_spec(s["probe_set"]), s["probe_state"])]


BUILDERS = {
    "ensemble_spec/states": lambda s, m: [(rules.ensemble_spec(s["states"]), s["states"])],
    "ensemble_spec/params": lambda s, m: [(rules.ensemble_spec(s["params"]), s["params"])],
    "ensemble_spec/probes": lambda s, m: [
        (rules.ensemble_spec(s["probe_states"]), s["probe_states"])
    ],
    "serve_round_specs/probes": lambda s, m: _serve_round(s["served"]),
    "serve_round_specs/no-probes": lambda s, m: _serve_round(s["served_no_probes"]),
    "pyramid_input_spec": lambda s, m: [
        (rules.pyramid_input_spec(), s["positions"]),
        (rules.pyramid_input_spec(), s["vacancies"]),
    ],
    "descent_map_spec": lambda s, m: [
        (rules.descent_map_spec(), jax.ShapeDtypeStruct((8**level,), jnp.int32))
        for level in range(s["depth"] + 1)
    ],
    "find_request_spec": lambda s, m: [
        (rules.find_request_spec(), jax.ShapeDtypeStruct((N,), jnp.int32))
    ],
    "probe_state_spec": _probe_state,
    "sweep2d_spec": lambda s, m: [
        (rules.sweep2d_spec(rank=x.ndim), x) for x in jax.tree.leaves(s["states"]) if x.ndim >= 2
    ],
    "ensemble_sharded_spec": lambda s, m: [(rules.ensemble_sharded_spec(s["states"]), s["states"])],
}
CASES = (
    [("ensemble_spec/states", layout) for layout in REPLICA_LAYOUTS]
    + [("ensemble_spec/params", layout) for layout in REPLICA_LAYOUTS + ("sweep-2x2",)]
    + [("ensemble_spec/probes", layout) for layout in REPLICA_LAYOUTS]
    + [("serve_round_specs/probes", layout) for layout in REPLICA_LAYOUTS]
    + [("serve_round_specs/no-probes", layout) for layout in REPLICA_LAYOUTS]
    + [("pyramid_input_spec", layout) for layout in DATA_LAYOUTS]
    + [("descent_map_spec", layout) for layout in DATA_LAYOUTS]
    + [("find_request_spec", layout) for layout in DATA_LAYOUTS]
    + [("probe_state_spec", layout) for layout in DATA_LAYOUTS]
    + [("sweep2d_spec", layout) for layout in SWEEP_LAYOUTS]
    + [("ensemble_sharded_spec", layout) for layout in SWEEP_LAYOUTS]
)


def _axes(part):
    if part is None:
        return ()
    return (part,) if isinstance(part, str) else tuple(part)


@pytest.mark.parametrize("builder,layout", CASES)
def test_spec_fits_its_arrays(shapes, builder, layout):
    mesh = LAYOUTS[layout]
    checked = []

    def check(spec, x):
        assert len(spec) <= x.ndim, (spec, x.shape)
        for dim, part in zip(x.shape, spec):
            axes = _axes(part)
            for axis in axes:
                assert axis in rules.AXIS_CONTRACTS, (axis, spec)
                assert axis in mesh.axis_names, (axis, spec, mesh.axis_names)
            assert dim % math.prod(mesh.shape[a] for a in axes) == 0, (spec, x.shape)
        checked.append(spec)

    for specs, arrays in BUILDERS[builder](shapes, mesh):
        jax.tree.map(check, specs, arrays, is_leaf=lambda x: isinstance(x, P))
    assert checked


def test_mesh_builders_name_declared_axes():
    for mesh in (
        meshes.make_ensemble_mesh(),
        meshes.make_data_mesh(),
        meshes.make_sweep_mesh(1, 1),
    ):
        assert set(mesh.axis_names) <= set(rules.AXIS_CONTRACTS), mesh.axis_names
