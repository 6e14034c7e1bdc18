"""Entry kind `sweep`: K replicas through `EnsembleEngine.simulate`.

The deployment's `sweep` gives the grid (each knob's values, their
cartesian product in the order the keys are listed) and the replicates of
each grid point; the engine is built at the sweep's smallest sigma, as the
sweep workflow prescribes.  Replica r draws its stream from the r-th split
of the run's key.
"""

from __future__ import annotations

import itertools
import time

import numpy as np
import jax

import reference


class Sweep:
    def __init__(self, config: dict, positions: np.ndarray, chunk_steps: int):
        from repro.core.engine import EngineConfig, PlasticityEngine
        from repro.core.ensemble import EnsembleEngine
        from repro.core.msp import MSPConfig
        from repro.core.traversal import FMMConfig
        from repro.launch import sweep

        net, fmm, eng, grid = config["network"], config["fmm"], config["engine"], config["sweep"]
        points = [dict(zip(grid["knobs"], v)) for v in itertools.product(*grid["knobs"].values())]
        self.points = [p for p in points for _ in range(grid["replicates"])]
        sigma0 = min(p.get("sigma", fmm["sigma"]) for p in self.points)
        speedup = config["msp"]["calibrated_speedup"]
        msp_cfg = MSPConfig.calibrated(speedup=speedup)
        engine = PlasticityEngine(
            positions,
            msp_cfg,
            FMMConfig(sigma=sigma0, p=fmm["p"], c1=fmm["c1"], c2=fmm["c2"],
                      size_guard=fmm["size_guard"]),
            EngineConfig(
                method=eng["method"],
                backend=eng["backend"],
                edge_capacity_per_neuron=eng["edge_capacity_per_neuron"],
                max_requests_per_neuron=eng["max_requests_per_neuron"],
                domain=net["domain_um"],
            ),
        )
        if chunk_steps != msp_cfg.update_interval:
            raise ValueError(
                f"a chunk must be one update interval ({msp_cfg.update_interval} steps)"
            )
        self.ensemble = EnsembleEngine(engine)
        self.params = sweep.pack_params(engine, self.points)
        self.replicas = len(self.points)
        self.neurons = engine.n
        self.chunk_steps = chunk_steps
        self._models = [
            reference.calibrated(
                speedup,
                p.get("sigma", fmm["sigma"]),
                p.get("inhibitory_fraction", 0.0),
                eng["max_requests_per_neuron"],
                dict(fmm, c1=p.get("c1", fmm["c1"]), c2=p.get("c2", fmm["c2"])),
                sigma0,
            )
            for p in self.points
        ]
        self.compiled = None

    def empty(self):
        """Every replica's empty network: no synapse, no element."""
        return jax.jit(self.ensemble.init_states, static_argnums=0)(self.replicas)

    def device(self, chunks):
        """The stacked device state of the replicas' host chunks."""
        from repro.core.engine import SimState
        from repro.core.msp import NeuronState
        from repro.core.synapses import SynapseState

        stack = lambda get, dtype=None: np.stack([np.asarray(get(c), dtype) for c in chunks])
        return jax.device_put(SimState(
            neurons=NeuronState(**{k: stack(lambda c, k=k: c.neurons[k]) for k in chunks[0].neurons}),
            edges=SynapseState(stack(lambda c: c.src), stack(lambda c: c.dst),
                               stack(lambda c: c.valid)),
            step=stack(lambda c: c.step, np.int32),
            dropped=stack(lambda c: c.dropped, np.int32),
        ))

    def stream(self, key):
        """The replicas' keys, one split of the run's key each."""
        return jax.random.split(key, self.replicas)

    def keys(self, key):
        return list(self.stream(key))

    def compile(self, state, keys) -> float:
        t0 = time.perf_counter()
        lowered = type(self.ensemble).simulate.lower(
            self.ensemble, state, keys, self.chunk_steps, self.params
        )
        self.compiled = lowered.compile()
        return time.perf_counter() - t0

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def chunk(self, state, keys):
        return self.compiled(state, keys, self.params)

    def models(self):
        return self._models

    def host(self, state, records=None):
        nrn = state.neurons
        fields = dict(
            x=nrn.x,
            refrac=nrn.refrac,
            spiked=nrn.spiked,
            calcium=nrn.calcium,
            ax_elems=nrn.ax_elems,
            den_elems=nrn.den_elems,
        )
        fields = {k: np.asarray(v) for k, v in fields.items()}
        src, dst, valid = (np.asarray(a) for a in state.edges)
        step, dropped = np.asarray(state.step), np.asarray(state.dropped)
        recs = {} if records is None else {k: np.asarray(v) for k, v in records._asdict().items()}
        return [
            reference.Chunk(
                {k: v[r] for k, v in fields.items()},
                src[r],
                dst[r],
                valid[r],
                int(step[r]),
                int(dropped[r]),
                {k: v[:, r] for k, v in recs.items()},
            )
            for r in range(self.replicas)
        ]


def build(config: dict, positions: np.ndarray, chunk_steps: int) -> Sweep:
    return Sweep(config, positions, chunk_steps)
