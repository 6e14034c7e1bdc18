"""Entry kind `single`: one network through `PlasticityEngine.simulate`.

The timed program is the engine's own jitted `simulate` for one chunk,
compiled ahead of time.
"""

from __future__ import annotations

import time

import numpy as np
import jax

import reference


class Single:
    replicas = 1

    def __init__(self, config: dict, positions: np.ndarray, chunk_steps: int):
        from repro.core.engine import EngineConfig, PlasticityEngine
        from repro.core.msp import MSPConfig
        from repro.core.traversal import FMMConfig

        net, fmm, eng = config["network"], config["fmm"], config["engine"]
        msp_cfg = MSPConfig.calibrated(speedup=config["msp"]["calibrated_speedup"])
        self.engine = PlasticityEngine(
            positions,
            msp_cfg,
            FMMConfig(sigma=fmm["sigma"], p=fmm["p"], c1=fmm["c1"], c2=fmm["c2"],
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
        self.chunk_steps = chunk_steps
        self.neurons = self.engine.n
        self.model = reference.calibrated(
            config["msp"]["calibrated_speedup"],
            fmm["sigma"],
            0.0,
            eng["max_requests_per_neuron"],
            fmm,
            fmm["sigma"],
        )
        self.compiled = None

    def empty(self):
        """The empty network of the paper's start: no synapse, no element."""
        return jax.jit(self.engine.init_state)()

    def device(self, chunks):
        """The device state of host chunks (one replica)."""
        from repro.core.engine import SimState
        from repro.core.msp import NeuronState
        from repro.core.synapses import SynapseState

        (c,) = chunks
        return jax.device_put(SimState(
            neurons=NeuronState(**{k: np.asarray(v) for k, v in c.neurons.items()}),
            edges=SynapseState(c.src, c.dst, c.valid),
            step=np.int32(c.step),
            dropped=np.int32(c.dropped),
        ))

    def compile(self, state, key) -> float:
        t0 = time.perf_counter()
        lowered = type(self.engine).simulate.lower(self.engine, state, key, self.chunk_steps)
        self.compiled = lowered.compile()
        return time.perf_counter() - t0

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def stream(self, key):
        """What the chunk program takes besides the state."""
        return key

    def chunk(self, state, stream):
        """One chunk of the compiled program: (state, records)."""
        return self.compiled(state, stream)

    def keys(self, key):
        """The key each replica's stream is folded from."""
        return [key]

    def models(self):
        return [self.model]

    def host(self, state, records=None):
        """Host copies of each replica's state (and records)."""
        nrn = state.neurons
        neurons = dict(
            x=nrn.x,
            refrac=nrn.refrac,
            spiked=nrn.spiked,
            calcium=nrn.calcium,
            ax_elems=nrn.ax_elems,
            den_elems=nrn.den_elems,
        )
        recs = {} if records is None else records._asdict()
        return [
            reference.Chunk(
                {k: np.asarray(v) for k, v in neurons.items()},
                np.asarray(state.edges.src),
                np.asarray(state.edges.dst),
                np.asarray(state.edges.valid),
                int(state.step),
                int(state.dropped),
                {k: np.asarray(v) for k, v in recs.items()},
            )
        ]


def build(config: dict, positions: np.ndarray, chunk_steps: int) -> Single:
    return Single(config, positions, chunk_steps)
