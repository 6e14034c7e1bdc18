"""Neuron somata placed uniformly at random in a cube, as in the paper's
growth experiment."""

import numpy as np


def positions(network: dict) -> np.ndarray:
    rng = np.random.default_rng(network["geometry_seed"])
    side = network["domain_um"]
    return rng.uniform(0.0, side, (network["n"], 3)).astype(np.float32)
