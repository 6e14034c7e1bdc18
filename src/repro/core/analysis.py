"""Graph-topological analysis of grown networks — the paper's stated future
work ("we plan to analyze the resulting networks with respect to the
graph-topological metrics so we can assess the functionality of the
networks", Sec. 6) — implemented here as a beyond-paper deliverable.

All metrics are pure-jnp over the fixed-capacity edge list, so they can run
on-device mid-simulation (e.g. every connectivity update) or on checkpoints.
"""
from __future__ import annotations

from typing import Dict

import numpy as np
import jax.numpy as jnp

from repro.core.synapses import SynapseState, degrees


def degree_statistics(edges: SynapseState, n: int) -> Dict[str, jnp.ndarray]:
    out_d, in_d = degrees(edges, n)
    return {
        "out_mean": jnp.mean(out_d.astype(jnp.float32)),
        "out_std": jnp.std(out_d.astype(jnp.float32)),
        "in_mean": jnp.mean(in_d.astype(jnp.float32)),
        "in_std": jnp.std(in_d.astype(jnp.float32)),
        "out_max": jnp.max(out_d),
        "in_max": jnp.max(in_d),
        "isolated": jnp.sum(((out_d + in_d) == 0).astype(jnp.int32)),
    }


def reciprocity(edges: SynapseState, n: int) -> jnp.ndarray:
    """Fraction of directed edges with a reciprocal partner (multiplicity
    collapsed).  Random spatial graphs sit near the density; strongly
    reciprocal wiring is a structure signal."""
    key = edges.src.astype(jnp.int64) * n + edges.dst.astype(jnp.int64)
    rkey = edges.dst.astype(jnp.int64) * n + edges.src.astype(jnp.int64)
    valid = edges.valid
    # presence via sorted membership test
    sorted_keys = jnp.sort(jnp.where(valid, key, -1))
    idx = jnp.searchsorted(sorted_keys, rkey)
    idx = jnp.clip(idx, 0, sorted_keys.shape[0] - 1)
    has_recip = (sorted_keys[idx] == rkey) & valid
    denom = jnp.maximum(jnp.sum(valid.astype(jnp.int32)), 1)
    return jnp.sum(has_recip.astype(jnp.int32)) / denom


def connection_length_profile(edges: SynapseState, positions: jnp.ndarray,
                              bins: int = 20, max_dist: float | None = None
                              ) -> Dict[str, jnp.ndarray]:
    """Histogram of synapse lengths — the empirical realisation of the
    Gaussian kernel (Eq. 1).  The MSP predicts the density of realised
    connections at distance d to follow the kernel times the neuron-pair
    density at d; comparing profiles between FMM and Barnes-Hut quantifies
    the paper's freedom-of-choice discussion beyond mean counts."""
    d = jnp.linalg.norm(positions[edges.src] - positions[edges.dst], axis=-1)
    d = jnp.where(edges.valid, d, -1.0)
    if max_dist is None:
        max_dist = float(jnp.max(jnp.where(edges.valid, d, 0.0)))
        max_dist = max(max_dist, 1e-6)
    edges_b = jnp.linspace(0.0, max_dist, bins + 1)
    hist = jnp.histogram(jnp.where(edges.valid, d, -1.0), bins=edges_b)[0]
    return {"bin_edges": edges_b, "counts": hist,
            "mean_length": jnp.sum(jnp.where(edges.valid, d, 0.0))
            / jnp.maximum(jnp.sum(edges.valid.astype(jnp.int32)), 1)}


def clustering_coefficient(edges: SynapseState, n: int,
                           sample: int = 256, seed: int = 0) -> float:
    """Sampled undirected local clustering coefficient (host-side numpy;
    exact adjacency on the sampled nodes).  For n in the tested range this
    is exact enough to compare FMM vs BH topologies."""
    src = np.asarray(edges.src)[np.asarray(edges.valid)]
    dst = np.asarray(edges.dst)[np.asarray(edges.valid)]
    adj = [set() for _ in range(n)]
    for s, t in zip(src, dst):
        if s != t:
            adj[s].add(int(t))
            adj[t].add(int(s))
    rng = np.random.default_rng(seed)
    nodes = rng.permutation(n)[:sample]
    coeffs = []
    for v in nodes:
        nb = list(adj[v])
        k = len(nb)
        if k < 2:
            continue
        links = sum(1 for i in range(k) for j in range(i + 1, k)
                    if nb[j] in adj[nb[i]])
        coeffs.append(2.0 * links / (k * (k - 1)))
    return float(np.mean(coeffs)) if coeffs else 0.0


def summarize(edges: SynapseState, positions: jnp.ndarray) -> Dict:
    """One-call report used by examples/brain_sim.py --analyze."""
    n = positions.shape[0]
    deg = {k: float(v) for k, v in degree_statistics(edges, n).items()}
    prof = connection_length_profile(edges, positions)
    return {
        "degrees": deg,
        "reciprocity": float(reciprocity(edges, n)),
        "mean_connection_length": float(prof["mean_length"]),
        "clustering_coefficient": clustering_coefficient(edges, n),
    }
