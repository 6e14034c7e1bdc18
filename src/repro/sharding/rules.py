"""PartitionSpecs of the simulator's sharded programs, and its axis contracts.

Two named mesh axes, both declared in `AXIS_CONTRACTS`:

  "data"      the neuron-shard axis of core/distributed.py (the paper's MPI
              ranks): exact raw-sum psums and edge-table/request gathers;
  "ensemble"  the replica (and serve-slot) axis of core/ensemble.py and
              repro/serve: pure batching, no collective.

The specs below place the engines' state, records and probe buffers on
those axes: `ensemble_spec` and `serve_round_specs` (replica axis only),
`pyramid_input_spec`, `descent_map_spec`, `find_request_spec` and
`probe_state_spec` (data axis, DESIGN.md §9, §10, §12), and `sweep2d_spec`
/ `ensemble_sharded_spec` (the 2-D replica x data sweep, §7).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P

PyTree = Any

# -- machine-readable axis contracts (repro.audit rule R2, DESIGN.md §15) ----
# Every named mesh axis the simulation engines use, with its role and the
# collective primitives sanctioned over it.  The contract auditor walks
# traced jaxprs and flags any collective whose axis is undeclared here or
# whose primitive is outside the sanctioned set — e.g. a psum over
# "ensemble" would silently couple replicas and void the per-replica
# bitwise contract (§7), yet typecheck fine.
AXIS_CONTRACTS = {
    # The neuron-shard axis: exact raw-sum transport (pyramid partials,
    # descent maps, request exchange) plus the edge-table/request gathers.
    # psum_scatter is the routed exchange's sparse-p2p stand-in (§13); jax
    # spells it `reduce_scatter` in jaxprs and may simplify it to `psum` on
    # a size-1 axis, so all three spellings are sanctioned together.
    "data": {
        "role": "shard",
        "collectives": frozenset(
            {"psum", "all_gather", "reduce_scatter", "psum_scatter"}
        ),
    },
    # The replica/slot axis: pure batching.  Replicas (and serve slots)
    # must stay independent — NO collective is ever sanctioned here.
    "ensemble": {
        "role": "replica",
        "collectives": frozenset(),
    },
}


# -- ensemble replica axis ------------------------------------------------------

def ensemble_spec(tree: PyTree, axis: str = "ensemble", dim: int = 0) -> PyTree:
    """P with `axis` at position `dim` (None elsewhere) for every leaf.

    The ensemble subsystem (core/ensemble.py) gives every SimState /
    KernelParams leaf a leading K-replica axis and every StepRecord
    trajectory a (T, K) layout (dim=1).  Replicas are independent, so
    sharding this axis is pure data parallelism — shard_map with these specs
    runs K/devices replicas per device with zero collectives."""
    s = P(*([None] * dim + [axis]))
    return jax.tree.map(lambda _: s, tree)


def serve_round_specs(states: PyTree, params: PyTree, extras: PyTree,
                      probe_states: PyTree, record_template: PyTree,
                      axis: str = "ensemble"):
    """(in_specs, out_specs) for the serving layer's round program.

    The served round (repro/serve/service.py) is `scan_replicas` over the
    slot axis with three extra inputs vs the plain ensemble path: raw
    (K, ...) uint32 key data (wrapped to typed keys inside the program),
    the per-slot SlotExtras scalars, and the probe-state carry.  Slots
    never communicate — the same zero-collective data parallelism as
    `ensemble_spec` — and records come back (round_steps, K), so their
    slot axis sits at dim 1."""
    state_spec = ensemble_spec(states, axis)
    probe_spec = ensemble_spec(probe_states, axis)
    in_specs = (state_spec, P(axis), ensemble_spec(params, axis),
                ensemble_spec(extras, axis), probe_spec)
    out_specs = (state_spec, probe_spec,
                 ensemble_spec(record_template, axis, dim=1))
    return in_specs, out_specs


# -- owner-span pyramid partials (distributed upward pass) ---------------------

def pyramid_input_spec() -> P:
    """Spec of the upward pass's neuron-axis inputs (positions, global
    vacancy vectors) at a shard_map boundary: REPLICATED into the span
    build (used by the fig_pyramid_scaling harness; the engine's own
    vacancies arrive via its in-step all_gather instead).

    The connectivity update all_gathers vacancies for the descent anyway, so
    the pyramid re-uses the replicated vectors and each device dynamic-slices
    its owner span out of them — O(n/p) touched elements per level despite
    the replicated layout.  The OwnerSpans start/stop tables are likewise
    replicated, as closed-over host constants: every device holds the whole
    (depth+1, p) table and selects its column by data-axis rank inside
    shard_map (octree.build_pyramid_spans, DESIGN.md §9).  The hierarchical
    request-routed exchange that drops the replication for 1000+ devices
    ships as `pyramid_exchange="routed"` (DESIGN.md §13); its static
    request tables (octree.routed_tables) ride as closed-over host
    constants exactly like the span tables here, so no new spec is needed.
    """
    return P()


# -- sharded find phase (distributed connectivity update) ----------------------

def descent_map_spec() -> P:
    """Spec of the per-level dense (8^l,) descent target maps at a shard_map
    boundary: REPLICATED — each level's map is the psum of the ranks'
    disjoint owned-box scatters, so after the merge every device holds the
    whole map (the next level's parent lookups may cross owners).  The
    per-rank PARTIALS never cross a boundary; they exist only inside the
    step (traversal.descend_sharded, DESIGN.md §10)."""
    return P()


def find_request_spec(data_axis: str = "data") -> P:
    """Spec of the per-neuron partner/request vectors of the sharded find
    phase BEFORE the request exchange: sharded over the data axis (each
    device resolves only its owned contiguous neuron rows).  The request
    exchange is an all_gather of exactly these vectors — O(n) ints, the
    replacement for the legacy O(E) edge-table gather (DESIGN.md §10)."""
    return P(data_axis)


# -- probe recording buffers (core/probes.py) ----------------------------------

def probe_state_spec(probe_set, data_axis: str = "data",
                     ensemble_axis: str | None = None) -> PyTree:
    """ProbeState-shaped PartitionSpec tree for a probe-attached simulate.

    Owner-span-local recording (DESIGN.md §12): a `row_sharded` probe's
    (chunk, n) buffer shards its NEURON dim over the data axis, so each
    device records only its owned contiguous rows — recording adds zero
    collectives.  Aggregate probes (needs_merge, e.g. synapse turnover)
    keep replicated buffers: their per-device partials are psummed by the
    engine before the row is written, so every device holds the identical
    merged rows.  The cursor/step0 scalars are replicated too (devices
    record in lockstep).

    ensemble_axis: set on the 2-D sweep mesh — every leaf gains the leading
    replica axis (buffers are (K, chunk, ...), cursors (K,)), composing
    exactly like ensemble_sharded_spec does for SimState.
    """
    from repro.core.probes import ProbeState   # deferred: core imports rules
    lead = () if ensemble_axis is None else (ensemble_axis,)
    buf_specs = {}
    for p in probe_set.probes:
        buf_specs[p.name] = (P(*lead, None, data_axis) if p.row_sharded
                             else P(*lead))
    return ProbeState(cursor=P(*lead), step0=P(*lead), buffers=buf_specs)


# -- 2-D sweep mesh (ensemble x data) ------------------------------------------

def sweep2d_spec(ensemble_axis: str = "ensemble", data_axis: str = "data",
                 rank: int = 2, data_dim: int = 1) -> P:
    """P placing the replica axis at dim 0 and the data axis at `data_dim`
    of a rank-`rank` leaf (None elsewhere)."""
    parts: list = [None] * rank
    parts[0] = ensemble_axis
    parts[data_dim] = data_axis
    return P(*parts)


def ensemble_sharded_spec(tree: PyTree, ensemble_axis: str = "ensemble",
                          data_axis: str = "data") -> PyTree:
    """2-D sweep specs for a (K, ...)-leading SimState tree.

    Composes the replica layout of `ensemble_spec` with the neuron-axis
    decomposition of core/distributed.py: every leaf leads with the replica
    axis; leaves with a second (neuron/edge) dim shard it over the data
    axis; per-replica scalars (rank-1 leaves: step, dropped, keys, swept
    KernelParams columns) replicate across data.  Replicas exchange zero
    collectives — only the data axis carries the step's psum/all_gather.
    """
    return jax.tree.map(
        lambda x: sweep2d_spec(ensemble_axis, data_axis, x.ndim)
        if x.ndim >= 2 else P(ensemble_axis), tree)
