"""Sharding rules: parameter/optimizer/cache/batch PartitionSpecs per mesh.

Scheme (MaxText-style 2-axis logical layout, extended with a "pod" axis):

  fsdp axis  = ("pod", "data")   parameters, optimizer moments, activations'
                                 batch dim  (ZeRO-3: every weight matrix
                                 shards its d_model-ish dim over fsdp)
  tensor axis = "model"          heads / ffn / experts / vocab / ssm-heads

Rules are name+rank based over the parameter pytree paths (plain dicts), so
they apply to any architecture in the zoo without per-model annotations.
Divisibility is checked and falls back to replication on that dim (recorded —
the dry-run prints every fallback so sharding gaps are visible, not silent).
"""
from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _fits(mesh: Mesh, dim: int, axes) -> bool:
    if axes is None or dim <= 0:
        return True
    size = int(np.prod([mesh.shape[a] for a in
                        ((axes,) if isinstance(axes, str) else axes)]))
    return dim % size == 0


def _spec(mesh: Mesh, shape: Sequence[int], wanted: Sequence) -> P:
    """Drop axis assignments that don't divide the dim (with fallback)."""
    return P(*[a if _fits(mesh, d, a) else None
               for d, a in zip(shape, wanted)])


# -- parameters ---------------------------------------------------------------

def param_spec(mesh: Mesh, path, leaf) -> P:
    """PartitionSpec for one parameter; `path` is a tree_flatten_with_path
    key path, `leaf` an array (or ShapeDtypeStruct)."""
    fsdp = fsdp_axes(mesh)
    name = str(getattr(path[-1], "key", path[-1]))
    shape = leaf.shape
    rank = len(shape)

    def build(*tail):
        """Pad with leading None for stacked-layer dims."""
        lead = (None,) * (rank - len(tail))
        return _spec(mesh, shape, lead + tail)

    if name == "table":                       # embedding (V, d)
        return build("model", fsdp)
    if name in ("wq", "wk", "wv", "wi", "wg", "in_proj", "w_dkv", "w_kr"):
        # experts (.., E, d, f) vs dense (.., d, f)
        if name in ("wi", "wg") and rank >= 3 and _looks_like_experts(path):
            return build("model", fsdp, None)
        return build(fsdp, "model")
    if name == "wo":
        if rank >= 3 and _looks_like_experts(path):
            return build("model", None, fsdp)
        return build("model", fsdp)
    if name == "out_proj":
        return build("model", fsdp)
    if name == "w":                            # head / frontend (d_in, d_out)
        return build(fsdp, "model")
    if name == "router":
        return build(fsdp, None)
    if name in ("w_uk", "w_uv"):               # (r, H, n)
        return build(None, "model", None)
    if name == "conv_w":                       # (W, C)
        return build(None, "model")
    if name in ("a_log", "d_skip", "dt_bias"):  # (H,)
        return build("model")
    if name in ("bq", "bk", "bv"):             # (H*hd,)
        return build("model")
    # norms scales, small biases: replicated
    return P(*([None] * rank))


def _looks_like_experts(path) -> bool:
    return any(str(getattr(p, "key", p)) in ("moe",) for p in path)


def _strip_axes(spec: P, strip: set) -> P:
    cleaned = []
    for part in spec:
        if part is None:
            cleaned.append(None)
        elif isinstance(part, (tuple, list)):
            kept = tuple(a for a in part if a not in strip)
            cleaned.append(kept if kept else None)
        else:
            cleaned.append(None if part in strip else part)
    return P(*cleaned)


def param_spec_serve(mesh: Mesh, path, leaf) -> P:
    """Serving-posture parameter sharding: tensor-parallel over "model" only,
    REPLICATED over the fsdp axes.

    Training shards weights over fsdp (ZeRO) because optimizer state forces
    it; a serving step has no optimizer, and FSDP weights cost one all-gather
    per layer per decoded token (measured: ~80 MB f32/step at qwen2 scale —
    EXPERIMENTS.md §Perf LM-cell-2, iteration 2).  Callers fall back to the
    training spec when the model-only shards don't fit HBM (llama4-400b)."""
    return _strip_axes(param_spec(mesh, path, leaf), set(fsdp_axes(mesh)))


def param_spec_dp(mesh: Mesh, path, leaf) -> P:
    """DP-over-model training posture: weights ZeRO-sharded over fsdp axes,
    REPLICATED over "model"; the model axis carries batch shards instead.

    16-way tensor parallelism costs one (B_loc, S, d) psum per contraction
    per layer — the census showed this dominating EVERY train cell whose
    state doesn't actually need model sharding (qwen2: 2.28 s collective vs
    0.089 s compute).  When the optimizer state fits at fsdp-only sharding
    and the global batch divides the whole mesh, pure DP eliminates the
    per-layer collectives entirely; gradients reduce once per step
    (EXPERIMENTS.md §Perf, LM-global iteration)."""
    return _strip_axes(param_spec(mesh, path, leaf), {"model"})


# -- activations / batches ----------------------------------------------------

def batch_spec(mesh: Mesh, batch: int, include_model: bool = False) -> P:
    """Shard the batch over (pod, data[, model]) by divisibility fallback.

    include_model=True is the DP-over-model posture (see param_spec_dp):
    the batch also spans the "model" axis because nothing else uses it."""
    fsdp = fsdp_axes(mesh)
    if include_model and _fits(mesh, batch, fsdp + ("model",)):
        return P(fsdp + ("model",))
    if _fits(mesh, batch, fsdp):
        return P(fsdp)
    if _fits(mesh, batch, "data"):
        return P("data")
    return P(None)


def data_spec(mesh: Mesh, shape: Sequence[int],
              include_model: bool = False) -> P:
    """(B, S) token batches / (B, S, F) feature batches."""
    b = batch_spec(mesh, shape[0], include_model)
    return P(*(list(b) + [None] * (len(shape) - 1)))


# -- decode caches -------------------------------------------------------------

def cache_spec(mesh: Mesh, path, leaf) -> P:
    """KV caches (L, B, S, KV, hd) / (L, B, S, r): batch over fsdp, SEQ over
    "model" (decode attention's softmax/reductions over the sharded seq dim
    lower to psums — flash-decoding's partial-softmax pattern, derived by
    SPMD).  SSM states (L, B, H, N, P): heads over "model"."""
    fsdp = fsdp_axes(mesh)
    name = str(getattr(path[-1], "key", path[-1]))
    shape = leaf.shape
    if name in ("k", "v"):                     # (L, B, S, KV, hd)
        return _spec(mesh, shape, (None, fsdp, "model", None, None))
    if name in ("c", "kr"):                    # (L, B, S, r)
        return _spec(mesh, shape, (None, fsdp, "model", None))
    if name == "ssm":                          # (L, B, H, N, P)
        return _spec(mesh, shape, (None, fsdp, "model", None, None))
    if name == "conv":                         # (L, B, W-1, C)
        return _spec(mesh, shape, (None, fsdp, None, "model"))
    return P(*([None] * len(shape)))


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


# -- whole-state helpers --------------------------------------------------------

def tree_specs(mesh: Mesh, tree: PyTree, spec_fn) -> PyTree:
    flat = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        flat[1], [spec_fn(mesh, path, leaf) for path, leaf in flat[0]])


def tree_shardings(mesh: Mesh, tree: PyTree, spec_fn) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_specs(mesh, tree, spec_fn),
                        is_leaf=lambda x: isinstance(x, P))
