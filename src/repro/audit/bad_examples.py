"""Golden seeded violations: the corpus the auditor must catch (R1-R4).

Each builder reproduces one historical failure shape in miniature (the
incident log is DESIGN.md §15) and returns an `EntrySpec` whose audit MUST
produce findings for the named rule; `clean_controls()` returns the
corrected twin of each, which must audit clean — together they pin both
directions of every rule.  tests/test_audit.py consumes these directly;
``tools/run_audit.py --self-test`` runs them in CI.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.audit.tracer import EntrySpec

_N = 96


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("ensemble", "data"))


def _pinned(x):
    """The real pin: int32 bitcast round-trip (engine._pin_f32's shape)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jax.lax.bitcast_convert_type(bits + jnp.int32(0), jnp.float32)


def _std(x, *, pin) -> jax.Array:
    """The record-path std shape: mean -> squared deviation -> sqrt."""
    inv = jnp.float32(1.0 / x.shape[0])
    mean = _halving_sum(x) * inv
    if pin:
        mean = _pinned(mean)
    dev2 = (x - mean) ** 2
    return jnp.sqrt(_halving_sum(dev2) * inv)


def _halving_sum(x):
    """Tiny stand-in for synapses.det_sum (pairwise halving tree)."""
    n = x.shape[0]
    k = 1
    while k < n:
        k *= 2
    x = jnp.pad(x, (0, k - n))
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


# -- R1: record std whose mean lost its _pin_f32 ----------------------------


def bad_r1_unpinned_mean() -> EntrySpec:
    def build():
        fn = lambda x: _std(x, pin=False)
        return fn, (jnp.ones((_N,), jnp.float32),)

    return EntrySpec(name="bad.r1_unpinned_mean", rules={"R1": {}}, build=build)


def good_r1_pinned_mean() -> EntrySpec:
    def build():
        fn = lambda x: _std(x, pin=True)
        return fn, (jnp.ones((_N,), jnp.float32),)

    return EntrySpec(name="good.r1_pinned_mean", rules={"R1": {}}, build=build)


# -- R2: collective over the replica axis / an undeclared axis --------------


def bad_r2_replica_psum() -> EntrySpec:
    def build():
        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            lambda x: jax.lax.psum(x, "ensemble"),
            mesh=_mesh(),
            in_specs=P("ensemble"),
            out_specs=P(),
            check_vma=False,
        )
        return fn, (jnp.ones((4,), jnp.float32),)

    return EntrySpec(
        name="bad.r2_replica_psum",
        rules={"R2": {"allowed_axes": ("ensemble", "data")}},
        build=build,
    )


def bad_r2_out_of_scope_gather() -> EntrySpec:
    """A data-axis collective inside an entry scoped replica-local."""

    def build():
        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            lambda x: jax.lax.all_gather(x, "data", tiled=True),
            mesh=_mesh(),
            in_specs=P("data"),
            out_specs=P(),
            check_vma=False,
        )
        return fn, (jnp.ones((4,), jnp.float32),)

    return EntrySpec(
        name="bad.r2_out_of_scope_gather",
        rules={"R2": {"allowed_axes": ()}},
        build=build,
    )


def good_r2_data_psum() -> EntrySpec:
    def build():
        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            lambda x: jax.lax.psum(x, "data"),
            mesh=_mesh(),
            in_specs=P(None, "data"),
            out_specs=P(),
            check_vma=False,
        )
        return fn, (jnp.ones((1, 4), jnp.float32),)

    return EntrySpec(
        name="good.r2_data_psum",
        rules={"R2": {"allowed_axes": ("data",)}},
        build=build,
    )


# -- R3: cond lowered to select under vmap ----------------------------------

_E = 512  # the "edge table" the conditional path gathers


def _gather_branch(x):
    from jax.sharding import PartitionSpec as P  # noqa: F401  (doc symmetry)

    return jnp.sum(jax.lax.all_gather(x, "data", tiled=True))


def bad_r3_select_gather() -> EntrySpec:
    """Per-element predicate: vmap batches it, the cond lowers to select
    and the O(E) gather runs unconditionally — the pre-`_cond_delete` bug."""

    def build():
        from jax.sharding import PartitionSpec as P

        def one(pred, x):
            return jax.lax.cond(pred, _gather_branch, lambda x: jnp.float32(0), x)

        fn = jax.shard_map(
            jax.vmap(one),
            mesh=_mesh(),
            in_specs=(P(), P(None, "data")),
            out_specs=P(),
            check_vma=False,
        )
        preds = jnp.zeros((2,), bool)
        xs = jnp.ones((2, _E), jnp.float32)
        return fn, (preds, xs)

    return EntrySpec(name="bad.r3_select_gather", rules={"R3": {"min_size": _E}}, build=build)


def good_r3_reduced_predicate() -> EntrySpec:
    """Batch-reduced predicate outside the vmap keeps a genuine cond
    (the `_cond_delete` fix shape)."""

    def build():
        from jax.sharding import PartitionSpec as P

        def batched(preds, xs):
            return jax.lax.cond(
                jnp.any(preds),
                lambda xs: jax.vmap(_gather_branch)(xs),
                lambda xs: jnp.zeros((xs.shape[0],), jnp.float32),
                xs,
            )

        fn = jax.shard_map(
            batched,
            mesh=_mesh(),
            in_specs=(P(), P(None, "data")),
            out_specs=P(),
            check_vma=False,
        )
        preds = jnp.zeros((2,), bool)
        xs = jnp.ones((2, _E), jnp.float32)
        return fn, (preds, xs)

    return EntrySpec(
        name="good.r3_reduced_predicate", rules={"R3": {"min_size": _E}}, build=build
    )


# -- R4: raw float sum over a padded axis -----------------------------------


def bad_r4_raw_padded_sum() -> EntrySpec:
    def build():
        def fn(x, n_active):
            mask = jnp.arange(x.shape[0]) < n_active
            masked = jnp.where(mask, x, 0.0)
            return jnp.sum(masked) / n_active.astype(jnp.float32)

        return fn, (jnp.ones((_N,), jnp.float32), jnp.int32(61))

    return EntrySpec(
        name="bad.r4_raw_padded_sum", rules={"R4": {"padded_sizes": (_N,)}}, build=build
    )


def good_r4_halving_sum() -> EntrySpec:
    def build():
        def fn(x, n_active):
            mask = jnp.arange(x.shape[0]) < n_active
            masked = jnp.where(mask, x, 0.0)
            return _halving_sum(masked) / n_active.astype(jnp.float32)

        return fn, (jnp.ones((_N,), jnp.float32), jnp.int32(61))

    return EntrySpec(
        name="good.r4_halving_sum", rules={"R4": {"padded_sizes": (_N,)}}, build=build
    )


def bad_examples() -> list[EntrySpec]:
    """Seeded violations; auditing each MUST yield >= 1 finding."""
    return [
        bad_r1_unpinned_mean(),
        bad_r2_replica_psum(),
        bad_r2_out_of_scope_gather(),
        bad_r3_select_gather(),
        bad_r4_raw_padded_sum(),
    ]


def clean_controls() -> list[EntrySpec]:
    """Corrected twins; auditing each MUST yield zero findings."""
    return [
        good_r1_pinned_mean(),
        good_r2_data_psum(),
        good_r3_reduced_predicate(),
        good_r4_halving_sum(),
    ]


def expected_rule(spec_name: str) -> str:
    """Which rule a corpus entry seeds (``bad.r2_...`` -> ``R2``)."""
    return spec_name.split(".", 1)[1].split("_", 1)[0].upper()
