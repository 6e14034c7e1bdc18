"""Generic jaxpr traversal for the contract auditor (DESIGN.md §15).

A closed jaxpr is a tree: each equation may carry sub-jaxprs in its params
(`cond` branches, `scan`/`while` bodies, `pjit`/`custom_*` calls,
`shard_map`, `pallas_call`, ...).  Rules in `repro.audit.rules` never walk
that tree themselves — they consume the iterators here, which yield every
equation exactly once together with an `EqnContext` describing *where* it
sits (nesting path and, crucially for rule R3, whether any enclosing
equation is a `lax.cond`).

Also hosts the local data-flow helpers rules share: a definition map
(var -> defining eqn), backward slices, and provenance chasing through
shape-only no-ops.  All of it is level-local — values crossing a sub-jaxpr
boundary appear as unbound invars, which every helper treats as opaque.

The only non-public surface touched is `jax._src.source_info_util` for
user frames in diagnostics; `source_functions` degrades to `()` if that
module moves.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator

from jax.extend import core as jax_core

try:  # diagnostics only; private module, tolerate relocation
    from jax._src import source_info_util as _src_info
except Exception:  # pragma: no cover - depends on jax version
    _src_info = None

# Equations that only reshape/retype/move their single operand; provenance
# chasing (`root_def`) looks through these.
SHAPE_NOOPS = frozenset(
    {
        "broadcast_in_dim",
        "convert_element_type",
        "copy",
        "device_put",
        "reshape",
        "squeeze",
        "expand_dims",
        "slice",
        "dynamic_slice",
        "transpose",
    }
)

# Primitives whose appearance marks a branch of `lax.cond` in the jaxpr.
_COND_PRIMITIVES = frozenset({"cond"})


@dataclasses.dataclass(frozen=True)
class EqnContext:
    """Where an equation lives inside the traced program.

    path     -- labels of the enclosing sub-jaxpr params, outermost first
                (e.g. ``("pjit:simulate", "scan:body", "cond:branch1")``).
    in_cond  -- True iff any enclosing equation is a ``lax.cond``.  This is
                the R3 predicate: work under a cond branch only runs when
                the branch is taken, work outside runs unconditionally
                (a cond that lowered to ``select`` has no cond equation,
                so its former branches show up with ``in_cond=False``).
    """

    path: tuple[str, ...] = ()
    in_cond: bool = False

    def enter(self, label: str, is_cond: bool) -> "EqnContext":
        return EqnContext(path=self.path + (label,), in_cond=self.in_cond or is_cond)


def _as_jaxpr(obj: Any):
    """Unwrap ClosedJaxpr-likes to a raw Jaxpr; None if not jaxpr-shaped."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj
    return None


def sub_jaxprs(eqn) -> Iterator[tuple[str, Any]]:
    """Yield ``(label, jaxpr)`` for every sub-jaxpr in an equation's params.

    Discovery is structural, not a primitive allowlist: any param value that
    is (or contains, one list/tuple level deep) a jaxpr is yielded.  That
    keeps the walker correct as jax adds higher-order primitives.
    """
    name = eqn.primitive.name
    for key, val in eqn.params.items():
        candidates = val if isinstance(val, (list, tuple)) else (val,)
        for i, cand in enumerate(candidates):
            jx = _as_jaxpr(cand)
            if jx is not None:
                suffix = f"{key}{i}" if isinstance(val, (list, tuple)) else key
                yield f"{name}:{suffix}", jx


def iter_eqns(jaxpr, ctx: EqnContext | None = None) -> Iterator[tuple[Any, EqnContext]]:
    """Depth-first over every equation of ``jaxpr`` and all sub-jaxprs."""
    jx = _as_jaxpr(jaxpr)
    if jx is None:
        raise TypeError(f"not a jaxpr: {jaxpr!r}")
    ctx = ctx or EqnContext()
    for eqn in jx.eqns:
        yield eqn, ctx
        is_cond = eqn.primitive.name in _COND_PRIMITIVES
        for label, sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, ctx.enter(label, is_cond))


def iter_jaxprs(jaxpr, ctx: EqnContext | None = None) -> Iterator[tuple[Any, EqnContext]]:
    """Depth-first over each (sub-)jaxpr level exactly once."""
    jx = _as_jaxpr(jaxpr)
    if jx is None:
        raise TypeError(f"not a jaxpr: {jaxpr!r}")
    ctx = ctx or EqnContext()
    yield jx, ctx
    for eqn in jx.eqns:
        is_cond = eqn.primitive.name in _COND_PRIMITIVES
        for label, sub in sub_jaxprs(eqn):
            yield from iter_jaxprs(sub, ctx.enter(label, is_cond))


def def_map(jaxpr) -> dict[Any, Any]:
    """Map each level-local Var to the equation that defines it."""
    jx = _as_jaxpr(jaxpr)
    defs: dict[Any, Any] = {}
    for eqn in jx.eqns:
        for out in eqn.outvars:
            defs[out] = eqn
    return defs


def _var_inputs(eqn) -> list[Any]:
    return [v for v in eqn.invars if isinstance(v, jax_core.Var)]


def backward_slice(jaxpr, var, defs: dict[Any, Any] | None = None) -> list[Any]:
    """Equations (this level only) that ``var`` transitively depends on.

    Values produced inside sub-jaxprs are opaque: the slice stops at the
    equation that carries the sub-jaxpr (e.g. a ``scan``), which is the
    right granularity for level-local rules like R1.
    """
    jx = _as_jaxpr(jaxpr)
    defs = defs if defs is not None else def_map(jx)
    seen: set[Any] = set()
    out: list[Any] = []
    stack = [var]
    while stack:
        v = stack.pop()
        eqn = defs.get(v)
        if eqn is None or id(eqn) in seen:
            continue
        seen.add(id(eqn))
        out.append(eqn)
        stack.extend(_var_inputs(eqn))
    return out


def root_def(var, defs: dict[Any, Any], *, through: frozenset[str] = SHAPE_NOOPS):
    """Chase ``var`` back through shape-only no-ops to its defining equation.

    Returns the first defining equation whose primitive is *not* in
    ``through`` (None for unbound invars/constants).  Multi-operand no-ops
    (e.g. ``dynamic_slice`` index operands) follow operand 0, the data
    input for every primitive in SHAPE_NOOPS.
    """
    while True:
        eqn = defs.get(var)
        if eqn is None:
            return None
        if eqn.primitive.name not in through:
            return eqn
        data_in = eqn.invars[0]
        if not isinstance(data_in, jax_core.Var):
            return None
        var = data_in


def root_def_min_size(var, defs: dict[Any, Any]) -> tuple[Any, int]:
    """`root_def` plus the smallest element count seen along the no-op chain.

    A reduced-then-rebroadcast value (a mean) pinches to size ~1 somewhere
    on its chain even when vmap rematerialized the broadcast; the pinch
    size distinguishes the mean side of a subtract from the data side.
    """
    smallest = aval_size(var)
    while True:
        eqn = defs.get(var)
        if eqn is None:
            return None, smallest
        if eqn.primitive.name not in SHAPE_NOOPS:
            return eqn, smallest
        data_in = eqn.invars[0]
        if not isinstance(data_in, jax_core.Var):
            return None, smallest
        var = data_in
        smallest = min(smallest, aval_size(var))


def aval_size(var_or_aval) -> int:
    """Total element count of a var's (or aval's) shape."""
    aval = getattr(var_or_aval, "aval", var_or_aval)
    size = 1
    for d in getattr(aval, "shape", ()):
        size *= int(d)
    return size


def out_size(eqn) -> int:
    """Total element count of an equation's first output."""
    return aval_size(eqn.outvars[0])


def is_float(var_or_aval) -> bool:
    aval = getattr(var_or_aval, "aval", var_or_aval)
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and dtype.kind == "f"


def source_functions(eqn) -> tuple[str, ...]:
    """Best-effort ``fn@file:line`` strings for an equation's user frames."""
    if _src_info is None:
        return ()
    try:
        frames = list(_src_info.user_frames(eqn.source_info))
    except Exception:  # pragma: no cover - frame layout varies across jax
        return ()
    out = []
    for fr in frames:
        fname = str(getattr(fr, "file_name", "?")).rsplit("/", 1)[-1]
        out.append(f"{getattr(fr, 'function_name', '?')}@{fname}:{getattr(fr, 'start_line', 0)}")
    return tuple(out)
