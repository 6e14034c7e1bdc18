"""The contract rules R1-R4 (DESIGN.md §15).

Each rule is a pure function ``(jaxpr, config, entry) -> list[Finding]``
over a closed jaxpr, built on the iterators in `repro.audit.walker`.
`audit_jaxpr` dispatches a ``{rule_id: config}`` mapping; unknown rule ids
are an error so a typo in an AUDIT annotation cannot silently skip a rule.

Origin incidents (why each rule exists) are documented per-rule below and
in DESIGN.md §15; the golden seeded violations live in
`repro.audit.bad_examples` and tests/test_audit.py.
"""

from __future__ import annotations

from typing import Any, Mapping

from jax.extend import core as jax_core

from repro.audit import walker
from repro.audit.report import Finding
from repro.audit.walker import EqnContext

# Cross-device collective primitives and where their axis names live in
# eqn.params.  `psum_scatter` lowers to `reduce_scatter`; on a size-1 mesh
# axis jax may simplify it to a plain `psum`, so both spellings are listed.
COLLECTIVE_AXIS_PARAMS: dict[str, str] = {
    "psum": "axes",
    "all_gather": "axis_name",
    "reduce_scatter": "axis_name",
    "psum_scatter": "axis_name",
    "all_to_all": "axis_name",
    "ppermute": "axis_name",
}


def collective_axes(eqn) -> tuple[str, ...]:
    """Named mesh axes a collective equation operates over."""
    param = COLLECTIVE_AXIS_PARAMS.get(eqn.primitive.name)
    if param is None:
        return ()
    axes = eqn.params.get(param, ())
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _where(eqn, ctx: EqnContext) -> str:
    src = walker.source_functions(eqn)
    loc = src[0] if src else ""
    path = "/".join(ctx.path)
    return f"{path} {loc}".strip()


def _allowlisted(eqn, ctx: EqnContext, allowlist) -> bool:
    """True if any allowlist substring matches a source frame or path label."""
    if not allowlist:
        return False
    hay = list(walker.source_functions(eqn)) + list(ctx.path)
    return any(any(token in h for h in hay) for token in allowlist)


# ---------------------------------------------------------------------------
# R1 — bit-pin coverage.
#
# Origin incident: at pool=48 with K>=2 serve slots, LLVM contracted the
# `fsub`-of-`fmul` in the record std (calcium - mean, mean = det_sum * inv)
# into an FMA, drifting calcium_std by 1 ulp vs the isolated run.  The fix
# is `_pin_f32` (engine.py): an int32 bitcast round-trip the optimizer
# cannot see through.  R1 statically re-checks the shape of the fix: any
# float `sub` feeding a `sqrt` whose broadcast-expanded operand is rooted
# at a raw `mul`/`div` (an unpinned mean) is a violation; pinned means the
# provenance chain ends at a bitcast instead.
# ---------------------------------------------------------------------------


def _detect_pins(jx) -> list[Any]:
    """Bitcast int->float eqns whose input chains back to a float->int bitcast."""
    defs = walker.def_map(jx)
    pins = []
    for eqn in jx.eqns:
        if eqn.primitive.name != "bitcast_convert_type":
            continue
        if not walker.is_float(eqn.outvars[0]):
            continue
        # walk back through integer arithmetic to find the opening bitcast
        stack = [v for v in eqn.invars if isinstance(v, jax_core.Var)]
        seen: set[int] = set()
        found = False
        while stack and not found:
            v = stack.pop()
            d = defs.get(v)
            if d is None or id(d) in seen:
                continue
            seen.add(id(d))
            name = d.primitive.name
            if name == "bitcast_convert_type" and walker.is_float(d.invars[0]):
                found = True
            elif name in ("add", "sub", "min", "max", "convert_element_type") or name in (
                walker.SHAPE_NOOPS
            ):
                stack.extend(v for v in d.invars if isinstance(v, jax_core.Var))
        if found:
            pins.append(eqn)
    return pins


def _squared_subs(slice_eqns, defs) -> list[Any]:
    """`sub` eqns whose result is squared inside the slice.

    The FMA hazard is exactly `fmul(fsub(x, mean), fsub(x, mean))`: LLVM
    contracts the mul-of-sub when the mean is a visible `fmul`.  A sub
    whose result is not squared cannot contract this way, so restricting
    to squared subs keeps unrelated x-minus-scalar arithmetic in the
    activity update out of the rule.
    """
    subs = []
    for eqn in slice_eqns:
        name = eqn.primitive.name
        if name == "integer_pow" and eqn.params.get("y") == 2:
            squared = [eqn.invars[0]]
        elif name == "mul" and eqn.invars[0] is eqn.invars[1]:
            squared = [eqn.invars[0]]
        else:
            continue
        for v in squared:
            if not isinstance(v, jax_core.Var):
                continue
            d = defs.get(v)
            if d is not None and d.primitive.name == "sub" and walker.is_float(d.outvars[0]):
                subs.append(d)
    return subs


def rule_r1_bit_pin(jaxpr, config: Mapping[str, Any], entry: str) -> list[Finding]:
    allowlist = tuple(config.get("allowlist", ()))
    require_pins = int(config.get("require_pins", 1))
    require_pinned_subs = int(config.get("require_pinned_subs", 1))
    findings: list[Finding] = []
    total_pins = 0
    pinned_subs = 0
    for jx, ctx in walker.iter_jaxprs(jaxpr):
        total_pins += len(_detect_pins(jx))
        defs = walker.def_map(jx)
        for eqn in jx.eqns:
            if eqn.primitive.name != "sqrt" or not walker.is_float(eqn.outvars[0]):
                continue
            arg = eqn.invars[0]
            if not isinstance(arg, jax_core.Var):
                continue
            slice_eqns = walker.backward_slice(jx, arg, defs)
            for dep in _squared_subs(slice_eqns, defs):
                for op in dep.invars:
                    if not isinstance(op, jax_core.Var):
                        continue
                    root, pinch = walker.root_def_min_size(op, defs)
                    if root is None or pinch >= walker.out_size(dep):
                        continue  # the deviation side, not the reduced mean
                    rname = root.primitive.name
                    if rname == "bitcast_convert_type":
                        pinned_subs += 1
                    elif rname in ("mul", "div") and walker.is_float(root.outvars[0]):
                        if _allowlisted(dep, ctx, allowlist):
                            continue
                        findings.append(
                            Finding(
                                rule="R1",
                                entry=entry,
                                message=(
                                    "record-path std: squared deviation subtract reads "
                                    f"a raw `{rname}` mean with no _pin_f32 bitcast "
                                    "round-trip (FMA contraction hazard)"
                                ),
                                where=_where(dep, ctx),
                            )
                        )
    if total_pins < require_pins:
        findings.append(
            Finding(
                rule="R1",
                entry=entry,
                message=(
                    f"expected >= {require_pins} _pin_f32 bitcast round-trip(s) in the "
                    f"trace, found {total_pins} — record path lost its pin"
                ),
            )
        )
    if pinned_subs < require_pinned_subs:
        findings.append(
            Finding(
                rule="R1",
                entry=entry,
                message=(
                    f"expected >= {require_pinned_subs} pinned deviation subtract(s) "
                    f"feeding a sqrt, found {pinned_subs} — std record path missing or "
                    "restructured; update the entry's R1 config if intentional"
                ),
            )
        )
    return findings


# ---------------------------------------------------------------------------
# R2 — collective scoping.
#
# Origin incident: the bitwise contract scopes every cross-device reduction
# to the data axis (replicas on the ensemble axis must stay independent —
# a psum over "ensemble" silently averages replicas and still typechecks).
# Axis roles are declared machine-readably in sharding/rules.AXIS_CONTRACTS;
# each entry point additionally declares which axes it may touch at all.
# ---------------------------------------------------------------------------


def rule_r2_collective_scope(jaxpr, config: Mapping[str, Any], entry: str) -> list[Finding]:
    from repro.sharding import rules as sharding_rules

    contracts = config.get("contracts")
    if contracts is None:
        contracts = sharding_rules.AXIS_CONTRACTS
    allowed = config.get("allowed_axes")
    allowed = None if allowed is None else frozenset(allowed)
    findings: list[Finding] = []
    for eqn, ctx in walker.iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_AXIS_PARAMS:
            continue
        for axis in collective_axes(eqn):
            contract = contracts.get(axis)
            if contract is None:
                findings.append(
                    Finding(
                        rule="R2",
                        entry=entry,
                        message=(
                            f"collective `{name}` over undeclared axis {axis!r} — "
                            "declare it in sharding/rules.AXIS_CONTRACTS"
                        ),
                        where=_where(eqn, ctx),
                    )
                )
                continue
            if name not in contract["collectives"]:
                findings.append(
                    Finding(
                        rule="R2",
                        entry=entry,
                        message=(
                            f"collective `{name}` over axis {axis!r} violates its "
                            f"declared role {contract['role']!r} "
                            f"(sanctioned: {sorted(contract['collectives']) or 'none'})"
                        ),
                        where=_where(eqn, ctx),
                    )
                )
            if allowed is not None and axis not in allowed:
                findings.append(
                    Finding(
                        rule="R2",
                        entry=entry,
                        message=(
                            f"collective `{name}` over axis {axis!r} inside an entry "
                            f"point scoped to axes {sorted(allowed) or 'none'} — "
                            "replica-local phases must not reduce across this axis"
                        ),
                        where=_where(eqn, ctx),
                    )
                )
    return findings


# ---------------------------------------------------------------------------
# R3 — cond-vs-select.
#
# Origin incident: `lax.cond` with a batched predicate lowers to `select`
# under vmap — both branches run.  For the rare-deletion path that turned
# the O(E) edge-table gather into unconditional per-step work (DESIGN.md
# §10); `_cond_delete` (custom_vmap, batch-reduced predicate) restored the
# cond.  R3 generalizes the jaxpr walker that pinned the fix: every
# `all_gather` at least `min_size` elements large must sit under a real
# `cond` equation, and (by default) at least one such conditional gather
# must exist so the rule cannot pass vacuously.
# ---------------------------------------------------------------------------


def rule_r3_cond_gather(jaxpr, config: Mapping[str, Any], entry: str) -> list[Finding]:
    min_size = int(config["min_size"])
    require_conditional = bool(config.get("require_conditional", True))
    findings: list[Finding] = []
    conditional = 0
    for eqn, ctx in walker.iter_eqns(jaxpr):
        if eqn.primitive.name != "all_gather":
            continue
        if walker.out_size(eqn) < min_size:
            continue
        if ctx.in_cond:
            conditional += 1
        else:
            findings.append(
                Finding(
                    rule="R3",
                    entry=entry,
                    message=(
                        f"O(E) all_gather ({walker.out_size(eqn)} elems >= {min_size}) "
                        "runs unconditionally — a lax.cond lowered to select "
                        "(batched predicate under vmap?); see _cond_delete"
                    ),
                    where=_where(eqn, ctx),
                )
            )
    if require_conditional and conditional == 0:
        findings.append(
            Finding(
                rule="R3",
                entry=entry,
                message=(
                    f"no conditional all_gather >= {min_size} elems found — the "
                    "deletion gather disappeared; update the entry's R3 config if "
                    "the threshold moved"
                ),
            )
        )
    return findings


# ---------------------------------------------------------------------------
# R4 — reduction-order stability.
#
# Origin incident: `jnp.sum` associates by shape, so a raw sum over an axis
# whose length varies with shard count or padding changes its rounding —
# the padded serve pool and the sharded engines only stay bitwise because
# record-path reductions go through the prefix-stable halving tree
# (`synapses.det_sum`) or exact integer/zero-padded paths.  R4 flags float
# `reduce_sum`/`dot_general` equations whose reduced axis length equals a
# declared padded/sharded size, outside an explicit allowlist.
# ---------------------------------------------------------------------------


def _dot_contract_sizes(eqn) -> list[int]:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    shape = getattr(eqn.invars[0].aval, "shape", ())
    return [int(shape[d]) for d in lhs_c if d < len(shape)]


def rule_r4_reduction_order(jaxpr, config: Mapping[str, Any], entry: str) -> list[Finding]:
    padded = frozenset(int(s) for s in config.get("padded_sizes", ()))
    allowlist = tuple(config.get("allowlist", ()))
    if not padded:
        return []
    findings: list[Finding] = []
    for eqn, ctx in walker.iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "reduce_sum":
            if not walker.is_float(eqn.invars[0]):
                continue  # integer sums are exact in any order
            shape = getattr(eqn.invars[0].aval, "shape", ())
            reduced = [int(shape[a]) for a in eqn.params.get("axes", ())]
        elif name == "dot_general":
            if not walker.is_float(eqn.outvars[0]):
                continue
            reduced = _dot_contract_sizes(eqn)
        else:
            continue
        hits = sorted(set(reduced) & padded)
        if not hits or _allowlisted(eqn, ctx, allowlist):
            continue
        findings.append(
            Finding(
                rule="R4",
                entry=entry,
                message=(
                    f"raw float `{name}` over padded/sharded axis size {hits} — "
                    "use the halving-tree helper (synapses.det_sum) or add an "
                    "allowlist entry with a stability argument"
                ),
                where=_where(eqn, ctx),
            )
        )
    return findings


RULES = {
    "R1": rule_r1_bit_pin,
    "R2": rule_r2_collective_scope,
    "R3": rule_r3_cond_gather,
    "R4": rule_r4_reduction_order,
}


def audit_jaxpr(jaxpr, rule_configs: Mapping[str, Mapping[str, Any]], entry: str) -> list[Finding]:
    """Run the configured rules over one traced entry point."""
    findings: list[Finding] = []
    for rule_id, config in rule_configs.items():
        rule = RULES.get(rule_id)
        if rule is None:
            raise KeyError(f"unknown audit rule {rule_id!r} for entry {entry!r}")
        findings.extend(rule(jaxpr, config or {}, entry))
    return findings
