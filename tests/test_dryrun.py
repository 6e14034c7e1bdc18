"""The dry-run's collective census (repro.launch.brain_dryrun) and the
cost-analysis behaviour it has to work around."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.launch.brain_dryrun import collective_census

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
BYTES = 8 * 128 * 4  # f32[8,128]


def _module(entry, body=""):
    """HLO text: an ENTRY computation of `entry` lines and, when `body` is
    given, a while loop whose body computation runs those lines."""
    loop = ""
    if body:
        loop = (
            "%body (b: f32[8,128]) -> f32[8,128] {\n"
            "  %b = f32[8,128]{1,0} parameter(0)\n"
            f"{body}"
            "  ROOT %nb = f32[8,128]{1,0} negate(f32[8,128]{1,0} %b)\n"
            "}\n\n"
            "%cond (c: f32[8,128]) -> pred[] {\n"
            "  %c = f32[8,128]{1,0} parameter(0)\n"
            "  ROOT %t = pred[] constant(true)\n"
            "}\n\n"
        )
        entry += "  %w = f32[8,128]{1,0} while(f32[8,128]{1,0} %p), condition=%cond, body=%body\n"
    return (
        "HloModule census\n\n"
        f"{loop}"
        "ENTRY %main (p: f32[8,128]) -> f32[8,128] {\n"
        "  %p = f32[8,128]{1,0} parameter(0)\n"
        f"{entry}"
        "  ROOT %r = f32[8,128]{1,0} negate(f32[8,128]{1,0} %p)\n"
        "}\n"
    )


def _op(kind, name="x", form="sync"):
    if form == "sync":
        return f"  %{name} = f32[8,128]{{1,0}} {kind}(f32[8,128]{{1,0}} %p)\n"
    return (
        f"  %{name}s = f32[8,128]{{1,0}} {kind}-start(f32[8,128]{{1,0}} %p)\n"
        f"  %{name}d = f32[8,128]{{1,0}} {kind}-done(f32[8,128]{{1,0}} %{name}s)\n"
    )


@pytest.mark.parametrize("form", ["sync", "async"])
@pytest.mark.parametrize("kind", KINDS)
def test_census_counts_each_collective_once(kind, form):
    """A collective counts once with its result bytes; of an async pair the
    `-start` counts and the `-done` does not."""
    census = collective_census(_module(_op(kind, form=form)))
    assert census[kind] == {"count": 1, "bytes": BYTES, "in_loop_bytes": 0}
    for other in KINDS:
        if other != kind:
            assert census[other]["count"] == 0
    assert census["total_bytes"] == BYTES


def test_body_trips_multiply_only_while_body_collectives():
    hlo = _module(_op("all-gather", "g"), body=_op("all-reduce", "a"))
    census = collective_census(hlo, body_trips=12)
    assert census["all-reduce"] == {"count": 1, "bytes": 12 * BYTES, "in_loop_bytes": 12 * BYTES}
    assert census["all-gather"] == {"count": 1, "bytes": BYTES, "in_loop_bytes": 0}
    assert census["total_bytes"] == 13 * BYTES
    assert collective_census(hlo)["all-reduce"]["bytes"] == BYTES


def test_census_of_hlo_without_collectives_is_zero():
    census = collective_census(_module(""))
    assert census["total_bytes"] == 0
    assert all(census[kind] == {"count": 0, "bytes": 0, "in_loop_bytes": 0} for kind in KINDS)


def test_cost_analysis_counts_scan_body_once():
    """Why the census takes `body_trips`: XLA's cost analysis, like the HLO
    text, counts a while body once however many times it runs."""

    def body(x, w):
        return jnp.tanh(x @ w), None

    def with_scan(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    def unrolled(x, ws):
        for i in range(ws.shape[0]):
            x = jnp.tanh(x @ ws[i])
        return x

    x = jnp.zeros((64, 128))
    ws = jnp.zeros((8, 128, 128))
    f_scan = jax.jit(with_scan).lower(x, ws).compile().cost_analysis()["flops"]
    f_unr = jax.jit(unrolled).lower(x, ws).compile().cost_analysis()["flops"]
    assert f_unr == pytest.approx(8 * f_scan, rel=0.05)


def _run(script, xla_flags=None):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    res = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True, timeout=600
    )
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    return res.stdout.strip().splitlines()[-1]


def test_import_leaves_the_device_count_alone():
    """Only running the module as a script asks for placeholder devices."""
    script = "import jax, repro.launch.brain_dryrun; print(jax.device_count())"
    assert _run(script) == "1"


def test_dry_run_counts_the_distributed_steps_collectives():
    """One sharded step of 4 x 64 neurons on 4 host devices: the census
    finds the step's all-reduces and all-gathers over the data axis."""
    script = (
        "import json\n"
        "from repro.launch import brain_dryrun\n"
        "print(json.dumps(brain_dryrun.run(n_per_rank=64, ranks=4)))\n"
    )
    out = json.loads(_run(script, "--xla_force_host_platform_device_count=4"))
    assert out["ranks"] == 4 and out["neurons"] == 256
    census = out["collectives"]
    assert census["all-reduce"]["count"] > 0
    assert census["all-gather"]["count"] > 0
    assert census["total_bytes"] > 0
    assert out["flops"] > 0
