"""runtime/spans: the host-side recorder, JAX's compile events as spans and
counters, the device scopes of the step and the update, and the benchmark's
set-up metrics that read the recorder."""

import importlib.util
import re
import sys
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache

from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig
from repro.runtime import spans

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import reduce_trace  # noqa: E402

ACTIVITY = {"synaptic_input", "neuron_update", "records"}
UPDATE = {"delete", "vacancies", "upward", "find", "conflicts", "insert"}
# What `lax.scan` and the step's `lax.cond` emit themselves, named at their
# call sites: the loop's condition and counter, the stacking of each step's
# records, the cond and its predicate as a branch index.
LOOP = "jit(simulate)/while/"
CONTROL = {
    LOOP + "cond/lt",
    LOOP + "body/add",
    LOOP + "body/dynamic_update_slice",
    LOOP + "body/closed_call/cond",
    LOOP + "body/closed_call/convert_element_type",
}
# XLA's own instructions where it inlines the scan body (hoisted constants,
# broadcasts of them, tuple plumbing) carry the call's name and no deeper.
INLINED = LOOP + "body/closed_call"

_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")


@pytest.fixture(autouse=True)
def fresh_registry():
    spans.reset()
    yield
    spans.reset()


def test_spans_nest_with_parents_and_monotone_times():
    with spans.span("outer"):
        with spans.span("inner"):
            pass
        with spans.span("inner"):
            pass
    got = spans.spans()
    assert [(s.name, s.parent) for s in got] == [
        ("inner", "outer"),
        ("inner", "outer"),
        ("outer", None),
    ]
    for s in got:
        assert s.start <= s.end
    first, second, outer = got
    assert outer.start <= first.start <= first.end <= second.start <= second.end <= outer.end


def test_seconds_counts_overlapping_spans_once():
    assert spans.seconds("a") is None
    for s in [
        spans.Span("a", 0.0, 2.0, None),
        spans.Span("a", 1.0, 1.5, "a"),  # nested: not counted again
        spans.Span("b", 1.0, 4.0, None),
        spans.Span("a", 5.0, 6.0, None),
    ]:
        spans._record(s)
    assert spans.seconds("a") == pytest.approx(3.0)
    assert spans.seconds("a", "b") == pytest.approx(5.0)
    spans.count("x")
    spans.count("x", 2)
    assert spans.counters() == {"x": 3}
    spans.reset()
    assert spans.spans() == [] and spans.counters() == {}


@pytest.fixture
def persistent_cache(tmp_path):
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _tiny():
    return jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)


def test_compile_events_become_spans_and_cache_counters(persistent_cache):
    x = np.arange(7, dtype=np.float32)
    with spans.span("setup"):
        _tiny()(x).block_until_ready()
    names = {s.name for s in spans.spans()}
    assert {"compile.trace", "compile.lower", "compile.backend"} <= names
    assert spans.counters().get("compile.cache_miss", 0) >= 1
    assert "compile.cache_hit" not in spans.counters()
    backend = [s for s in spans.spans() if s.name == "compile.backend"]
    assert all(s.parent == "setup" and s.start <= s.end for s in backend)
    assert any("lambda" in (s.fun_name or "") for s in backend)

    spans.reset()
    jax.clear_caches()
    _tiny()(x).block_until_ready()
    assert spans.counters().get("compile.cache_hit", 0) >= 1
    assert "compile.cache_miss" not in spans.counters()
    assert spans.seconds("compile.backend") > 0


def _chunk_program(method: str) -> str:
    pos = np.random.default_rng(0).uniform(0, 1000, (512, 3)).astype(np.float32)
    engine = PlasticityEngine(pos, MSPConfig(), FMMConfig(), EngineConfig(method=method))
    assert [s.name for s in spans.spans() if s.name == "engine.init"] == ["engine.init"]
    steps = engine.msp_cfg.update_interval
    lowered = PlasticityEngine.simulate.lower(engine, engine.init_state(), jax.random.key(0), steps)
    return lowered.compile().as_text()


def _device_op_names(text: str) -> dict:
    """{instruction: op_name or None} for the instructions that run as
    device ops: not those inside a fusion's body, nor the bodies of reducers
    and comparators, which run inside their caller's op."""
    inner = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    out, comp = {}, None
    for line in text.splitlines():
        head = reduce_trace._HEADER.match(line)
        if head:
            comp = head.group(1)
            continue
        ins = _INSTR.match(line)
        if ins and comp not in inner:
            op_name = re.search(r'op_name="([^"]+)"', ins.group(2))
            out[ins.group(1)] = op_name.group(1) if op_name else None
    return out


def _phase(op_name: str, layer: str):
    parts = op_name.split("/")
    for a, b in zip(parts, parts[1:]):
        if a == layer:
            return b
    return None


@pytest.mark.parametrize("method", ["fmm", "barnes_hut", "direct"])
def test_device_ops_name_their_layer_and_phase(method):
    text = _chunk_program(method)
    layers = reduce_trace.parse_hlo(text)
    seen = {"update": set(), "activity": set()}
    unscoped = []
    for instr, op_name in _device_op_names(text).items():
        layer = layers[instr][0]
        if layer == "other" or op_name is None or op_name == INLINED:
            continue
        if layer == "activity" and op_name in CONTROL:
            continue
        phase = _phase(op_name, layer)
        if phase is None:
            unscoped.append((instr, layer, op_name))
        else:
            seen[layer].add(phase)
    assert unscoped == []
    assert seen["activity"] == ACTIVITY
    assert seen["update"] == (UPDATE - {"upward"} if method == "direct" else UPDATE)
    if method == "fmm":
        for scope in ("update/find/descent/", "update/find/leaf_resolve/"):
            assert scope in text


def _metric(name):
    spec = importlib.util.spec_from_file_location(name, CHIP / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize(
    "metric, names",
    [
        ("setup_engine_s", ["engine.init"]),
        ("setup_lower_s", ["compile.trace", "compile.lower"]),
        ("setup_compile_s", ["compile.backend"]),
    ],
)
def test_setup_metrics_read_the_recorded_spans(metric, names):
    read = _metric(metric).read
    assert read({}, {}) is None
    spans._record(spans.Span("unrelated", 0.0, 50.0, None))
    for i, name in enumerate(names):
        spans._record(spans.Span(name, 10.0 * i, 10.0 * i + 1.5, None))
    assert read({}, {}) == pytest.approx(1.5 * len(names))
