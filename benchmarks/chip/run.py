"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload grow-131k --seed 7 \
        --seconds 25 --trace 0

A cell is an entry of BENCHMARK.json's `workloads`: a deployment
(`configs/<config>.json`) under a traffic (`traffic/<traffic>.json`: the
chunk length and the start state, `states/<config>/<start>.npz`, grown on
the chip by `make_start.py`), with the limits of the numbers that decide
`correct` in `limits/<cell>.json`.  The deployment
names its entry kind, `drivers/<kind>.py`, and its geometry,
`geometry/<name>.py`.  Per-layer metrics are `metrics/<metric>.py`, listed
for the cell in BENCHMARK.json.  Adding a deployment, a traffic, a cell, an
entry kind or a metric is adding files.

A run: build the deployment (set-up starts at process start), compile the
cell's one chunk program ahead of time, run one chunk as warm-up, then run
chunks, each ending in `block_until_ready`, until `--seconds` have passed
and the chunk in flight is done.  A compile inside that window ends the run
with an error.  With `--trace 1` the window is one profiled chunk and the
result holds the per-layer metrics instead of the end-to-end ones.  After
the window, one chunk drawn from the seed is replayed by `reference.py` and
compared; the numbers and their limits close standard error and the result.

The last line of standard output is the result, one JSON object.  Without a
TPU, or with fewer chips than the cell asks for, the run fails before it.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import states  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The precision a configuration states, as JAX's default for matrix
# products: on a TPU the default would run float32 products in one
# bfloat16 pass.
MATMUL = {"float32": "float32"}
CHECKS = ("neurons_off", "records_gap", "descent_gap", "pick_gap", "edges_off")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str) -> dict:
    """The cell's entry in BENCHMARK.json with its deployment, its traffic,
    its limits and the per-layer metrics it reports."""
    bench = load_json(CHECKOUT / "BENCHMARK.json")
    (entry,) = [w for w in bench["workloads"] if w["name"] == name]
    traffic = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    return dict(
        name=name,
        chips=entry["chips"],
        config=load_json(HERE / "configs" / f"{entry['config']}.json"),
        traffic=traffic,
        start=states.path(entry["config"], traffic["start"]),
        limits=load_json(HERE / "limits" / f"{name}.json"),
        metrics=[m for m in bench["per_layer"] if name in m.get("workloads", [name])],
    )


class CompileCounter:
    """Counts backend compilations while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def device_info(devices) -> dict:
    dev = devices[0]
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0) for d in devices if d.memory_stats()]
    return dict(
        platform=dev.platform,
        kind=dev.device_kind,
        count=len(devices),
        memory_peak_bytes=max(peaks) if peaks else 0,
    )


def build(cell: dict):
    """The deployment's positions and its entry-kind driver, which computes
    in the precision the deployment states."""
    import jax

    config = cell["config"]
    jax.config.update("jax_default_matmul_precision", MATMUL[config["precision"]])
    geometry = load_module(HERE / "geometry" / f"{config['network']['geometry']}.py")
    positions = geometry.positions(config["network"])
    driver = load_module(HERE / "drivers" / f"{config['driver']}.py").build(
        config, positions, cell["traffic"]["chunk_steps"])
    return positions, driver


def start(cell: dict, driver, seed: int):
    """The traffic's start state on the device and the seed's key, from
    which every random stream of the run is folded."""
    import jax

    return driver.device(states.load(cell["start"])), jax.random.key(seed)


def window(driver, state, stream, seconds: float, trace: bool):
    """Chunks until `seconds` have passed (one chunk when traced); returns
    [(state in, (state out, records))] and the seconds taken."""
    import jax

    chunks, ends = [], []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("dispatch chunk"):
            out = driver.chunk(state, stream)
        with jax.profiler.TraceAnnotation("wait for chunk"):
            out = jax.block_until_ready(out)
        ends.append(time.perf_counter() - t0)
        chunks.append((state, out))
        state = out[0]
        if trace or ends[-1] >= seconds:
            log(f"chunks end at {ends} s")
            return chunks, time.perf_counter() - t0


def check(cell: dict, driver, positions, key, seed: int, chunks, control: bool = False) -> dict:
    """Replay one chunk drawn from the seed; returns {number: (value, limit)}.
    With `control`, the bfloat16 reference stands in for the program."""
    import reference

    steps, limits = cell["traffic"]["chunk_steps"], cell["limits"]
    j = int(np.random.default_rng(seed).integers(len(chunks)))
    s_in, (s_out, recs) = chunks[j]
    starts, outs = driver.host(s_in), driver.host(s_out, recs)
    del chunks[:], s_in, s_out, recs
    t = time.perf_counter()
    tree = reference.octree(positions, cell["config"]["network"]["domain_um"])
    ties = (limits["descent_gap"], limits["pick_gap"])
    numbers = dict.fromkeys(CHECKS + ("float_gap", "shown", "left_out"), 0)
    for m, k, begin, got in zip(driver.models(), driver.keys(key), starts, outs):
        if control:
            got = reference.control_output(m, k, positions, tree, begin, steps)
        rep = reference.replay(m, k, begin, steps, tree)
        for name, value in reference.compare(m, positions, tree, begin, rep, got, ties).items():
            numbers[name] = (numbers[name] + value if name in ("neurons_off", "edges_off", "shown",
                                                              "left_out")
                             else max(numbers[name], value))
    log(f"reference_s {time.perf_counter() - t:.3f} (window chunk {j}; synapses "
        f"{sum(int(c.valid.sum()) for c in starts)} -> {sum(int(c.valid.sum()) for c in outs)}; "
        f"axons whose partner the output shows {numbers['shown']}, left out as near ties "
        f"{numbers['left_out']}; widest float gap of the neuron state {numbers['float_gap']!r})")
    return {name: dict(value=numbers[name], limit=limits.get(name)) for name in CHECKS}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             control: bool = False) -> dict:
    """Set up, warm up, measure and check one cell; returns the result."""
    import jax

    steps = cell["traffic"]["chunk_steps"]
    positions, driver = build(cell)
    state, key = start(cell, driver, seed)
    stream = driver.stream(key)
    log(f"compile_s {driver.compile(state, stream):.3f}")
    t = time.perf_counter()
    state, _ = jax.block_until_ready(driver.chunk(state, stream))
    setup_s = time.perf_counter() - T0
    log(f"warm_chunk_s {time.perf_counter() - t:.3f} setup_s {setup_s:.3f}")

    counter = CompileCounter()
    tracedir = tempfile.mkdtemp(prefix="trace-") if trace else None
    counter.armed = True
    if trace:
        jax.profiler.start_trace(tracedir)
    chunks, window_s = window(driver, state, stream, seconds, trace)
    if trace:
        jax.profiler.stop_trace()
    counter.armed = False
    if counter.count:
        raise SystemExit(f"FAILED: {counter.count} compilation(s) inside the window")
    del state
    device = device_info(jax.devices())
    log(f"window_s {window_s:.3f} chunks {len(chunks)} memory_peak_bytes "
        f"{device['memory_peak_bytes']}")

    result = dict(correct=False, attempted=len(chunks), failed=0, metrics={}, device=device)
    if trace:
        import reduce_trace

        reduced = reduce_trace.reduce(tracedir, driver.hlo_text(), window_s)
        shutil.rmtree(tracedir, ignore_errors=True)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        ctx = dict(steps=steps, updates=1, neurons=driver.neurons * driver.replicas,
                   device_kind=device["kind"])
        for spec in cell["metrics"]:
            value = load_module(HERE / "metrics" / f"{spec['name']}.py").read(reduced, ctx)
            if value is not None:
                result["metrics"][spec["name"]] = dict(value=value, unit=spec["unit"])
        result["breakdown"] = reduce_trace.breakdown(reduced)
    else:
        rate = driver.neurons * driver.replicas * steps * len(chunks) / window_s
        result["metrics"] = dict(
            neuron_steps_per_s=dict(value=rate, unit="neuron-steps/s"),
            setup_s=dict(value=setup_s, unit="s"),
        )

    driver.compiled = None  # the program's state is freed before the reference
    checks = check(cell, driver, positions, key, seed, chunks, control)
    ok = all(c["limit"] is not None and c["value"] <= c["limit"] for c in checks.values())
    result.update(correct=ok, failed=0 if ok else 1, checks=checks)
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description="one run of one benchmark cell")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    cell = load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"FAILED: no TPU, JAX runs on {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"FAILED: the cell needs {cell['chips']} chips, {len(devices)} found")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
