"""Readings that the limits of a cell are set from: the compared numbers of
the program and of the bfloat16 control, seed by seed, in one process.

    python3 benchmarks/chip/readings.py --workload grow-131k \
        --seeds 11 12 13 --control-seeds 11 12 13 --chunks 1

The deployment's program is compiled once and every seed runs as a
benchmark run does (start state, warm chunk, `--chunks` window chunks, the
chunk the seed draws checked), so a dozen seeds cost one compile.  The last
line of standard output is a JSON list of {seed, control, numbers}.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="*", default=[])
    parser.add_argument("--control-seeds", type=int, nargs="*", default=[])
    parser.add_argument("--chunks", type=int, default=1)
    args = parser.parse_args(argv)

    sys.path.insert(0, str(run.CHECKOUT / "src"))
    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("FAILED: no TPU")
    cell = run.load_cell(args.workload)
    positions, driver = run.build(cell)
    rows = []
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        state, key = run.start(cell, driver, seed)
        stream = driver.stream(key)
        if driver.compiled is None:
            run.log(f"compile_s {driver.compile(state, stream):.3f}")
        state, _ = driver.chunk(state, stream)
        chunks = []
        for _ in range(args.chunks):
            out = jax.block_until_ready(driver.chunk(state, stream))
            chunks.append((state, out))
            state = out[0]
        for control in (False, True):
            if seed in (args.control_seeds if control else args.seeds):
                checks = run.check(cell, driver, positions, key, seed, list(chunks), control)
                numbers = {k: v["value"] for k, v in checks.items()}
                rows.append(dict(seed=seed, control=control, numbers=numbers))
                run.log(f"seed {seed} control {control} {numbers}")
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
