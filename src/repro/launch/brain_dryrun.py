"""Dry-run of the paper's workload on 256 and 512 placeholder ranks.

Lowers + compiles one sharded simulation step of the distributed MSP-FMM
engine (neurons sharded over the flattened device axis — the analogue of the
paper's 64-rank MPI runs, at 256/512 'ranks') and records its memory and
cost analysis and the collective census of its optimized HLO.

    PYTHONPATH=src python -m repro.launch.brain_dryrun [--n-per-rank 512]
"""
import argparse
import json
import os
import re
from typing import Any, Dict, Optional

import numpy as np

if __name__ == "__main__":
    # Before the jax import: jax fixes the device count at first init.  Only
    # the script asks for placeholder host devices; importing the module
    # (tests do) leaves the importer's device count alone.
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import jax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core.distributed import DistributedPlasticityEngine  # noqa: E402
from repro.core.engine import EngineConfig  # noqa: E402
from repro.core.msp import MSPConfig  # noqa: E402
from repro.core.traversal import FMMConfig  # noqa: E402

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s32|u32|s64|u64|s16|u16|s8|u8|pred)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
          "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_census(hlo: str, body_trips: int = 1) -> Dict[str, Any]:
    """Per-collective op count + result bytes from optimized HLO text.

    Conventions:
    * bytes = result-shape bytes of each collective instruction (for
      all-reduce this equals operand bytes; for all-gather it is the gathered
      size — what a ring actually moves through each chip's links);
    * `-start` variants counted, `-done` skipped (same op);
    * collectives inside a WHILE BODY execute once per trip, but appear once
      in the text: their bytes are multiplied by `body_trips`.
    """
    # Map computation name -> its text block.
    comp_blocks: Dict[str, str] = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+)[\w ]* \(.*\) -> .* \{", line)
        if m:
            if cur_name is not None:
                comp_blocks[cur_name] = "\n".join(cur_lines)
            cur_name, cur_lines = m.group(1), []
        elif cur_name is not None:
            cur_lines.append(line)
            if line.startswith("}"):
                comp_blocks[cur_name] = "\n".join(cur_lines)
                cur_name, cur_lines = None, []
    if cur_name is not None:
        comp_blocks[cur_name] = "\n".join(cur_lines)

    # While bodies referenced by any while instruction.
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))

    out = {k: {"count": 0, "bytes": 0, "in_loop_bytes": 0}
           for k in _COLLECTIVES}
    for comp, block in comp_blocks.items():
        mult = body_trips if comp in bodies else 1
        for line in block.splitlines():
            s = line.strip()
            m = re.match(r"%?[\w.\-]+ = (.*?) ([a-z\-]+)(?:-start)?\(", s)
            if not m:
                continue
            op = m.group(2)
            if op.endswith("-done"):
                continue
            for c in _COLLECTIVES:
                if f" {c}(" in s or f" {c}-start(" in s:
                    b = _shape_bytes(m.group(1))
                    out[c]["count"] += 1
                    out[c]["bytes"] += b * mult
                    if mult > 1:
                        out[c]["in_loop_bytes"] += b * mult
                    break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def first(d: Optional[Dict], *keys, default=0.0):
    """The value of the first of `keys` present in `d` (a compiled
    program's cost analysis, which may be empty), else `default`."""
    if not d:
        return default
    for k in keys:
        if k in d:
            return d[k]
    return default


def run(n_per_rank: int, ranks: int) -> dict:
    n = n_per_rank * ranks
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 2000.0, (n, 3)).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()[:ranks]).reshape(ranks), ("data",))
    eng = DistributedPlasticityEngine(
        pos, mesh, "data", MSPConfig.calibrated(),
        FMMConfig(), EngineConfig(method="fmm", domain=2000.0))
    step = eng.make_sharded_step()
    state = jax.eval_shape(eng.init_state)
    lowered = step.lower(state, jax.eval_shape(lambda: jax.random.key(0)))
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    census = collective_census(compiled.as_text(), body_trips=1)
    return {
        "ranks": ranks, "neurons": n, "octree_depth": eng.structure.depth,
        "flops": float(first(cost, "flops")),
        "bytes": float(first(cost, "bytes accessed")),
        "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
        "collectives": census,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-per-rank", type=int, default=512)
    ap.add_argument("--out", default="brain_dryrun_results.json")
    args = ap.parse_args()
    out = {}
    for ranks in (256, 512):
        print(f"[brain dry-run] {ranks} ranks x {args.n_per_rank} neurons",
              flush=True)
        out[ranks] = run(args.n_per_rank, ranks)
        print(f"  depth={out[ranks]['octree_depth']} "
              f"coll_bytes={out[ranks]['collectives']['total_bytes']/1e6:.1f} MB "
              f"temp={out[ranks]['temp_bytes_per_device']/1e6:.1f} MB/device",
              flush=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
