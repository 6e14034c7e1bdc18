import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count at first init.
# Placeholder host devices exist ONLY for this dry-run; smoke tests and
# benchmarks run in separate processes and see the real single device.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds abstract (ShapeDtypeStruct) train/serve state with full sharding
     annotations from repro.sharding.rules,
  2. jits the step with in/out shardings and .lower().compile()s it on the
     production mesh (16x16 single-pod / 2x16x16 multi-pod),
  3. records compiled.memory_analysis() (fits-per-device evidence),
     compiled.cost_analysis() (FLOPs / bytes for §Roofline), and the
     collective-op byte census parsed from the optimized HLO,
  4. appends the row to the JSON results file (resumable: existing cells are
     skipped unless --force).

Run:  PYTHONPATH=src python -m repro.launch.dryrun --all
      PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --multi-pod
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models.config import ALL_SHAPES, ModelConfig, ShapeConfig, \
    shape_applicability
from repro.optim import adamw

RESULTS = "dryrun_results.json"

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
    * collectives inside a WHILE BODY (the scanned layer stacks) execute once
      per trip, but appear once in the text: their bytes are multiplied by
      `body_trips` (the layer count).  XLA hoists the parameter all-gathers
      out of the loops, so those stay x1 — verified on probes.
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


def _first(d: Optional[Dict], *keys, default=0.0):
    if not d:
        return default
    for k in keys:
        if k in d:
            return d[k]
    return default


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             opt_cfg: Optional[adamw.OptConfig] = None) -> Dict[str, Any]:
    cfg = configs.get(arch)
    shape = next(s for s in ALL_SHAPES if s.name == shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    row: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "kind": shape.kind}

    skip = shape_applicability(cfg, shape)
    if skip:
        row.update(status="SKIP", reason=skip)
        return row

    opt_cfg = opt_cfg or adamw.OptConfig()
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = S.input_specs(cfg, shape)
    batch_sh = S.batch_shardings(mesh, specs)

    if shape.kind == "train":
        from repro.sharding import rules as R
        state = S.abstract_train_state(cfg, opt_cfg)
        # TP-degree policy: pure DP (model axis carries batch shards) when
        # the train state fits at fsdp-only ZeRO sharding — kills the
        # per-layer tensor-parallel psums (EXPERIMENTS.md §Perf LM-global).
        dp = S.use_dp_over_model(cfg, mesh, shape.global_batch)
        row["dp_over_model"] = dp
        state_sh = S.state_shardings(mesh, cfg, opt_cfg, dp_over_model=dp)
        if dp:
            batch_sh = {k: NamedSharding(mesh, R.data_spec(
                mesh, v.shape, include_model=True))
                for k, v in specs.items()}
        fn = S.make_train_step(cfg, opt_cfg, mesh=mesh, dp_over_model=dp)
        metrics_sh = {"loss": NamedSharding(mesh, P()),
                      "grad_norm": NamedSharding(mesh, P()),
                      "lr": NamedSharding(mesh, P())}
        jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, metrics_sh))
        with mesh:
            lowered = jitted.lower(state, specs)
    elif shape.kind == "prefill":
        params = S.abstract_params(cfg)
        params_sh = S.param_shardings(mesh, cfg, serve=True)
        fn = S.make_prefill_step(cfg, mesh=mesh)
        # Prefill logits (last position) stay sharded, like decode's.
        logits_sh = S.logits_shardings(mesh, cfg, shape.global_batch)
        if cfg.is_encoder:
            # encoder emits (B, S, V) frame logits: batch-sharded output
            from repro.sharding import rules as _rules
            logits_sh = NamedSharding(mesh, _rules.data_spec(
                mesh, (shape.global_batch, shape.seq_len, cfg.vocab_size)))
            jitted = jax.jit(fn, in_shardings=(params_sh, batch_sh),
                             out_shardings=logits_sh)
            with mesh:
                lowered = jitted.lower(params, specs)
        else:
            caches = S.abstract_caches(cfg, shape.global_batch, shape.seq_len)
            caches_sh = S.cache_shardings(mesh, cfg, shape.global_batch,
                                          shape.seq_len)
            jitted = jax.jit(fn, in_shardings=(params_sh, caches_sh, batch_sh),
                             out_shardings=(logits_sh, caches_sh))
            with mesh:
                lowered = jitted.lower(params, caches, specs)
    else:  # decode
        params = S.abstract_params(cfg)
        params_sh = S.param_shardings(mesh, cfg, serve=True)
        caches = S.abstract_caches(cfg, shape.global_batch, shape.seq_len)
        caches_sh = S.cache_shardings(mesh, cfg, shape.global_batch,
                                      shape.seq_len)
        fn = S.make_decode_step(cfg, mesh=mesh)
        # Serving keeps logits SHARDED (batch@fsdp, vocab@model): replicating
        # them all-gathered 78 MB f32/step at qwen2-decode scale — sampling
        # works on sharded vocab with tiny argmax/psum collectives
        # (EXPERIMENTS.md §Perf LM-cell-2).
        logits_sh = S.logits_shardings(mesh, cfg, shape.global_batch)
        jitted = jax.jit(fn, in_shardings=(params_sh, caches_sh, batch_sh),
                         out_shardings=(logits_sh, caches_sh))
        with mesh:
            lowered = jitted.lower(params, caches, specs)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    census = collective_census(hlo, body_trips=cfg.num_layers)

    row.update(
        status="OK",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        flops=float(_first(cost, "flops")),
        hlo_bytes=float(_first(cost, "bytes accessed")),
        mem_per_device={
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size": getattr(mem, "generated_code_size_in_bytes",
                                           None),
        },
        collectives=census,
    )
    return row


def load_results(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return {}


def save_results(path: str, rows: Dict[str, Any]):
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=RESULTS)
    args = ap.parse_args()

    rows = load_results(args.out)
    archs = sorted(configs.ARCHS) if (args.all or not args.arch) \
        else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'2x16x16' if mp else '16x16'}"
                if key in rows and rows[key].get("status") in ("OK", "SKIP") \
                        and not args.force:
                    print(f"[cached] {key}")
                    continue
                print(f"[run] {key} ...", flush=True)
                try:
                    row = run_cell(arch, shape, mp)
                except Exception as e:
                    row = {"arch": arch, "shape": shape,
                           "mesh": '2x16x16' if mp else '16x16',
                           "status": "FAIL", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                rows[key] = row
                save_results(args.out, rows)
                status = row["status"]
                extra = row.get("reason") or row.get("error") or \
                    f"compile={row.get('compile_s')}s flops={row.get('flops'):.3g}"
                print(f"  -> {status}: {extra}", flush=True)


if __name__ == "__main__":
    main()
