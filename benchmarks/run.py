"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call where a wall time
is meaningful on this host; derived = the figure's headline quantity), and
writes the full JSON to bench_results.json.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig1 fig5  # subset
    PYTHONPATH=src python -m benchmarks.run --quick fig_ensemble fig_sweep2d
    PYTHONPATH=src python -m benchmarks.run --quick --pr 5 fig_find_scaling

--quick shrinks every figure to CI-smoke sizes (minutes on 2 cores): the
numbers are not publication curves, but the code paths — including the
multi-device subprocesses — are exercised end to end and the JSON artifact
is uploaded per PR, so the perf trajectory stays populated.

--pr N additionally copies the results into benchmarks/trajectory/
BENCH_<N>.json — the committed per-PR perf trajectory (see
benchmarks/README.md).
"""
from __future__ import annotations

import json
import os
import sys
import time

from benchmarks import figures
from repro.launch import compile_cache

# CI-smoke sizes per figure (--quick).  Keys match the run() names below.
QUICK = {
    "fig1_calcium": dict(steps=2_000, n=200),
    "fig2_synapses": dict(steps=2_000, n=200),
    "fig3_strong_scaling": dict(neurons=(1_250, 2_500), reps=1),
    "fig4_weak_scaling": dict(device_counts=(1, 2), n_per=128),
    "fig5_expansion_error": dict(num_boxes=80),
    "fig_ensemble": dict(n=48, k=8, steps=400, reps=1),
    "fig_sweep2d": dict(ensemble=2, data=2, n=128, k=2, steps=300),
    "fig_pyramid_scaling": dict(device_counts=(1, 2), n=512, reps=1, depth=2),
    "fig_find_scaling": dict(device_counts=(1, 2), n=256, steps=400, reps=1,
                             depth=2),
    "fig_exchange": dict(device_counts=(1, 2), n=128, steps=1500, depth=3,
                         sweep_k=2, reps=1, weak_counts=(1, 2, 4, 8, 16)),
    "fig_kernels": dict(gauss_sizes=((256, 1024),), m2l_sizes=(2048,),
                        msp_sizes=(65536,), reps=2),
    "fig_probes": dict(n=160, steps=400, chunk_sizes=(50, 200), reps=1),
    "fig_serve": dict(pool=64, num_sessions=8, round_steps=100,
                      max_rounds_of_work=3, traffic_seed=6, canaries=2),
}


def main() -> None:
    compile_cache.enable()
    args = sys.argv[1:]
    quick = "--quick" in args
    pr_id = None
    if "--pr" in args:
        idx = args.index("--pr")
        if idx + 1 >= len(args) or args[idx + 1].startswith("-") \
                or args[idx + 1].startswith("fig"):
            sys.exit("usage: --pr <id> (a PR number for "
                     "benchmarks/trajectory/BENCH_<id>.json)")
        pr_id = args[idx + 1]
        del args[idx:idx + 2]
    want = set(a for a in args if not a.startswith("-"))
    results = {}
    rows = []

    def run(name, fn, derived_fn):
        if want and not any(name.startswith(w) for w in want):
            return
        t0 = time.perf_counter()
        res = fn(**QUICK.get(name, {})) if quick else fn()
        dt = time.perf_counter() - t0
        if isinstance(res, dict):
            # Whole-figure wall time (compile included), for the trajectory
            # regression gate (tools/check_bench_trajectory.py).
            res["_wall_s"] = dt
        results[name] = res
        rows.append(f"{name},{dt * 1e6:.0f},{derived_fn(res)}")
        print(rows[-1], flush=True)

    run("fig1_calcium", figures.fig1_calcium,
        lambda r: f"ca_fmm={r['fmm']['ca_end']:.3f};target=0.7;"
                  f"agree={r['agree']:.4f}")
    run("fig2_synapses", figures.fig2_synapses,
        lambda r: f"fmm_over_bh={r['fmm_over_bh']:.3f}")
    run("fig3_strong_scaling", figures.fig3_strong_scaling,
        lambda r: "ratios=" + "/".join(str(x) for x in r["scaling_ratios"]))
    run("fig4_weak_scaling", figures.fig4_weak_scaling,
        lambda r: ";".join(f"p{p}={v.get('time_200_steps_s', -1):.2f}s"
                           for p, v in r.items()))
    run("fig5_expansion_error", figures.fig5_expansion_error,
        lambda r: f"hermite_max={r['hermite']['max_pct']:.4f}%;"
                  f"taylor_max={r['taylor']['max_pct']:.4f}%;"
                  f"bound={r['paper_bound_pct']}%")
    run("complexity_sweep", figures.complexity_sweep,
        lambda r: f"fmm_per_neuron@512k={r[512_000]['fmm_per_neuron']:.2f}")
    run("fig_ensemble", figures.fig_ensemble,
        lambda r: f"speedup={r['speedup']:.2f};"
                  f"batched_rps={r['batched_replicas_per_s']:.2f};"
                  f"sequential_rps={r['sequential_replicas_per_s']:.2f}")
    run("fig_sweep2d", figures.fig_sweep2d,
        lambda r: r.get("error", "")[:60] or
                  f"mesh_rps={r['mesh_replicas_per_s']:.2f};"
                  f"seq_rps={r['sequential_replicas_per_s']:.2f};"
                  f"bitwise={r['bitwise_match']}")
    run("fig_pyramid_scaling", figures.fig_pyramid_scaling,
        lambda r: ";".join(
            [f"error@p{k}={str(v['error'])[:40]}" for k, v in r.items()
             if isinstance(v, dict) and "error" in v]
            or ["shardable_ratio="
                + "/".join(str(v) for v in r.get("shardable_ratio_vs_p1",
                                                 {}).values())
                + f";bitwise={r.get('bitwise_all')}"]))
    run("fig_find_scaling", figures.fig_find_scaling,
        lambda r: ";".join(
            [f"error@p{k}={str(v['error'])[:40]}" for k, v in r.items()
             if isinstance(v, dict) and "error" in v]
            or ["boxes_ratio="
                + "/".join(str(v) for v in
                           r.get("descent_boxes_ratio_vs_p1", {}).values())
                + ";payload_ratio="
                + "/".join(str(v) for v in
                           r.get("payload_ratio_sharded_over_replicated",
                                 {}).values())
                + f";bitwise={r.get('bitwise_all')}"]))
    run("fig_exchange", figures.fig_exchange,
        lambda r: ";".join(
            [f"error@p{k}={str(v['error'])[:40]}" for k, v in r.items()
             if isinstance(v, dict) and "error" in v]
            or [f"routed_flatness_x={r['routed_flatness_x']}"
                + f";gathered_growth_x={r['gathered_growth_x']}"
                + f";bitwise={r.get('bitwise_all')}"]))
    run("fig_kernels", figures.fig_kernels,
        lambda r: ";".join(
            [f"error={str(v.get('error'))[:40]}"
             for tier in ("gaussian_nbody", "m2l", "msp_update")
             for v in r[tier].values() if "error" in v]
            or [f"backend={r['backend']};"
                + "gauss_ref_s="
                + "/".join(f"{v['ref_s']:.3f}"
                           for v in r["gaussian_nbody"].values())
                + ";m2l_ref_s="
                + "/".join(f"{v['ref_s']:.3f}" for v in r["m2l"].values())
                + ";msp_ref_s="
                + "/".join(f"{v['ref_s']:.4f}"
                           for v in r["msp_update"].values())]))
    run("fig_probes", figures.fig_probes,
        lambda r: ";".join(
            [f"error@{c}={str(v['error'])[:40]}"
             for c, v in r["chunks"].items() if "error" in v]
            or ["overhead_x="
                + "/".join(f"{v['overhead_x']:.2f}"
                           for v in r["chunks"].values())
                + f";probe_free_s={r['probe_free_s']:.2f}"]))
    run("fig_serve", figures.fig_serve,
        lambda r: (f"error={str(r['error'])[:60]}" if "error" in r else
                   f"batched_sps={r['batched_sessions_per_s']:.3f};"
                   f"seq_sps={r['sequential_sessions_per_s']:.3f};"
                   f"full_batch_x={r['full_batch_over_sequential']:.2f};"
                   f"evictions={r['evictions']}"))

    with open("bench_results.json", "w") as f:
        json.dump(results, f, indent=1, default=str)
    if pr_id is not None:
        # Per-PR perf trajectory: a committed, numbered copy of the figures
        # this PR ran (benchmarks/README.md "Perf trajectory").
        tdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "trajectory")
        os.makedirs(tdir, exist_ok=True)
        path = os.path.join(tdir, f"BENCH_{pr_id}.json")
        with open(path, "w") as f:
            json.dump({"pr": pr_id, "quick": quick, "results": results},
                      f, indent=1, default=str)
        print(f"trajectory -> {path}", file=sys.stderr)

    # Subprocess-backed figures report crashes as {"error": ...} instead of
    # raising (so one bad leg doesn't lose the others' results) — surface
    # them as a nonzero exit so the CI bench-smoke job fails loudly.
    def errors(node, path):
        if isinstance(node, dict):
            for key, val in node.items():
                if key == "error":
                    yield path, val
                yield from errors(val, f"{path}.{key}")

    failed = list(errors(results, ""))
    for path, msg in failed:
        print(f"BENCH ERROR at {path}: {str(msg)[:300]}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
