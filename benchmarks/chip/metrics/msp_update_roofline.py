"""Roofline share of the `msp_update` Pallas kernel: the least time the
chip needs for one neuron update per step of all neurons, over the kernel's
device time.  The kernel works in f32, for which no compute peak is
published, so the bound is the HBM bytes over the peak bandwidth."""

import json
from pathlib import Path

import kernel_cost

PEAKS = json.loads((Path(__file__).resolve().parents[1] / "peaks.json").read_text())


def read(reduced: dict, ctx: dict):
    busy = sum(e[2] for e in reduced["events"] if e[4] == "msp_update")
    if busy <= 0:
        return None
    peak = PEAKS[ctx["device_kind"]]
    work = kernel_cost.msp_update(ctx["neurons"])
    least = ctx["steps"] * work["hbm_bytes"] / peak["hbm_bytes_per_s"]
    return 100.0 * least / busy
