"""Start states: a deployment grown on the chip from its empty start, saved
per replica as `states/<config>/<start>.npz`.

The file holds each replica's neuron fields, its live synapses (slot,
source, target) in an edge table of `capacity` slots, its step and its
overflow counter; `meta` records how it was grown (`make_start.py`).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import reference

HERE = Path(__file__).resolve().parent


def path(config: str, start: str) -> Path:
    return HERE / "states" / config / f"{start}.npz"


def save(file: Path, chunks, meta: dict) -> None:
    live = [np.flatnonzero(c.valid) for c in chunks]
    file.parent.mkdir(parents=True, exist_ok=True)
    np.savez_compressed(
        file,
        **{f: np.stack([c.neurons[f] for c in chunks]) for f in reference.FIELDS},
        capacity=np.int64(chunks[0].valid.size),
        replica=np.concatenate([np.full(s.size, r, np.int32) for r, s in enumerate(live)]),
        slot=np.concatenate(live).astype(np.int32),
        src=np.concatenate([c.src[s] for c, s in zip(chunks, live)]).astype(np.int32),
        dst=np.concatenate([c.dst[s] for c, s in zip(chunks, live)]).astype(np.int32),
        step=np.array([c.step for c in chunks], np.int32),
        dropped=np.array([c.dropped for c in chunks], np.int32),
        meta=np.array(json.dumps(meta)),
    )


def load(file: Path):
    """The replicas' states as host chunks."""
    with np.load(file) as z:
        a = {k: z[k] for k in z.files}
    chunks = []
    for r in range(a["step"].size):
        mine = a["replica"] == r
        src = np.zeros(int(a["capacity"]), np.int32)
        dst = np.zeros_like(src)
        valid = np.zeros(src.size, bool)
        src[a["slot"][mine]] = a["src"][mine]
        dst[a["slot"][mine]] = a["dst"][mine]
        valid[a["slot"][mine]] = True
        chunks.append(reference.Chunk({f: a[f][r] for f in reference.FIELDS}, src, dst, valid,
                                      int(a["step"][r]), int(a["dropped"][r]), {}))
    return chunks
