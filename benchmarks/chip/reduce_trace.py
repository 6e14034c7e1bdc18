"""Reduction of a profiler trace of one chunk to what the per-layer metrics
read.

Each device operation in the trace is an instruction of the compiled chunk
program.  Its place in that program's HLO decides its layer:

* update    inside the branch of the step's `lax.cond` that runs the
            connectivity update: the branch whose instructions come from
            `connectivity_update` (by the HLO's stack frames);
* activity  elsewhere inside the scan's loop body: the activity step;
* other     outside the loop.

A Pallas kernel is a `tpu_custom_call` whose stack frames pass through one
of `src/repro/kernels/<kernel>.py`.

The reduced trace is a dict: `events`, one [name, start_s, duration_s,
layer, kernel] per device operation, starts relative to the window; `host`,
one [name, start_s, duration_s] per span the harness annotated; `busy_s`,
the union of the device operations' intervals; `window_s`, the host's
clock over the traced chunk.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

UPDATE_FUNCTION = "PlasticityEngine.connectivity_update"
KERNEL_DIR = "/repro/kernels/"
CONTAINERS = {"while", "conditional", "call"}
HOST_SPANS = ("dispatch chunk", "wait for chunk")

_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations|called_computations)="
    r"(\{[^}]*\}|%[\w.\-]+)")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
_EVENT = re.compile(r"%?([\w.\-]+)")


def _opcode(rest: str) -> str:
    """The opcode after an instruction's result type."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.index(" ")
    rest = rest[i:].lstrip()
    return rest[: rest.index("(")] if "(" in rest else rest


def parse_hlo(text: str) -> dict:
    """{instruction: (layer, kernel, opcode, label)} for an HLO module's
    text; the label is the last part of the instruction's op name and the
    innermost function of the simulator it comes from."""
    tables = {"FileNames": {}, "FunctionNames": {}, "FileLocations": {}, "StackFrames": {}}
    comps, instrs, entry = {}, {}, None
    table = comp = None
    for line in text.splitlines():
        if line in tables:
            table = line
            continue
        if table is not None:
            row = _TABLE_ROW.match(line)
            if row:
                tables[table][int(row.group(1))] = row.group(2)
                continue
            table = None
        head = _HEADER.match(line)
        if head:
            comp = head.group(1)
            comps[comp] = []
            if line.startswith("ENTRY"):
                entry = comp
            continue
        ins = _INSTR.match(line)
        if ins and comp is not None:
            name, rest = ins.groups()
            called = [c.strip().lstrip("%") for m in _CALLED.findall(rest)
                      for c in m.strip("{}").split(",") if c.strip()]
            frame = _FRAME_ID.search(rest)
            target = re.search(r'custom_call_target="([^"]+)"', rest)
            op_name = re.search(r'op_name="([^"]+)"', rest)
            instrs[name] = dict(comp=comp, opcode=_opcode(rest), called=called,
                                frame=int(frame.group(1)) if frame else None,
                                target=target.group(1) if target else None,
                                op=op_name.group(1).rsplit("/", 1)[-1] if op_name else "")
            comps[comp].append(name)

    def field(row: str, key: str) -> int:
        return int(re.search(key + r"=(\d+)", row).group(1))

    def stack(frame):
        """(file, function) of each frame from the innermost outwards."""
        out, seen = [], set()
        while frame and frame not in seen and frame in tables["StackFrames"]:
            seen.add(frame)
            row = tables["StackFrames"][frame]
            loc = tables["FileLocations"].get(field(row, "file_location_id"), "")
            if loc:
                file_ = tables["FileNames"].get(field(loc, "file_name_id"), "").strip('"')
                func = tables["FunctionNames"].get(field(loc, "function_name_id"), "").strip('"')
                out.append((file_, func))
            frame = field(row, "parent_frame_id") - 1  # printed one above its id
        return out

    def reach(roots) -> set:
        """Instructions of the computations reachable from `roots`."""
        seen, todo, out = set(), list(roots), set()
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            for i in comps[c]:
                out.add(i)
                todo.extend(instrs[i]["called"])
        return out

    frames = {i: stack(d["frame"]) for i, d in instrs.items()}
    from_update = {i for i, s in frames.items() if any(f == UPDATE_FUNCTION for _, f in s)}
    update = set()
    for i, d in instrs.items():
        if d["opcode"] == "conditional" and i not in update:
            for branch in d["called"]:
                inside = reach([branch])
                if inside & from_update:
                    update |= inside
    loop = set()
    for i in comps.get(entry, []):
        if instrs[i]["opcode"] == "while":
            loop |= reach(instrs[i]["called"])
    out = {}
    for i, d in instrs.items():
        layer = "update" if i in update else "activity" if i in loop else "other"
        kernel = None
        if d["target"] == "tpu_custom_call":
            for file_, _ in frames[i]:
                if KERNEL_DIR in file_:
                    kernel = os.path.splitext(os.path.basename(file_))[0]
                    break
        inner = [f for file_, f in frames[i] if "/repro/" in file_]
        label = d["op"] + (f" ({inner[0]})" if inner else "")
        out[i] = (layer, kernel, d["opcode"], label)
    return out


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for start, stop in sorted(intervals):
        if stop > end:
            total += stop - max(start, end)
            end = stop
    return total


def read_xplane(tracedir: str):
    """(device ops, host spans) of a trace directory, in ns: device ops as
    (plane, name, start, duration), host spans as (name, start, duration)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"), recursive=True)
    ops, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":  # named "%<instruction> = <HLO text>"
                    ops.extend((plane.name, _EVENT.match(e.name).group(1), e.start_ns,
                                e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return ops, host


def reduce_events(ops, host, hlo: dict, window_s: float) -> dict:
    """The reduced trace from raw device ops and host spans (ns)."""
    t0 = min([s for _, s, _ in host] + [s for _, _, s, _ in ops])
    events, labels, per_device = [], {}, defaultdict(list)
    for plane, name, start, dur in ops:
        layer, kernel, opcode, label = hlo.get(name, ("other", None, "", ""))
        if opcode in CONTAINERS:
            continue
        events.append([name, (start - t0) * 1e-9, dur * 1e-9, layer, kernel])
        labels[name] = label
        per_device[plane].append(((start - t0) * 1e-9, (start - t0 + dur) * 1e-9))
    busy = sum(_union(v) for v in per_device.values()) / max(len(per_device), 1)
    return dict(events=events, labels=labels,
                host=[[n, (s - t0) * 1e-9, d * 1e-9] for n, s, d in host],
                busy_s=busy, window_s=window_s)


def reduce(tracedir: str, hlo_text: str, window_s: float) -> dict:
    ops, host = read_xplane(tracedir)
    return reduce_events(ops, host, parse_hlo(hlo_text), window_s)


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps, each named by the host span it fell in."""
    per_op = defaultdict(float)
    for name, _, dur, layer, kernel in reduced["events"]:
        per_op[f"{name} {reduced['labels'].get(name, '')} [{kernel or layer}]"] += dur
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps, end = [], 0.0
    for _, start, dur, _, _ in sorted(reduced["events"], key=lambda e: e[1]):
        if start > end:
            gaps.append((end, start))
        end = max(end, start + dur)
    if reduced["window_s"] > end:
        gaps.append((end, reduced["window_s"]))

    def label(a, b):
        mid = (a + b) / 2
        names = [n for n, s, d in reduced["host"] if s <= mid <= s + d]
        return names[-1] if names else "no host span"

    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return dict(device_ops=[[k, v] for k, v in ops],
                idle_gaps=[[label(a, b), b - a] for a, b in gaps])
