"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so the second can be checked on a small recorded
trace without a chip:

1. ``load_events`` reads the ``.xplane.pb`` a ``jax.profiler.trace``
   capture wrote and keeps, per device, the events of its "XLA Ops" line
   (name, start, duration, and the scope path that the compiled module's
   ``op_name`` metadata gives the op) and, from the host, the spans the
   harness opened around its own calls;
2. ``reduce_events`` turns those into device busy time (the union of op
   intervals), idle gaps with the host span that covered them, self time
   per paper stage (the ``jax.named_scope`` the program puts around each
   stage, found as a component of the op's scope path; ops under none of
   them count as ``other``), collective time and the ops that took most
   time.  Per-device figures are averaged over the devices.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence

STAGES = ("associate", "allocate", "schedule", "train", "eval")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "allreduce", "allgather",
               "reducescatter", "alltoall", "collectivepermute")
HOST_PREFIX = "bench/"
NAME_CHARS = 160            # of an op's HLO text kept for the breakdown


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope path (``op_name`` metadata), from the text
    of a compiled module."""
    return dict(re.findall(r'%([\w.\-]+) = [^\n]*?op_name="([^"]*)"',
                           hlo_text))


def instruction(event_name: str) -> str:
    """The HLO instruction an XLA-op event names: ``fusion.7`` of both
    ``fusion.7`` and ``%fusion.7 = f32[...] fusion(...)``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load_events(trace_dir: str, scopes: Optional[Dict[str, str]] = None
                ) -> Dict:
    """Normalised events of the newest capture under ``trace_dir``.  A
    TPU's XLA-op events carry the op's HLO text but not its scope path;
    ``scopes`` (from ``hlo_scopes`` of the program the window drove) gives
    it, and an op it does not name keeps its instruction name, which falls
    under ``other``."""
    scopes = scopes or {}
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[dict]] = {}
    host: List[dict] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:GPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name = instruction(ev.name)
                    ops.append({"name": ev.name[:NAME_CHARS],
                                "start": float(ev.start_ns),
                                "dur": float(ev.duration_ns),
                                "scope": scopes.get(name, name)})
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append({"name": ev.name,
                                     "start": float(ev.start_ns),
                                     "dur": float(ev.duration_ns)})
    return {"devices": devices, "host": host}


def stage_of(scope: str, stages: Sequence[str] = STAGES) -> str:
    """The innermost paper stage among the components of a scope path."""
    parts = scope.replace("(", "/").replace(")", "/").split("/")
    found = [p for p in parts if p in stages]
    return found[-1] if found else "other"


def is_collective(name: str, scope: str) -> bool:
    text = (name + " " + scope).lower()
    return any(c in text for c in COLLECTIVES)


def _union(intervals: Iterable[tuple]) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _self_times(ops: List[dict]) -> List[float]:
    """Duration of each op less the part covered by ops nested in it (a
    control-flow op that spans its body counts only its own time)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["start"],
                                                   -ops[i]["dur"]))
    own = [op["dur"] for op in ops]
    stack: List[int] = []
    for i in order:
        s = ops[i]["start"]
        while stack and ops[stack[-1]]["start"] + ops[stack[-1]]["dur"] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            end = min(s + ops[i]["dur"],
                      ops[parent]["start"] + ops[parent]["dur"])
            own[parent] -= max(0.0, end - s)
        stack.append(i)
    return [max(0.0, t) for t in own]


def reduce_events(events: Dict, window: Optional[tuple] = None,
                  top: int = 10) -> Dict:
    """Busy/idle, per-stage self time, collectives and the top ops.

    ``window`` (start_ns, end_ns) clips the ops to the traced window; by
    default it is the span of the harness's host spans, or else of the
    ops themselves.  All times are returned in seconds, per device
    averaged over the devices that ran ops."""
    devices = events["devices"]
    host = events.get("host", [])
    if not devices:
        return {"n_devices": 0}
    if window is None:
        if host:
            window = (min(h["start"] for h in host),
                      max(h["start"] + h["dur"] for h in host))
        else:
            starts = [o["start"] for ops in devices.values() for o in ops]
            ends = [o["start"] + o["dur"] for ops in devices.values()
                    for o in ops]
            window = (min(starts), max(ends))
    w0, w1 = window
    n_dev = len(devices)
    busy = 0.0
    stage = defaultdict(float)
    coll = 0.0
    per_op = defaultdict(float)
    gaps: List[tuple] = []
    for ops in devices.values():
        ops = [dict(o, start=max(o["start"], w0),
                    dur=min(o["start"] + o["dur"], w1) - max(o["start"], w0))
               for o in ops]
        ops = [o for o in ops if o["dur"] > 0]
        own = _self_times(ops)
        union = _union((o["start"], o["start"] + o["dur"]) for o in ops)
        busy += sum(e - s for s, e in union)
        edges = [w0] + [t for iv in union for t in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for o, t in zip(ops, own):
            st = stage_of(o["scope"])
            stage[st] += t
            per_op[f"{st}/{o['name']}"] += t
            if is_collective(o["name"], o["scope"]):
                coll += t
    ns = 1e-9 / n_dev
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_label(host, g), (g[1] - g[0]) * 1e-9] for g in gaps[:top]]
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "n_devices": n_dev,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * ns,
        "stage_s": {k: v * ns for k, v in stage.items()},
        "collective_s": coll * ns,
        "device_ops": [[k, v * ns] for k, v in ops_top],
        "idle_gaps": idle,
    }


def _host_label(host: List[dict], gap: tuple) -> str:
    """The harness span that covered most of an idle gap."""
    best, cover = "no host span", 0.0
    for h in host:
        c = min(gap[1], h["start"] + h["dur"]) - max(gap[0], h["start"])
        if c > cover:
            best, cover = h["name"], c
    return best
