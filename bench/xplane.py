"""Reducing a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers take.

A device plane is one whose name starts with ``/device:TPU:``; its ops are
the events of its ``XLA Ops`` line. Busy time is the union of those events'
intervals inside the window, averaged over the devices; the idle gaps are
the stretches between them. Host annotations (``TraceAnnotation``) are the
events of the host planes, and name each gap by the innermost annotation
that covers its middle.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host: List[Tuple[str, float, float]],
                  window: Tuple[float, float]) -> Dict:
    """``device_ops``: per device, (op name, start s, duration s);
    ``host``: (annotation name, start s, duration s); ``window``: (start s,
    end s) on the same clock. -> the reduced trace."""
    w0, w1 = window
    busy_each, by_op, gaps = [], defaultdict(float), []
    counts: Dict[str, int] = defaultdict(int)
    for dev, ops in sorted(device_ops.items()):
        iv = []
        for name, s, d in ops:
            s0, s1 = max(s, w0), min(s + d, w1)
            if s1 <= s0:
                continue
            iv.append((s0, s1))
            by_op[name] += s1 - s0
            counts[name] += 1
        merged = _union(iv)
        busy_each.append(sum(e - s for s, e in merged))
        edge = w0
        for s, e in merged + [(w1, w1)]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    named = defaultdict(float)
    for s, e in gaps:
        named[_annotation_at(host, (s + e) / 2) or "no annotation"] += e - s
    n = max(len(busy_each), 1)
    return {
        "window_s": w1 - w0,
        "busy_s": sum(busy_each) / n,
        "devices": len(busy_each),
        "op_seconds": dict(by_op),
        "op_counts": dict(counts),
        "idle_by_host": dict(named),
    }


def _annotation_at(host, t: float) -> Optional[str]:
    best, best_len = None, None
    for name, s, d in host:
        if s <= t <= s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


def read_xplane(path: str, window_name: str = "",
                host_names: Optional[set] = None) -> Dict:
    """The reduced trace of ``path``. The window is the host annotation
    ``window_name``; without one it runs from the first to the last device
    op. ``host_names`` keeps only the host events of those names."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    device_ops: Dict[str, List] = {}
    host: List[Tuple[str, float, float]] = []
    lo, hi = None, None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                    ops.append((ev.name, s, d))
                    lo = s if lo is None else min(lo, s)
                    hi = s + d if hi is None else max(hi, s + d)
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if host_names is not None and ev.name not in host_names:
                        continue
                    host.append((ev.name, ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9))
    marks = [(s, s + d) for name, s, d in host if name == window_name]
    if marks:
        window = max(marks, key=lambda w: w[1] - w[0])
    elif lo is not None:
        window = (lo, hi)
    else:
        window = (0.0, 0.0)
    return reduce_events(device_ops, host, window)


def short_name(hlo: str) -> str:
    """An op's HLO text -> its instruction name, with the custom-call
    target or the loop's condition that says what it is."""
    name = hlo.split(" = ", 1)[0]
    for key in ('custom_call_target="', "condition=%", "calls=%"):
        if key in hlo:
            tail = hlo.split(key, 1)[1]
            tail = tail.split('"', 1)[0].split(",", 1)[0].split(" ", 1)[0]
            return f"{name} [{tail}]"
    return name


def breakdown(reduced: Dict, top: int = 10) -> Dict:
    """The ops that took most device time (nested ops count in their own
    entries too) and the longest idle time by host annotation."""
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    ops = [(short_name(k), v) for k, v in ops]
    gaps = sorted(reduced["idle_by_host"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}
