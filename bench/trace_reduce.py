"""Reduce a profiler trace to what the per-layer metrics read.

:func:`load` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain events; :func:`reduce` computes from them:

``busy_s``
    the union of the intervals in which an operation ran on a device
    (line ``XLA Ops``), averaged over the devices traced;
``executables``
    device seconds per jitted function, from the ``XLA Modules`` line,
    named as the program names them (``jit_fused_step(3)`` ->
    ``fused_step``);
``top_ops``
    the operations that took the most device time;
``idle_gaps``
    the longest stretches with no operation on the device, each named
    by the innermost host span (``engine.*`` / ``bench.*``) open at its
    middle, or ``no span`` where none was.

Times come back in seconds on the trace's own clock; ``marker_s`` is
the start of the ``bench.marker`` host span, which the harness also
stamps on its own clock, so the two clocks can be aligned.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("engine.", "bench.")
MARKER = "bench.marker"
# an HLO op's trace name is its whole instruction; the breakdown keeps
# its head (name, result shape and layout)
NAME_CHARS = 160


def load(trace_dir: str) -> Dict:
    """Plain events from the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "spans": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, Dict[str, List]] = {}
    spans: List[List] = []
    layout: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        layout[plane.name] = {line.name: len(list(line.events))
                              for line in plane.lines}
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [[e.name, int(e.start_ns),
                                         int(e.duration_ns)]
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    return {"devices": devices, "spans": spans, "layout": layout}


def executable_name(module: str) -> str:
    """``jit_fused_step(12)`` -> ``fused_step``."""
    name = re.sub(r"\(\d+\)$", "", module.strip())
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _innermost(spans: List[List], t_ns: float) -> str:
    best: Optional[List] = None
    for name, s, d in spans:
        if s <= t_ns <= s + d and (best is None or d < best[2]):
            best = [name, s, d]
    return best[0] if best else "no span"


def reduce(events: Dict, window_ns: Optional[Tuple[int, int]] = None,
           top: int = 10) -> Dict:
    """Busy time, per-executable time, top ops and named idle gaps.
    ``window_ns`` clips device intervals to the traced window; by
    default the window is the span of all device operations."""
    devices = {p: lines for p, lines in events["devices"].items()
               if lines.get(OPS_LINE)}
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy, execs, ops, gaps = [], {}, {}, []
    spans = events["spans"]
    for lines in devices.values():
        iv = [(s, s + d) for _, s, d in lines[OPS_LINE]]
        lo = min(a for a, _ in iv) if window_ns is None else window_ns[0]
        hi = max(b for _, b in iv) if window_ns is None else window_ns[1]
        merged = [(max(a, lo), min(b, hi)) for a, b in _union(iv)
                  if b > lo and a < hi]
        busy.append(sum(b - a for a, b in merged) / 1e9)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _innermost(spans, (a + b) / 2)))
        for name, s, d in lines[OPS_LINE]:
            ops[name] = ops.get(name, 0) + d
        for name, s, d in lines.get(MODULES_LINE, []):
            key = executable_name(name)
            execs[key] = execs.get(key, 0) + d
    n = len(devices)
    gaps.sort(key=lambda g: -g[0])
    marker = [s for name, s, _ in spans if name == MARKER]
    return {
        "devices": n,
        "busy_s": sum(busy) / n,
        "executables": {k: v / 1e9 / n for k, v in execs.items()},
        "top_ops": [[k[:NAME_CHARS], v / 1e9 / n] for k, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, d / 1e9] for d, name in gaps[:top]],
        "marker_s": marker[0] / 1e9 if marker else None,
    }


def trim(events: Dict, start_ns: int, end_ns: int) -> Dict:
    """The events that overlap ``[start_ns, end_ns)`` (for a fixture)."""
    def keep(evs):
        return [e for e in evs if e[1] < end_ns and e[1] + e[2] > start_ns]
    return {"devices": {p: {ln: keep(evs) for ln, evs in lines.items()}
                        for p, lines in events["devices"].items()},
            "spans": keep(events["spans"])}
