"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Works on the planes that ``jax.profiler.ProfileData`` reads: each plane
has a ``name`` and ``lines``, each line a ``name`` and ``events`` with
``name``, ``start_ns`` and ``duration_ns``. Device planes are named
``/device:TPU:<n>``; on them the line ``XLA Ops`` holds one event per
operation run and ``XLA Modules`` one per program run (its name starts
with the jitted function's, e.g. ``jit_round_fn``). The benchmark's own
host annotations (``jax.profiler.TraceAnnotation``) are events on the host
plane's lines; the one named ``WINDOW`` bounds the traced window.

``reduce`` returns a ``Summary``: per device the busy seconds (the union
of operation intervals inside the window) and the seconds per program;
the operations that took most time; and the longest idle gaps, each named
by the innermost of the given host annotations open at its middle.
"""
from __future__ import annotations

import dataclasses
import re

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MODULE_NAME = re.compile(r"^([^(]*)")
OP_NAME = re.compile(r"^%?([^ =]*)")        # "%fusion.3 = f32[..] ..." -> fusion.3


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                   # mean over devices
    n_devices: int
    module_s: dict                  # program name -> seconds, mean/device
    top_ops: list                   # [(name, seconds)] mean over devices
    idle_gaps: list                 # [(host annotation, seconds)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load(path: str):
    """The planes of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(path).planes


def _union(intervals, lo: float, hi: float):
    """Merged [start, end) intervals clipped to [lo, hi], sorted."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ev in line.events]


def reduce(planes, labels=(), top: int = 10) -> Summary:
    """``labels``: names of the host annotations that may name a gap."""
    planes = list(planes)
    keep = set(labels) | {WINDOW}
    host = []                       # (name, start, end) annotations
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                host.extend((n, s, s + d) for n, s, d in _events(ln)
                            if n in keep)
    if not devices:
        raise ValueError("the trace holds no device plane")
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    lo, hi = win[0]
    busy, mods, ops = [], {}, {}
    gaps = []
    for k, lines in enumerate(devices):
        op_ev = lines.get(OPS_LINE) or lines.get(MODULES_LINE, [])
        spans = _union([(s, s + d) for _, s, d in op_ev], lo, hi)
        busy.append(sum(e - s for s, e in spans))
        for n, s, d in op_ev:
            if lo <= s < hi:
                n = OP_NAME.match(n).group(1)
                ops[n] = ops.get(n, 0.0) + d
        for n, s, d in lines.get(MODULES_LINE, []):
            if lo <= s < hi:
                name = MODULE_NAME.match(n).group(1)
                mods[name] = mods.get(name, 0.0) + d
        if k == 0:
            edges = [lo] + [x for se in spans for x in se] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    nd = len(devices)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        inner = [(hs, n) for n, hs, he in host
                 if hs <= mid < he and n != WINDOW]
        named.append((max(inner)[1] if inner else "outside annotations",
                      (e - s) * 1e-9))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / nd * 1e-9, n_devices=nd,
                   module_s={n: v / nd * 1e-9 for n, v in mods.items()},
                   top_ops=[(n, v / nd * 1e-9) for n, v in top_ops],
                   idle_gaps=named)


def program_seconds(summary: Summary, pattern: str) -> float | None:
    """Device seconds (mean per device) of the programs whose name
    matches ``pattern`` in full; None when no such program ran."""
    rx = re.compile(pattern)
    hits = [v for n, v in summary.module_s.items() if rx.fullmatch(n)]
    return sum(hits) if hits else None
