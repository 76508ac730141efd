"""The program's own spans and device scopes in a profiler trace.

``trace_reduce`` reads what the benchmark itself puts in a trace: its
annotations and the names of the jitted programs. This module reads what
the program puts there, from the same planes (``trace_reduce.load``):

- host spans: with ``Telemetry(annotate=True)`` each ``repro.obs`` span is
  a host event named ``repro.<kind>`` (``round``, ``select``, ``stage``,
  ``dispatch``, ``eval``, ``sync``, ``fold``, ...). Only the events on the
  thread that holds the window annotation count: that thread calls
  ``round``.
- device scopes: the compiled round's stages run under ``jax.named_scope``
  (``solver``, ``aggregate``, ``mean_loss``, ``discrepancy``, ...), which
  the compiler keeps in each operation's ``op_name`` metadata
  (``jit(round_fn)/solver/...``). An operation's scope is the first
  component of that path that names one. The path is read from the
  operation's trace stats where the trace carries it, or else from the
  compiled module's HLO text (``op_names_from_hlo``), keyed by the
  instruction names the trace shows.

``reduce`` returns a ``Program``: host seconds per span kind (total and
self), device idle seconds split by the innermost program span open at
each instant, the longest idle gaps each named by the span that covers
most of it, and device seconds per scope as the union of its operations'
intervals (an operation nested in the solver's ``while`` loop counts
once). Device numbers are means over the devices.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

from bench.trace_reduce import (DEVICE_PLANE, MODULE_NAME, MODULES_LINE,
                                OP_NAME, OPS_LINE, WINDOW, _events, _union)

PREFIX = "repro."
SCOPES = ("solver", "quarantine", "aggregate", "mean_loss", "discrepancy",
          "assign", "stage", "grouped_eval")
OUTSIDE = "outside program spans"
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=.*?op_name="([^"]*)"')


@dataclasses.dataclass
class Program:
    window_s: float
    span_s: dict        # kind -> {"total": s, "self": s, "count": n}
    round_sync_s: float  # host seconds in sync spans inside round spans
    labels: dict        # caller's annotation -> {"total": s, "count": n}
    idle_by_span: dict  # innermost span (or OUTSIDE) -> idle seconds
    idle_gaps: list     # [(span covering most of the gap, seconds)]
    scope_s: dict       # scope -> device seconds


def op_names_from_hlo(text: str) -> dict:
    """{instruction name: op_name} from a compiled module's HLO text."""
    out = {}
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_of(path: str, scopes=SCOPES) -> str | None:
    """The first component of an op_name path that names a scope."""
    for part in path.split("/"):
        if part in scopes:
            return part
    return None


def _nest(events):
    """Host events of one thread (properly nested) -> per event its self
    seconds and whether a ``repro.round`` encloses it, in start order:
    [(name, start, end, self_ns, in_round)]."""
    out, stack = [], []           # stack: indices into out
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        in_round = any(out[i][0] == PREFIX + "round" for i in stack)
        if stack:
            out[stack[-1]][3] -= e - s
        out.append([name, s, e, e - s, in_round])
        stack.append(len(out) - 1)
    return out


def _innermost(events, lo: float, hi: float):
    """[(start, end, name)] segments covering [lo, hi], each named by the
    innermost of the (properly nested) events open there, or None."""
    segs, stack, cur = [], [], lo

    def cut(to, name):
        nonlocal cur
        if to > cur:
            segs.append((cur, to, name))
            cur = to

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, inner = stack.pop()
            cut(min(end, hi), inner)
        cut(min(max(s, lo), hi), stack[-1][1] if stack else None)
        stack.append((e, name))
    while stack:
        end, inner = stack.pop()
        cut(min(end, hi), inner)
    cut(hi, None)
    return segs


def _split(gaps, segs) -> list:
    """Each gap split over the named segments -> [[(name, ns)] per gap]."""
    out, j = [], 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        parts, k = {}, j
        while k < len(segs) and segs[k][0] < ge:
            a, b = max(gs, segs[k][0]), min(ge, segs[k][1])
            if b > a:
                parts[segs[k][2]] = parts.get(segs[k][2], 0.0) + b - a
            k += 1
        out.append(list(parts.items()))
    return out


def _op_path(ev, module: str | None, op_names: dict) -> str:
    for _, v in getattr(ev, "stats", ()) or ():
        if isinstance(v, str) and "/" in v:
            return v
    table = op_names.get(module) or {}
    return table.get(OP_NAME.match(ev.name).group(1), "")


def reduce(planes, labels=(), op_names=None, scopes=SCOPES,
           top: int = 10) -> Program:
    """``labels``: the caller's own annotations, which may also name a gap;
    ``op_names``: {module name: {instruction: op_name}} for traces whose
    operations carry no op_name stat."""
    op_names = op_names or {}
    keep = set(labels)
    thread, devices = None, []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append({ln.name: ln.events for ln in plane.lines})
        elif plane.name.startswith("/host") and thread is None:
            for ln in plane.lines:
                evs = _events(ln)
                win = [(s, s + d) for n, s, d in evs if n == WINDOW]
                if win:
                    thread = (win[0], evs)
    if not devices:
        raise ValueError("the trace holds no device plane")
    if thread is None:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    (lo, hi), evs = thread
    inside = [(n, s, s + d) for n, s, d in evs if lo <= s < hi]
    prog = [e for e in inside if e[0].startswith(PREFIX)]
    named = [e for e in inside if e[0].startswith(PREFIX) or e[0] in keep]

    span_s, round_sync = {}, 0.0
    for name, s, e, self_ns, in_round in _nest(prog):
        kind = name[len(PREFIX):]
        agg = span_s.setdefault(kind, {"total": 0.0, "self": 0.0,
                                       "count": 0})
        agg["total"] += (e - s) * 1e-9
        agg["self"] += self_ns * 1e-9
        agg["count"] += 1
        if kind == "sync" and in_round:
            round_sync += (e - s) * 1e-9
    lab = {}
    for name, s, e in inside:
        if name in keep:
            agg = lab.setdefault(name, {"total": 0.0, "count": 0})
            agg["total"] += (e - s) * 1e-9
            agg["count"] += 1

    prog_segs = _innermost(prog, lo, hi)
    named_segs = _innermost(named, lo, hi)
    idle, scope_ns, gaps_named = {}, {}, []
    for k, lines in enumerate(devices):
        ops = list(lines.get(OPS_LINE, ()))
        mods = sorted((float(m.start_ns), float(m.start_ns + m.duration_ns),
                       MODULE_NAME.match(m.name).group(1))
                      for m in lines.get(MODULES_LINE, ()))
        starts = [m[0] for m in mods]
        busy = _union([(float(o.start_ns), float(o.start_ns + o.duration_ns))
                       for o in ops], lo, hi)
        edges = [lo] + [x for se in busy for x in se] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for parts in _split(gaps, prog_segs):
            for name, ns in parts:
                key = OUTSIDE if name is None else name
                idle[key] = idle.get(key, 0.0) + ns
        if k == 0:
            for (gs, ge), parts in zip(gaps, _split(gaps, named_segs)):
                best = max(parts, key=lambda p: p[1])[0] if parts else None
                gaps_named.append((best or OUTSIDE, ge - gs))
        by_scope = {}
        for o in ops:
            s = float(o.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and s < mods[i][1] else None
            scope = scope_of(_op_path(o, module, op_names), scopes)
            if scope is not None:
                by_scope.setdefault(scope, []).append(
                    (s, s + float(o.duration_ns)))
        for scope, iv in by_scope.items():
            scope_ns[scope] = scope_ns.get(scope, 0.0) + sum(
                e - s for s, e in _union(iv, lo, hi))
    nd = len(devices)
    gaps_named.sort(key=lambda g: -g[1])
    return Program(
        window_s=(hi - lo) * 1e-9, span_s=span_s, round_sync_s=round_sync,
        labels=lab,
        idle_by_span={n: v / nd * 1e-9 for n, v in idle.items()},
        idle_gaps=[(n, v * 1e-9) for n, v in gaps_named[:top]],
        scope_s={n: v / nd * 1e-9 for n, v in scope_ns.items()})


def per_round_ms(program: Program | None, rounds: int, scopes) -> float | None:
    """Device milliseconds a round under the given scopes; None where the
    trace has no program reduction or none of the scopes ran."""
    if program is None or rounds <= 0:
        return None
    hits = [program.scope_s[s] for s in scopes if s in program.scope_s]
    return 1e3 * sum(hits) / rounds if hits else None
