"""The program's spans and scopes in a hand-built trace: an idle gap split
across ``repro.*`` spans by time, a scope's device time as the union of
its operations (an operation nested in a loop counted once), op paths
from trace stats or from the module's HLO text, and each reader of them
with a value on such planes and None on planes without them."""
import importlib
import os
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace_reduce as tr  # noqa: E402
from bench import trace_spans as ts  # noqa: E402

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def planes(host, ops, mods=(), other_thread=()):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name=tr.OPS_LINE, events=list(ops)),
        NS(name=tr.MODULES_LINE, events=list(mods))])
    hst = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(tr.WINDOW, 0, 100)] + list(host)),
        NS(name="prefetch", events=list(other_thread))])
    return [hst, dev]


def round_spans():
    # busy 0-10 and 60-100; the gap 10-60 falls under stage (10-25), sync
    # (25-45), fold (45-50), the eval (50-55) and its read (55-60)
    host = [ev("round", 0, 100), ev("repro.round", 0, 100),
            ev("repro.select", 1, 1), ev("repro.stage", 2, 23),
            ev("repro.sync", 25, 20), ev("repro.fold", 45, 50),
            ev("eval", 50, 20), ev("repro.eval", 50, 20),
            ev("repro.sync", 55, 10)]
    ops = [ev("fusion.1", 0, 10), ev("fusion.2", 60, 40)]
    return host, ops


def test_gap_split_by_time_across_spans():
    host, ops = round_spans()
    p = ts.reduce(planes(host, ops, other_thread=[ev("repro.stage", 0, 90)]),
                  labels=("round", "eval"))
    assert p.window_s == pytest.approx(0.1)
    assert p.idle_by_span == pytest.approx(
        {"repro.stage": 0.015, "repro.sync": 0.025, "repro.fold": 0.005,
         "repro.eval": 0.005})
    # the gap is named by the span that covers most of it
    assert p.idle_gaps == [("repro.sync", pytest.approx(0.05))]
    assert p.span_s["round"] == pytest.approx(
        {"total": 0.1, "self": 0.1 - 0.001 - 0.023 - 0.020 - 0.050,
         "count": 1})
    assert p.span_s["fold"]["self"] == pytest.approx(0.030)
    assert p.span_s["sync"] == pytest.approx(
        {"total": 0.030, "self": 0.030, "count": 2})
    assert p.round_sync_s == pytest.approx(0.030)
    assert p.labels["round"] == pytest.approx({"total": 0.1, "count": 1})


def test_gap_outside_program_spans():
    p = ts.reduce(planes([ev("repro.round", 50, 50)],
                         [ev("fusion.1", 0, 10)]))
    assert p.idle_by_span == pytest.approx(
        {ts.OUTSIDE: 0.040, "repro.round": 0.050})


LOOP = "jit(round_fn)/solver/vmap(while)"


def test_scope_union_counts_a_nested_op_once():
    ops = [ev("while.5", 10, 30, tf_op=LOOP),
           ev("fusion.195", 15, 10, tf_op=LOOP + "/body/dot_general"),
           ev("fusion.9", 40, 10, tf_op="jit(round_fn)/aggregate/dot"),
           ev("fusion.7", 50, 5, tf_op="jit(round_fn)/mean_loss/exp"),
           ev("copy.3", 70, 5, tf_op="jit(gather_cohort)/gather")]
    p = ts.reduce(planes([], ops))
    assert p.scope_s == pytest.approx(
        {"solver": 0.030, "aggregate": 0.010, "mean_loss": 0.005})


HLO = """HloModule jit_round_fn, is_scheduled=true

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %while.5 = (s32[], f32[4]) while(%t), condition=%c, body=%b, metadata={op_name="jit(round_fn)/solver/vmap(while)" stack_frame_id=3}
  ROOT %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(round_fn)/aggregate/dot_general"}
}
"""


def test_op_paths_from_the_module_hlo():
    names = ts.op_names_from_hlo(HLO)
    assert names == {"while.5": "jit(round_fn)/solver/vmap(while)",
                     "fusion.9": "jit(round_fn)/aggregate/dot_general"}
    ops = [ev("while.5", 10, 30), ev("fusion.9", 40, 10),
           ev("fusion.9", 80, 5)]            # another module's fusion.9
    mods = [ev("jit_round_fn(7)", 10, 45), ev("jit_fn(3)", 80, 5)]
    p = ts.reduce(planes([], ops, mods), op_names={"jit_round_fn": names})
    assert p.scope_s == pytest.approx({"solver": 0.030, "aggregate": 0.010})


def test_no_window_or_device_raises():
    with pytest.raises(ValueError):
        ts.reduce([NS(name="/host:CPU", lines=[])])
    with pytest.raises(ValueError):
        ts.reduce([planes([], [])[1]])


def _facts(host, ops, mods=(), counters=None, rounds=2):
    p = planes(host, ops, mods)
    return NS(program=ts.reduce(p, labels=("round",)), counters=counters,
              rounds=rounds, trace=tr.reduce(p, labels=("round",)))


READINGS = {
    "host_round_ms": 1e3 * (0.1 - 0.030),
    "sync_wait_ms": 30.0,
    "local_solver_ms": 15.0,
    "aggregate_ms": 5.0,
    "round_extras_ms": 5.0,
    "stage_device_ms": 2.5,
    "live_step_share": 25.0,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_readers(name):
    reader = importlib.import_module(f"bench.metrics.{name}")
    host, _ = round_spans()
    ops = [ev("while.5", 10, 30, tf_op=LOOP),
           ev("fusion.9", 40, 10, tf_op="jit(round_fn)/aggregate/dot"),
           ev("fusion.7", 50, 5, tf_op="jit(round_fn)/mean_loss/exp"),
           ev("fusion.8", 55, 5, tf_op="jit(round_fn)/discrepancy/sqrt"),
           ev("copy.3", 70, 5, tf_op="jit(gather_cohort)/gather")]
    mods = [ev("jit_round_fn(7)", 10, 55), ev("jit_gather_cohort(2)", 70, 5)]
    counters = {"solver.steps_run": 800, "solver.steps_live": 200}
    facts = _facts(host, ops, mods, counters)
    assert reader.read(facts) == pytest.approx(READINGS[name])
    # a trace of a program without spans, scopes or counters, as the
    # parent commit's: nothing to read
    bare = [ev("fusion.1", 10, 30), ev("fusion.2", 40, 10)]
    facts = _facts([ev("round", 0, 100)], bare,
                   [ev("jit_round_fn(7)", 10, 40)])
    assert reader.read(facts) is None
    # facts from a harness that sets neither ``program`` nor ``counters``
    assert reader.read(NS(rounds=2, trace=facts.trace)) is None
