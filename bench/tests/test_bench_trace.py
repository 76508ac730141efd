"""The trace reduction on a hand-built trace: busy time as the union of
operation intervals inside the window, device time per program, the top
operations, and idle gaps named by the innermost host annotation."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def line(name, events):
    return NS(name=name, events=events)


def trace(device_ops, modules, host):
    dev = NS(name="/device:TPU:0", lines=[line(tr.OPS_LINE, device_ops),
                                          line(tr.MODULES_LINE, modules)])
    hst = NS(name="/host:CPU", lines=[line("python", host)])
    return [NS(name="/host:metadata", lines=[]), hst, dev]


def planes():
    # window 0..100 ms; ops 10-30 and 25-40 overlap (busy 10..40), 60-70,
    # and one op before the window that must not count
    ops = [ev("%fusion.1 = f32[20]{0} fusion(f32[20]{0} %p)", 10, 20),
           ev("fusion.2", 25, 15), ev("all-reduce.3", 60, 10),
           ev("fusion.1", -20, 5)]
    mods = [ev("jit_round_fn(77)", 10, 30), ev("jit_fn(3)", 60, 10)]
    host = [ev(tr.WINDOW, 0, 100), ev("round", 5, 50), ev("eval", 45, 30),
            ev("other", 80, 10)]
    return trace(ops, mods, host)


def test_busy_programs_and_gaps():
    s = tr.reduce(planes(), labels=("round", "eval"))
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.040)          # 30 ms + 10 ms
    assert s.idle_share == pytest.approx(0.6)
    assert s.module_s == pytest.approx({"jit_round_fn": 0.03, "jit_fn": 0.01})
    assert tr.program_seconds(s, r"jit_fn") == pytest.approx(0.01)
    assert tr.program_seconds(s, r"jit_nothing") is None
    assert s.top_ops[0] == ("fusion.1", pytest.approx(0.02))
    # gaps: 70-100 (30 ms, mid 85: no label), 40-60 (20 ms, mid 50: eval
    # opened after round), 0-10 (10 ms, mid 5: round)
    assert [n for n, _ in s.idle_gaps] == ["outside annotations", "eval",
                                          "round"]
    assert [g for _, g in s.idle_gaps] == pytest.approx([0.03, 0.02, 0.01])


def test_two_devices_average():
    p = planes()
    dev2 = NS(name="/device:TPU:1",
              lines=[line(tr.OPS_LINE, [ev("fusion.9", 0, 100)])])
    s = tr.reduce(p + [dev2], labels=("round",))
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((0.040 + 0.100) / 2)


def test_no_device_or_window_raises():
    with pytest.raises(ValueError):
        tr.reduce([NS(name="/host:CPU", lines=[])])
    p = trace([ev("fusion.1", 0, 1)], [], [])
    with pytest.raises(ValueError):
        tr.reduce(p)
