"""Spans, device scopes and counters inside the round.

One ``FedGroupTrainer.round`` on a tiny pinned model records the span
tree ``round`` > {``select``, ``stage``, ``dispatch``, ``fold`` >
{``eval`` > ``sync``, ``sync``}}, every span with the round's ``t``; a
disabled tracer hands out ``NULL_SPAN`` on that path; with
``annotate=True`` a profiler capture holds the spans as ``repro.<kind>``
host events; the compiled round keeps its name ``jit_round_fn`` and
carries the stage scopes in its op metadata; and the solver step counters
match the live steps ``bench/flops.py`` counts for the same cohorts.
"""
import glob
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

from bench import flops as flops_lib  # noqa: E402
from repro.core.fedgroup import FedGroupTrainer  # noqa: E402
from repro.data.generators import mnist_like  # noqa: E402
from repro.fed.engine import FedConfig  # noqa: E402
from repro.obs import NULL_SPAN, Tracer  # noqa: E402

pytestmark = pytest.mark.obs

K, E, B = 4, 2, 5


@pytest.fixture(scope="module")
def data():
    return mnist_like(seed=0, n_clients=16, classes_per_client=2,
                      total_train=800, dim=16)


@pytest.fixture(scope="module")
def model():
    from repro.models.paper_models import mclr
    return mclr(16, 10)


def _trainer(model, data, **kw):
    cfg = FedConfig(n_rounds=4, clients_per_round=K, local_epochs=E,
                    batch_size=B, lr=0.05, n_groups=2, pretrain_scale=8,
                    seed=0, **kw)
    tr = FedGroupTrainer(model, data, cfg)
    tr.group_cold_start()           # every client assigned: no newcomers
    return tr


def _cohorts(tr):
    """Record each cohort the trainer draws."""
    drawn = []
    orig = tr._select

    def select():
        drawn.append(np.asarray(orig()))
        return drawn[-1]
    tr._select = select
    return drawn


def _inside(inner, outer):
    return (outer.start_ns <= inner.start_ns and
            inner.start_ns + inner.dur_ns <= outer.start_ns + outer.dur_ns)


def test_round_span_tree(model, data):
    tr = _trainer(model, data)
    tr.obs.tracer.enabled = True
    tr.round(5)
    tracer = tr.obs.tracer
    assert tracer.open_depth() == 0
    recs = tracer.records()
    assert {r.attrs.get("t") for r in recs} == {5}
    by = {}
    for r in recs:
        by.setdefault(r.kind, []).append(r)
    (rnd,) = by["round"]
    assert rnd.depth == 0
    for kind in ("select", "stage", "dispatch", "fold"):
        (r,) = by[kind]
        assert r.depth == 1 and _inside(r, rnd), kind
    (ev,) = by["eval"]
    (fold,) = by["fold"]
    assert _inside(ev, fold)
    # one read inside the eval (its counts), one of the round's scalars
    syncs = by["sync"]
    assert len(syncs) == 2
    assert sum(_inside(s, ev) for s in syncs) == 1
    assert all(_inside(s, fold) for s in syncs)


def test_disabled_tracer_hands_out_null_spans(model, data):
    tr = _trainer(model, data)
    tracer = tr.obs.tracer
    assert not tracer.enabled
    handed = []
    orig = tracer.span

    def span(kind, **attrs):
        handed.append((kind, orig(kind, **attrs)))
        return handed[-1][1]
    tracer.span = span
    tr.round(0)
    kinds = {k for k, _ in handed}
    assert {"round", "select", "stage", "fold", "eval", "sync"} <= kinds
    assert all(s is NULL_SPAN for _, s in handed)
    assert tracer.records() == []


def test_annotated_spans_reach_the_profiler(model, data, tmp_path):
    from jax.profiler import ProfileData
    tr = _trainer(model, data)
    tr.obs.tracer.enabled = True
    tr.obs.tracer.annotate = True
    tr.round(0)                     # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.round(1)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path[0]).planes
             if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events}
    assert {"repro.round", "repro.sync", "repro.eval",
            "repro.dispatch"} <= names
    # the Chrome export keeps the bare kinds
    assert "round" in {ev["name"] for ev in tr.obs.tracer.chrome_events()}


def test_round_program_keeps_its_name_and_carries_scopes(model, data):
    import jax.numpy as jnp
    tr = _trainer(model, data)
    idx = np.arange(K)
    x, y, n, keys = tr._stage_cohort(idx)
    jitted = tr._round_executor().__wrapped__
    text = jitted.lower(tr.group_params,
                        jnp.asarray(tr.membership[idx], jnp.int32),
                        x, y, n, keys).compile().as_text()
    assert text.startswith("HloModule jit_round_fn")
    for scope in ("solver", "aggregate", "mean_loss", "discrepancy"):
        assert f'op_name="jit(round_fn)/{scope}/' in text, scope


def _live_steps(sizes):
    """Live SGD steps of the cohorts' solves, as ``bench/flops.py`` counts
    them (its operations at one operation a sample, over B samples)."""
    return flops_lib.live_sgd_flops(np.concatenate(sizes), epochs=E,
                                    batch_size=B, per_sample=1) // B


@pytest.mark.parametrize("block_size", [1, 2])
def test_step_counters_match_the_live_step_count(model, data, block_size):
    tr = _trainer(model, data, block_size=block_size)
    drawn = _cohorts(tr)
    reg = tr.obs.registry
    tr.run(4)
    assert len(drawn) == 4
    sizes = [data.n_train[c] for c in drawn]
    max_steps = E * -(-data.x_train.shape[1] // B)
    assert reg.get("solver.steps_run") == 4 * K * max_steps
    assert reg.get("solver.steps_live") == _live_steps(sizes)
    assert 0 < reg.get("solver.steps_live") < reg.get("solver.steps_run")


def test_span_without_t_takes_its_parents():
    tr = Tracer(enabled=True)
    f = tr.wrap("dispatch", lambda: None, exec="round")
    with tr.span("round", t=3):
        f()
        with tr.span("sync", t=9):
            pass
    f()                             # outside any round: no t
    recs = {(r.kind, r.attrs.get("t")) for r in tr.records()}
    assert recs == {("round", 3), ("dispatch", 3), ("sync", 9),
                    ("dispatch", None)}
