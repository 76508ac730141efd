"""``BENCHMARK.json`` and every file it names: names, units, the metric
each per-layer metric moves, the share of four-chip cells, the files the
harness finds by name (traffic, limits, model kind, data generator,
metric readers), and ``bench/run.py`` refusing to run off a chip.
Nothing here loads the TPU library."""
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(spec, metric, cell):
    return cell in metric.get("workloads", [c["name"]
                                            for c in spec["workloads"]])


def test_top_level(spec):
    assert set(spec) == KEYS
    assert spec["command"][:2] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    rs = spec["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its 43,200 s
    assert 1200 + 24 * 180 + (2 + 14 * 24) * (rs + 60) <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_and_units(spec):
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    for w in spec["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in spec["configs"]:
        names += c["reduced"]
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        ns = [e["name"] for e in spec[group]]
        assert len(ns) == len(set(ns))
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_configs_and_cells(spec):
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))
    for c in spec["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert c["source"].startswith("https://")
    configs = {c["name"] for c in spec["configs"]}
    pairs = set()
    for w in spec["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        for sub, name in (("traffic", w["traffic"]),
                          ("limits", w["name"])):
            assert os.path.exists(os.path.join(ROOT, "bench", sub,
                                               f"{name}.json"))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert {c["config"] for c in spec["workloads"]} == configs
    for w in spec["workloads"]:
        # the model kind and the generator the harness finds by name
        cfg_file = next(c["file"] for c in spec["configs"]
                        if c["name"] == w["config"])
        with open(os.path.join(ROOT, cfg_file)) as f:
            cfg = json.load(f)
        with open(os.path.join(ROOT, "bench", "traffic",
                               f"{w['traffic']}.json")) as f:
            traffic = json.load(f)
        kind = importlib.import_module(f"bench.models.{cfg['model']['kind']}")
        for fn in ("program", "row_shape", "apply",
                   "train_flops_per_sample"):
            assert callable(getattr(kind, fn)), (w["name"], fn)
        gen = importlib.import_module(f"bench.data.{cfg['data']['generator']}")
        streamed = traffic["feeding"] == "population"
        assert callable(getattr(gen, "virtual" if streamed else "make"))
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_metrics(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and "\n" not in m["layer"]
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert reader.UNIT == m["unit"]
        for cell in m.get("workloads", [w["name"]
                                        for w in spec["workloads"]]):
            assert _reports(spec, e2e[m["moves"]], cell), (m, cell)
    for w in spec["workloads"]:
        mine = [m["name"] for m in spec["end_to_end"]
                if _reports(spec, m, w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(_reports(spec, m, w["name"]) for m in spec["per_layer"])


def test_run_off_a_chip_prints_no_result(spec):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = spec["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", cell, "--seed", "7", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
