"""chip_smoke.py off the chip: its phases at a tiny size on the CPU, the
four-chip phase on four forced host devices, and its refusals — no verdict
line on a CPU backend, and none outside a checkout of the repo."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.fed.engine import FedConfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "chip_smoke.py")

_TINY = dict(n_clients=16, total_train=800, dim=16, hidden=8, n_classes=26,
             madc_groups=2, madc_alpha=6, edc_rows=6)
_TINY_FED = dict(seed=0, clients_per_round=8, local_epochs=1, n_groups=2,
                 pretrain_scale=3)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod          # dataclasses resolve through it
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop("chip_smoke", None)


@pytest.fixture(scope="module")
def tiny(smoke):
    s = smoke.Setting(fed=FedConfig(**_TINY_FED), **_TINY)
    return s, s.data(), s.model()


def _run(args, cwd, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


class TestSetting:
    def test_defaults_are_the_papers_femnist_mlp512(self, smoke):
        s = smoke.Setting()
        assert s.d_w == 415_258                    # paper Table 2
        assert (s.n_clients, s.total_train, s.dim, s.n_classes) == \
            (200, 18345, 784, 26)
        cfg = s.cfg()
        assert (cfg.clients_per_round, cfg.local_epochs, cfg.batch_size,
                cfg.n_groups, cfg.pretrain_scale, cfg.measure) == \
            (20, 20, 10, 3, 20, "edc")
        assert s.madc_groups * s.madc_alpha >= 128  # compiled-kernel size


class TestPhasesAtTinySize:
    @pytest.mark.parametrize("phase", ["per_round", "block", "madc"])
    def test_phase_passes(self, smoke, tiny, phase, capsys):
        facts = getattr(smoke, phase + "_phase")(*tiny)
        out = capsys.readouterr().out
        assert "round" in out or "madc" in out
        if phase == "madc":
            # the CPU backend interprets the kernels; the smoke's verdict
            # needs them compiled, which only a chip gives
            assert facts == {"compiled": False, "kernel_cold_start": False}

    def test_out_of_tolerance_fails_the_phase(self, smoke, tiny,
                                              monkeypatch):
        monkeypatch.setattr(smoke, "KERNEL_TOL", -1.0)
        with pytest.raises(smoke.SmokeFailure, match="error"):
            smoke.madc_phase(*tiny)


class TestMeshPhase:
    def test_four_forced_host_devices_match_one_device(self):
        code = (
            "import importlib.util, json, sys\n"
            f"spec = importlib.util.spec_from_file_location('chip_smoke', "
            f"{_SCRIPT!r})\n"
            "cs = importlib.util.module_from_spec(spec)\n"
            "sys.modules['chip_smoke'] = cs\n"
            "spec.loader.exec_module(cs)\n"
            "from repro.fed.engine import FedConfig\n"
            f"s = cs.Setting(fed=FedConfig(**{_TINY_FED!r}), **{_TINY!r})\n"
            "cs.mesh_phase(s, s.data(), s.model())\n"
            "import jax\n"
            "dry = [m for m in sys.modules if m.endswith('dryrun')]\n"
            "print(json.dumps({'devices': jax.device_count(), "
            "'dryrun_modules': dry}))\n")
        proc = _run(["-c", code], _REPO, {
            "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.path.join(_REPO, "src"),
            "XLA_FLAGS": os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "(2, 2) mesh vs 1 device" in proc.stdout
        # the dry-run launchers rewrite XLA_FLAGS on import: never loaded
        assert json.loads(proc.stdout.splitlines()[-1]) == \
            {"devices": 4, "dryrun_modules": []}


class TestRefusals:
    def test_cpu_backend_gets_no_verdict(self, tmp_path):
        proc = _run([_SCRIPT], tmp_path, {"JAX_PLATFORMS": "cpu"})
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "needs a TPU" in proc.stderr

    def test_script_alone_gets_no_verdict(self, tmp_path):
        alone = tmp_path / "chip_smoke.py"
        shutil.copy(_SCRIPT, alone)
        proc = _run([str(alone)], tmp_path, {"JAX_PLATFORMS": "cpu"},
                    drop=("PYTHONPATH",))
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "No module named 'repro'" in proc.stderr
