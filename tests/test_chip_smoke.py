"""chip_smoke.py: refuses anything but a TPU, and its phases hold at CPU
size (the reduced qwen2-0.5b config, Pallas kernels in interpret mode).

The full-width run happens only on the chip; these tests keep the script's
control flow and its checks from rotting between chip runs.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "REDUCED", True)
    # a CPU-size model needs a larger step to fall visibly within 5 steps
    monkeypatch.setattr(mod, "TRAIN", {"batch": 2, "seq": 32, "steps": 5,
                                       "lr": 1e-2})
    monkeypatch.setattr(mod, "SERVE", {"requests": 3, "prompt_lens": "4,20",
                                       "gen": 6, "slots": 3, "chunk": 4})
    return mod


def test_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_train_phase_at_cpu_size(smoke, capsys):
    smoke.phase_train(0)
    out = capsys.readouterr().out
    assert "train ok" in out and "smoke wall time (not a metric)" in out


def test_serve_phase_at_cpu_size(smoke, capsys):
    smoke.phase_serve(0)
    assert "serve ok: 3/3 requests finished with 6 tokens" in \
        capsys.readouterr().out


def test_failed_check_exits_nonzero(smoke, monkeypatch, capsys):
    """A phase whose check fails ends the run non-zero with no JSON line."""
    monkeypatch.setattr(smoke, "device_info", lambda: {
        "platform": "tpu", "kind": "stand-in", "count": 1})

    def broken(seed):
        smoke.check(False, "stand-in failure")

    monkeypatch.setattr(smoke, "phase_train", broken)
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "stand-in failure" in err
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
