"""``chip_smoke.py``'s contract, as far as a sandbox without a chip can
check it: no accelerator → non-zero exit at ``inventory`` and no result
line; the same legs at a toy size on the CPU (``--cpu-toy``, explicit) run
end to end through the Orchestrator with the parent off jax."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _run(args, tmp_path, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_no_accelerator_fails_at_inventory_and_prints_no_result(tmp_path):
    p = _run([], tmp_path, 240)
    assert p.returncode != 0
    assert "JAX found no TPU" in p.stderr
    assert '"ok"' not in p.stdout and "leg=train" not in p.stdout


def test_alone_in_a_directory_it_is_not_the_repo(tmp_path):
    (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=60,
    )
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.slow
def test_cpu_toy_runs_every_leg(tmp_path):
    p = _run(["--cpu-toy"], tmp_path, 900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "toy": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    for leg in ("inventory", "train-1chip", "serve-1chip",
                "train-4chip-fsdp", "train-4chip-ring"):
        assert any(l.startswith(f"leg={leg} ok ") for l in lines), leg
    summary = json.loads(
        (REPO / "chiprun_out" / "chip_smoke" / "summary.json").read_text()
    )
    assert summary["ok"] is True and list(summary)[-1] == "claim"
    assert summary["claim"] is None
