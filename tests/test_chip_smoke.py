"""``chip_smoke.py`` refuses to run without a TPU: no CPU fallback."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    script = SCRIPT
    if where == "script_alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
