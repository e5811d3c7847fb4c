"""``chip_smoke.py`` rehearsed on the CPU.

The serving body runs at the smoke preset with the kernels in interpret
mode: the same requests, prefix sharing, page accounting and kernel
comparison as on the chip, at a size the CPU serves in about a minute.
The script's entry point must refuse to run without a TPU.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.qwen2_5_32b import REDUCED

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_serving_at_smoke_preset(chip_smoke):
    r = chip_smoke.one_chip(REDUCED, seed=0)
    assert r["completed"] == chip_smoke.MAX_BATCH
    assert r["pages_allocated"] == r["pages_freed"] > 0
    assert r["prefix_hits"] == 1
    assert r["prefix_tokens_saved"] == chip_smoke.SHARED_PREFIX
    # the CPU interprets the kernel: no TPU custom call in the program
    assert r["decode_has_tpu_custom_call"] is False
    assert r["kernel_max_abs_err"] <= chip_smoke.KERNEL_TOL


def test_main_refuses_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_script_alone_fails(tmp_path):
    """Copied away from the repo, the script cannot import the system
    and exits non-zero without a result line."""
    shutil.copy(SCRIPT, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
