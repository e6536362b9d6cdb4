"""Tier-1 static-analysis gate: the tree must stay dflint-clean, and when
ruff/mypy are installed (they are optional — the bare image ships neither),
their configured subsets must pass too. Skips keep the suite no worse than
seed on a bare environment."""

from __future__ import annotations

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
# dflint enforces the whole tree, tests included; ruff's scope is narrower
# (tests are excluded in pyproject.toml).
DFLINT_TARGETS = [
    "dragonfly2_tpu", "tools", "tests", "bench.py", "__graft_entry__.py", "chip_smoke.py",
]
LINT_TARGETS = ["dragonfly2_tpu", "tools", "bench.py"]


def test_dflint_clean():
    p = subprocess.run(
        [sys.executable, str(REPO / "tools" / "dflint.py"), *DFLINT_TARGETS],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert p.returncode == 0, (
        "dflint found violations (fix them or suppress with a reason):\n"
        + p.stdout
        + p.stderr
    )


@pytest.mark.skipif(shutil.which("ruff") is None, reason="ruff not installed")
def test_ruff_clean():
    p = subprocess.run(
        ["ruff", "check", *LINT_TARGETS],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert p.returncode == 0, "ruff check failed:\n" + p.stdout + p.stderr


@pytest.mark.skipif(shutil.which("mypy") is None, reason="mypy not installed")
def test_mypy_clean():
    # scope pinned in pyproject.toml: rpc, utils, telemetry
    p = subprocess.run(
        [
            "mypy",
            "dragonfly2_tpu/rpc",
            "dragonfly2_tpu/utils",
            "dragonfly2_tpu/telemetry",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert p.returncode == 0, "mypy failed:\n" + p.stdout + p.stderr
