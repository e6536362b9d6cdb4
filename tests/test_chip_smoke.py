"""chip_smoke.py and the rules it rests on, on CPU: the smoke refuses a CPU
run for the right reason, the compile cache can be placed from outside,
host-side processes pin the CPU, and the native library is keyed by content.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke
from dragonfly2_tpu.native import scorer as native_scorer
from dragonfly2_tpu.utils import jaxenv

REPO = Path(__file__).resolve().parents[1]


def _env(**extra: str) -> dict:
    # conftest's 8 virtual devices must not leak into children: the smoke's
    # served-scan leg would run the full-width GNN over a mesh of them
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    return {**env, **extra}


def test_chip_smoke_on_cpu_fails_only_on_platform(tmp_path):
    """At tiny sizes under JAX_PLATFORMS=cpu every phase passes and the run
    still exits non-zero BECAUSE the trainer process reports cpu — with no
    result line on stdout."""
    cache = tmp_path / "cache"
    out = subprocess.run(
        [
            sys.executable, str(REPO / "chip_smoke.py"),
            "--hosts", "64", "--downloads", "2048", "--probes", "1500",
            "--gnn-steps", "20", "--mlp-steps", "20", "--gnn-hidden", "32",
            "--stage-mib", "1",
        ],
        env=_env(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache)),
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    summary = json.loads(out.stderr.strip().splitlines()[-1])
    assert summary["ok"] is False and summary["claim"] is None
    assert summary["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert summary["phases"] == {
        "trainer": "ok", "artifacts": "ok", "scorer": "ok", "device": "ok",
        "platform": "FAILED",
    }, summary["detail"]
    assert summary["mesh"] == {"data": 1}
    platform = summary["detail"]["platform"]
    assert platform["trainer_reported"] == platform["device_child_reported"] == "cpu"
    assert any("trainer ran on 'cpu'" in p for p in platform["problems"])
    # off-chip the kernel only interprets, and the gate says so
    assert not any(r["compiled"] for r in summary["detail"]["device"]["pallas"].values())
    assert any("Pallas kernel not compiled" in p for p in platform["problems"])
    # the served scan: the first run built the program, the other two were served the kept one
    served = summary["detail"]["device"]["served_scan"]
    assert served["ok"] and served["traced"] == [1, 0, 0] and served["placement"]["gather_vjp"]["path"] == "derived"
    assert max(served["first_ms"][1:]) < served["first_ms"][0]
    # the scorer child was pinned to the host CPU; the cache went where the
    # environment said, not into the checkout
    assert summary["detail"]["scorer"]["platform"] == "cpu"
    assert summary["compile_cache"]["dir"] == str(cache)
    assert summary["detail"]["device"]["cache_dir"] == str(cache)


def test_result_line_has_exactly_the_contract_keys(monkeypatch, capsys):
    """On success stdout's one line is {"ok", "device": {"platform", "kind",
    "count"}} and nothing else; the detail is the stderr summary."""
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}
    trainer = {"ok": True, "device": tpu, "mesh": {"data": 1}, "gnn_artifact": "g"}
    child = {"ok": True, **tpu, "pallas": {"1024x16x256": {"ok": True, "compiled": True}}}
    monkeypatch.setattr(chip_smoke, "_trainer_phase", lambda *a: trainer)
    monkeypatch.setattr(chip_smoke, "_artifacts_phase", lambda t: {"ok": True, "missing": []})
    monkeypatch.setattr(chip_smoke, "_run_child", lambda name, *a, **k: child)
    assert chip_smoke.main([]) == 0
    out, err = capsys.readouterr()
    assert out.count("\n") == 1
    assert json.loads(out) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    summary = json.loads(err.strip().splitlines()[-1])
    assert summary["claim"] is None and set(summary["phases"].values()) == {"ok"}


def test_platform_phase_refuses_a_device_child_that_fell_back():
    """A trainer on the chip does not excuse a device child (staging, Pallas,
    {data: n}) that JAX quietly ran on the CPU, or a kernel that was only
    interpreted."""
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}
    cpu = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    compiled = {"1024x16x256": {"ok": True, "compiled": True}}
    interpreted = {"1024x16x256": {"ok": True, "compiled": False}}
    assert chip_smoke._platform_phase(tpu, {"ok": True, **tpu, "pallas": compiled})["ok"]
    assert not chip_smoke._platform_phase(tpu, {"ok": True, **cpu, "pallas": interpreted})["ok"]
    assert not chip_smoke._platform_phase(tpu, {"ok": True, **tpu, "pallas": interpreted})["ok"]
    assert not chip_smoke._platform_phase(tpu, {"ok": True, **tpu, "pallas": {}})["ok"]
    assert not chip_smoke._platform_phase(cpu, {"ok": True, **tpu, "pallas": compiled})["ok"]
    # a device child that died reports no platform at all
    assert not chip_smoke._platform_phase(tpu, {"ok": False, "error": "boom"})["ok"]
    assert not chip_smoke._platform_phase(
        tpu, {"ok": True, **tpu, "device_count": 4, "pallas": compiled})["ok"]


@pytest.fixture(scope="module")
def kernel_leg():
    """The device child's kernel leg as it runs off the chip: interpreted. On
    four of the virtual devices it also takes the per-shard case, for which
    the test, not the program, says these CPU devices get tables."""
    from dragonfly2_tpu.ops import neighbor_agg_pallas as pk
    from dragonfly2_tpu.parallel import mesh as meshlib

    four = jax.devices()[:4]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pk, "PLATFORM", "cpu")
        patch.setattr(meshlib, "mesh_for_run", lambda run=meshlib.mesh_for_run: run(four))
        return chip_smoke._pallas_check(False, n_dev=4)


def _kernel_leg_cases():
    """A case for each of the smoke's shapes, and one more for each with a hub
    as the row shard of each of four devices (the id names the shard's shape)."""
    for rows, width, hub in chip_smoke.KERNEL_SHAPES:
        yield pytest.param(rows, width, None, id=f"{rows}x{width}")
        if hub:
            yield pytest.param(rows, width, 4, id=f"{rows}x{width}_over_4")


@pytest.mark.parametrize("rows, width, shards", _kernel_leg_cases())
def test_kernel_leg_checks_the_steps_kernel_interpreted_on_cpu(kernel_leg, rows, width, shards):
    """The kernel the training step runs (`sum_by_destination` over a seeded
    table), interpreted, is within bfloat16 of `jnp.take`'s own VJP at each of
    the smoke's shapes; the second and third have a hub row, the third source
    blocks of 5.3 K slices, and both run once more as the row shard of each
    of four devices, a table a row shard holding the same blocks. A table
    holds as many blocks as its cotangent rows fill BLOCK_BYTES. A kernel
    summing rows into the wrong place fails it."""
    from dragonfly2_tpu.ops import neighbor_agg_pallas as pk

    assert len(kernel_leg) == len(list(_kernel_leg_cases()))
    blocks = -(-rows * 16 * width * 2 // pk.BLOCK_BYTES)  # K = 16 bfloat16 rows a node, in blocks of at most 32 MB
    if shards:
        result = kernel_leg[f"{rows * shards}x16x{width}/{shards}"]
        assert result["ok"] and result["compiled"] is False and result["forward_exact"]
        assert (result["shards"], result["blocks"]) == (shards, blocks) and 0 < result["max_err"] <= 2.0 ** -6
        assert result["live_windows"]["least"] <= result["live_windows"]["most"]
        return
    result = kernel_leg[f"{rows}x16x{width}"]
    assert result["ok"] and result["compiled"] is False
    assert result["blocks"] == blocks and 0 < result["max_err"] <= 2.0 ** -7


def test_compile_cache_honours_env_else_fixed_checkout_path(tmp_path, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    regex = "jax_hlo_source_file_canonicalization_regex"
    relative = getattr(jax.config, regex)
    monkeypatch.setenv(jaxenv.CACHE_ENV, str(tmp_path))
    try:
        assert jaxenv.enable_compile_cache() == tmp_path
        assert jax.config.jax_compilation_cache_dir == before  # no directory set in code
        # source files relative to the checkout, so that a key that holds them
        # (op_names_in_cache_key) is the same from a checkout in another place
        assert getattr(jax.config, regex) == re.escape(f"{REPO}/")
    finally:
        jax.config.update(regex, relative)
    # the step's first call is keyed by its op names too (a cached program
    # keeps the names it was compiled with); nothing else is
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
    with jaxenv.op_names_in_cache_key():
        assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
    monkeypatch.delenv(jaxenv.CACHE_ENV)
    assert jaxenv.compile_cache_dir() == REPO / ".jax_cache"
    # unset, the helper does configure the fixed path (fresh process: this
    # one's config must stay untouched for the rest of the suite)
    env = _env(JAX_PLATFORMS="cpu")
    env.pop(jaxenv.CACHE_ENV, None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from dragonfly2_tpu.utils import jaxenv; "
         "print(jaxenv.enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120, check=True,
    )
    assert out.stdout.split() == [str(REPO / ".jax_cache")] * 2


def test_scheduler_main_pins_host_cpu():
    """A host-side main() leaves jax_platforms == "cpu" even when nothing in
    the environment named a platform and jax was imported first."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, jax\n"
         "from dragonfly2_tpu.scheduler import server\n"
         "assert not jax.config.jax_platforms, jax.config.jax_platforms\n"
         "sys.argv = ['scheduler', '--help']\n"
         "try:\n    server.main()\nexcept SystemExit:\n    pass\n"
         "import os; print('PINNED', jax.config.jax_platforms, os.environ['JAX_PLATFORMS'])\n"],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-800:]
    assert out.stdout.strip().splitlines()[-1] == "PINNED cpu cpu"


def test_native_lib_name_follows_source_bytes():
    src = native_scorer._SRC.read_bytes()
    name = native_scorer.lib_file_name(src)
    assert name == native_scorer.lib_file_name(src)
    assert name != native_scorer.lib_file_name(src + b"\n")
    assert name.startswith("libdfscorer-") and name.endswith(".so")
