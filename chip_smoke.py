"""The quickest proof that the system still starts on the chip.

`python chip_smoke.py` (no arguments) drives the device path once, through
the entry points a user would call, at the widths the repo ships as default:

  trainer   `python -m dragonfly2_tpu.trainer.server` — the one process that
            owns the accelerator — is started as a child, fed synthetic
            telemetry over the real RPC surface (train_open / train_chunk /
            train_close), and trains the MLP (256, 256, 128) and the GNN
            (hidden 256, embed 128, 3 layers, batch 4096) on a 1,024-host
            K=16 graph. Only the step counts are cut. Checked: run status ok,
            both models in last_result, every loss finite, no swallowed
            training or native-export error, and a run manifest that says
            where the run was placed (graph rows and the pair batch over
            `data`, the state replicated). The child is stopped by PID and
            waited for before anything else opens the chip.
  artifacts params.msgpack + graph.npz + scorer.dfsc on disk.
  scorer    (child, pinned to the host CPU) the produced scorer.dfsc scores
            one 40-candidate round through NativeScorer in agreement with
            GNNScorer.
  device    (child, opens the chip) tpuvm/staging.py: a safetensors file from
            a seed is staged unsharded and under a NamedSharding over all
            local devices, pulled back and compared bit for bit. It also
            drives train_async three times on the mesh the program chooses
            ({data: n}: node rows and the pair batch are seen to span the
            devices): the first run builds the scan program, the second
            (another cluster, of 1,000 hosts: a count off the grid, placed
            at the first's rung of 1,024 rows) and the third (the first's
            again) must be served the kept one (`calls.traced` 0) and the
            third must return the first's losses bit for bit. And the kernel
            the training step runs, the gather's VJP (`sum_by_destination`),
            against `jnp.take`'s at three shapes.
  platform  the trainer process AND the device child must both report
            platform "tpu" with the same device kind and count, and Mosaic
            must have compiled the kernel at every shape, not the
            interpreter. A child that fell back to the CPU fails the smoke
            here.

This parent never imports jax: a parent that has touched JAX holds the chip
and a child that needs it then fails or hangs. The summary (mesh, each
phase's outcome and detail, the compile cache's entry counts) is one JSON line
on stderr, always. On success stdout carries exactly one line, the result
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}` with
the device as the trainer process's JAX reported it, and the exit code is 0.
On any failure stdout stays empty and the exit code is non-zero. The size
flags exist for the CPU test (tests/test_chip_smoke.py) and for the second
shape (`--hosts 16384 --gnn-hidden 512`).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
RESULT_PREFIX = "CHIP_SMOKE_CHILD "
# one wall-clock budget for the whole smoke (the contract allows 1200 s)
BUDGET_S = 1100.0
# the telemetry and the staged tensors are made from this seed
SEED = 0


def _log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def _cache_entries(cache_dir: Path) -> int:
    return sum(1 for p in cache_dir.rglob("*") if p.is_file()) if cache_dir.is_dir() else 0


def _spawn(args: list[str], log_path: Path) -> subprocess.Popen:
    """Start a child in its own process group, output to a log file."""
    # a parent that has touched JAX holds the chip its children need
    assert "jax" not in sys.modules, "chip_smoke.py's parent imported jax"
    with open(log_path, "wb") as log:
        return subprocess.Popen(
            [sys.executable, *args],
            cwd=HERE, stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
        )


def _stop(proc: subprocess.Popen) -> None:
    """Stop a child by its PID and wait for it: SIGTERM, then SIGKILL to the
    whole group — nothing this script started outlives it."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def _tail(path: Path, n: int = 25) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError:
        return ""


def _run_child(name: str, args: list[str], tmp: Path, *, timeout: float) -> dict:
    """Run one `chip_smoke.py --child ...` to completion; its result is the
    JSON after RESULT_PREFIX on its last matching line."""
    log_path = tmp / f"{name}.log"
    proc = _spawn([str(HERE / "chip_smoke.py"), *args], log_path)
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"{name} child exceeded {timeout:.0f}s"}
    finally:
        _stop(proc)
    for line in reversed(log_path.read_text(errors="replace").splitlines()):
        if line.startswith(RESULT_PREFIX):
            return json.loads(line[len(RESULT_PREFIX):])
    return {"ok": False, "error": f"{name} child rc={proc.returncode}:\n{_tail(log_path)}"}


# ---- phase: trainer (parent side: RPC only) --------------------------------


async def _drive_trainer(addr: str, args: argparse.Namespace, deadline: float) -> dict:
    from dragonfly2_tpu.rpc.trainer import RemoteTrainerClient
    from dragonfly2_tpu.scheduler.announcer import CHUNK_ROWS
    from dragonfly2_tpu.trainer.synthetic import synth_telemetry_records

    client = RemoteTrainerClient(addr)
    try:
        downloads, probes = synth_telemetry_records(
            args.downloads, args.probes, args.hosts, seed=SEED
        )
        token = await client.train_open("chip-smoke", 0)
        for kind, arr in (("downloads", downloads), ("probes", probes)):
            for start in range(0, len(arr), CHUNK_ROWS):
                await client.train_chunk(  # dflint: disable=DF025 already batched: CHUNK_ROWS rows per trip, the announcer's own upload shape
                    token, kind, arr[start : start + CHUNK_ROWS]
                )
        await client.train_close(token)
        while True:
            status = await client.status()
            if status["trains_started"] >= 1 and not status["training"]:
                break
            if time.monotonic() > deadline:
                return {"ok": False, "error": "training did not finish in time", "status": status}
            await asyncio.sleep(1.0)
        history = await client.train_history(limit=1)
    finally:
        await client.close()
    return {"status": status, "run": (history["runs"] or [None])[0]}


def _check_trainer(out: dict) -> dict:
    """Everything the trainer's own report must say for the phase to pass."""
    if "error" in out:
        return out
    status, run = out["status"], out["run"]
    device = {k: status.get(k) for k in ("platform", "device_kind", "device_count")}
    if run is None:
        return {"ok": False, "error": "trainer kept no run manifest", "device": device}
    result = status["last_result"] or {}
    problems: list[str] = []
    if run["status"] != "ok":
        problems.append(f"run status {run['status']!r}, error {run['error']!r}")
    losses: dict[str, list] = {}
    for m in ("mlp", "gnn"):
        info = run["models"].get(m)
        if m not in result or info is None:
            problems.append(f"no {m} in last_result")
            continue
        values = [v for _, v in info["curve"]] + [info["final_loss"]]
        losses[m] = [info["steps"], info["final_loss"]]
        if not all(isinstance(v, float) and math.isfinite(v) for v in values):
            problems.append(f"{m}: non-finite or missing loss")
        if info["native_export_error"]:
            problems.append(f"native export failed: {info['native_export_error']}")
    placement = (run["models"].get("gnn") or {}).get("placement")
    if not placement:
        problems.append("run manifest carries no mesh/placement")
    return {
        "ok": not problems, "problems": problems, "device": device,
        "mesh": placement and placement["mesh"], "placement": placement, "losses": losses,
        "gnn_artifact": (result.get("gnn") or {}).get("artifact"),
        "mlp_artifact": (result.get("mlp") or {}).get("artifact"),
        "wall_s": run["wall_s"],
        "device_peak_bytes": run["device_peak_bytes"],
    }


def _trainer_phase(args: argparse.Namespace, tmp: Path, deadline: float) -> dict:
    log_path = tmp / "trainer.log"
    server_args = [
        "-m", "dragonfly2_tpu.trainer.server", "--port", "0",
        "--model-dir", str(tmp / "models"),
        "--gnn-steps", str(args.gnn_steps), "--mlp-steps", str(args.mlp_steps),
    ]
    if args.gnn_hidden is not None:
        server_args += ["--gnn-hidden", str(args.gnn_hidden)]
    proc = _spawn(server_args, log_path)
    try:
        addr = None
        while addr is None:
            for line in log_path.read_text(errors="replace").splitlines():
                if line.startswith("TRAINER_READY "):
                    addr = line.split()[1]
            if addr is None:
                if proc.poll() is not None:
                    return {"ok": False, "error": f"trainer exited rc={proc.returncode}:\n{_tail(log_path)}"}
                if time.monotonic() > deadline:
                    return {"ok": False, "error": f"trainer not ready in time:\n{_tail(log_path)}"}
                time.sleep(0.5)
        _log(f"trainer pid {proc.pid} ready at {addr}")
        out = _check_trainer(asyncio.run(_drive_trainer(addr, args, deadline)))
        if not out["ok"]:
            out["log_tail"] = _tail(log_path)
        return out
    finally:
        # by PID, and waited for: the chip is free before the next phase
        _stop(proc)


def _artifacts_phase(trainer: dict) -> dict:
    missing = []
    for key, names in (
        ("mlp_artifact", ("params.msgpack",)),
        ("gnn_artifact", ("params.msgpack", "graph.npz", "scorer.dfsc")),
    ):
        d = trainer.get(key)
        missing += [f"{key}/{n}" for n in names if not d or not (Path(d) / n).is_file()]
    return {"ok": not missing, "missing": missing}


def _platform_phase(trainer_device: dict, device_child: dict) -> dict:
    """No phase may have run on a CPU that JAX fell back to: the trainer and
    the device child both say "tpu", name the same chips, and Mosaic (not the
    interpreter) compiled every Pallas shape."""
    child_device = {k: device_child.get(k) for k in ("platform", "device_kind", "device_count")}
    pallas = device_child.get("pallas") or {}
    problems = []
    if trainer_device.get("platform") != "tpu":
        problems.append(f"trainer ran on {trainer_device.get('platform')!r}")
    if child_device["platform"] != "tpu":
        problems.append(f"device child ran on {child_device['platform']!r}")
    if child_device != trainer_device:
        problems.append(f"device child saw {child_device}, trainer saw {trainer_device}")
    interpreted = [shape for shape, r in pallas.items() if not r.get("compiled")]
    if interpreted or not pallas:
        problems.append(f"Pallas kernel not compiled at {interpreted or 'any shape'}")
    return {
        "ok": not problems, "problems": problems,
        "trainer_reported": trainer_device.get("platform"),
        "device_child_reported": child_device["platform"],
    }


# ---- children (these import jax) -------------------------------------------


def _child_scorer(artifact: str) -> dict:
    """NativeScorer vs GNNScorer on one 40-candidate round, host CPU."""
    from dragonfly2_tpu.utils import jaxenv

    jaxenv.pin_host_cpu()
    import numpy as np

    from dragonfly2_tpu.models.features import FEATURE_DIM
    from dragonfly2_tpu.models.scorer import GNNScorer
    from dragonfly2_tpu.trainer import artifacts

    model, params = artifacts.load_gnn(artifact)
    graph, _hosts = artifacts.load_graph(artifact)
    jax_scorer = GNNScorer(model, params)
    jax_scorer.refresh(graph)
    native = artifacts.load_native(artifact)  # builds with g++; missing = failure
    rng = np.random.default_rng(SEED)
    n = jax_scorer.num_nodes
    child = np.full(40, rng.integers(0, n), np.int32)
    parent = rng.integers(0, n, size=40).astype(np.int32)
    feats = rng.random((40, FEATURE_DIM)).astype(np.float32)
    a = native.score(feats, child=child, parent=parent)
    b = jax_scorer.score(feats, child=child, parent=parent)
    err = float(np.max(np.abs(a - b)))
    # bf16 JAX head vs f32 C++ head (the tolerance tests/test_native.py pins)
    ok = bool(a.shape == (40,) and np.all(np.isfinite(a)) and np.all(np.isfinite(b)) and err <= 3e-2)
    report = jaxenv.device_report()
    # this child scores on the host: it must never have opened the chip
    ok = ok and report["platform"] == "cpu"
    return {"ok": ok, "max_abs_diff": err, "nodes": n, **report}


def _child_device(tmp: str, stage_mib: int) -> dict:
    """Opens the accelerator: staging round trip, the kernel, and the served
    scan's runs."""
    from dragonfly2_tpu.utils import jaxenv

    cache = jaxenv.enable_compile_cache()
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.tpuvm import safetensors as stlib
    from dragonfly2_tpu.tpuvm.staging import stage_tensors

    out: dict = {**jaxenv.device_report(), "cache_dir": str(cache)}
    n_dev = out["device_count"]
    rng = np.random.default_rng(SEED)
    rows = max(n_dev * 8, stage_mib * (1 << 20) // (4096 * 2))
    rows -= rows % (n_dev * 8)
    # finite bf16 bit patterns (exponent never all-ones): bits must survive
    # H2D + D2H exactly
    tensors = {
        "w_bf16": rng.integers(0, 1 << 16, (rows, 4096), dtype=np.uint16) & np.uint16(0xBFFF),
        "bias_f32": rng.standard_normal(4096).astype(np.float32),
        "table_f32": rng.standard_normal((n_dev * 16, 24)).astype(np.float32),
        "ids_i32": rng.integers(-(1 << 31), (1 << 31) - 1, (n_dev * 4, 7)).astype(np.int32),
        "flags_u8": rng.integers(0, 256, 3, dtype=np.uint8),
    }
    path = stlib.write_safetensors(Path(tmp) / "smoke.safetensors", tensors, bf16_names=["w_bf16"])
    mesh = Mesh(np.asarray(jax.local_devices()), ("x",))
    row_sharded = NamedSharding(mesh, P("x"))

    def sharding_for(name: str):
        return row_sharded if tensors[name].shape[0] % n_dev == 0 else NamedSharding(mesh, P())

    mismatched = []
    for label, shardings in (("unsharded", None), ("sharded", sharding_for)):
        staged = stage_tensors(path, shardings=shardings)
        for name, want in tensors.items():
            got = np.asarray(staged[name])  # dflint: disable=DF033 one D2H pull per staged tensor is the check itself
            if name == "w_bf16":
                got = got.view(np.uint16)
            if got.shape != want.shape or got.tobytes() != want.tobytes():
                mismatched.append(f"{label}:{name}")
        if shardings is not None:
            out["sharded_w_devices"] = len({s.device.id for s in staged["w_bf16"].addressable_shards})
            if out["sharded_w_devices"] != n_dev:
                mismatched.append("sharded:w_bf16 does not span every device")
        del staged
    out["staged_bytes"] = int(sum(t.nbytes for t in tensors.values()))
    out["mismatched"] = mismatched
    out["pallas"] = _pallas_check(out["platform"] == "tpu", n_dev)
    out["served_scan"] = _served_scan_runs(n_dev, out["platform"] == "tpu")
    ok = not mismatched and all(r["ok"] for r in out["pallas"].values()) and out["served_scan"]["ok"]
    return {"ok": ok, **out}


# (rows, width, hub): K = 16 and bfloat16, as the step's. The first is one
# source block; the second two, with a row a quarter of all slots point at
# one; the third three of 5.3 K slices: blocks whose range of the cotangent
# straddles a slice's edge. On several devices the shapes with a hub are each
# a row shard's, so that a shard's table holds the blocks one device's does
KERNEL_SHAPES = ((1024, 256, False), (4096, 512, True), (4608, 512, True))


def _pallas_check(compiled: bool, n_dev: int = 1) -> dict:
    """`sum_by_destination`, the kernel the training step runs (the gather's
    VJP), over `edges_by_destination` of a seeded table, against `jnp.take`'s
    own VJP in float32, at KERNEL_SHAPES; on several devices also each shape
    with a hub as a row shard's, through `neighbor_gather` on the program's
    own mesh, a table per row shard (`<rows>x16x<width>/<devices>`, the rows
    of all shards). On the chip Mosaic compiles the kernel;
    anywhere else only an interpreter exists (Pallas's HLO interpreter, which
    any truthy value but the TPU interpreter's parameters selects: plain XLA
    ops, so it also runs under a mesh's `shard_map`)."""
    import contextlib
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from dragonfly2_tpu.ops import neighbor_agg_pallas as pk
    from dragonfly2_tpu.ops.neighbor_agg import neighbor_gather
    from dragonfly2_tpu.parallel import mesh as meshlib

    def take_vjp(nbr, g):
        _, vjp = jax.vjp(lambda h: jnp.take(h, nbr, axis=0), jnp.zeros((nbr.shape[0], g.shape[-1]), jnp.float32))
        return vjp(g.astype(jnp.float32))[0]

    def relative_err(got, want):
        # float32 sums rounded once to bfloat16 are within 2^-8 of the largest
        # (a chip's; the chips' sums add up in bfloat16); a row summed into the
        # wrong place is O(1)
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))

    def seeded(n, width, hub):
        rng = np.random.default_rng(n)
        nbr = rng.integers(0, n, (n, 16)).astype(np.int32)
        if hub:
            nbr[rng.random(nbr.shape) < 0.25] = 3
        return rng, nbr, jnp.asarray(rng.standard_normal((n, 16, width)), jnp.bfloat16)

    interpreted = contextlib.nullcontext if compiled else partial(pltpu.force_tpu_interpret_mode, True)
    out = {}
    for n, width, hub in KERNEL_SHAPES:
        _, nbr, g = seeded(n, width, hub)
        table = pk.edges_by_destination(nbr, width, g.dtype)
        with interpreted():
            got = pk.sum_by_destination(jax.tree.map(jnp.asarray, table), g)
        err = relative_err(got, take_vjp(nbr, g))
        out[f"{n}x16x{width}"] = {
            "ok": err <= 2.0 ** -7, "compiled": compiled,
            "blocks": int(table.perm.shape[0]), "max_err": err,
        }
    for rows_a_shard, width, hub in KERNEL_SHAPES if n_dev > 1 else ():
        if not hub:
            continue
        # rows over `data`: what a four-chip host's step runs
        n = rows_a_shard * n_dev
        rng, nbr, g = seeded(n, width, hub)
        mesh, _ = meshlib.mesh_for_run()
        tables, reason = pk.gather_vjp_tables(nbr, width, g.dtype, mesh)
        key = f"{n}x16x{width}/{n_dev}"
        if tables is None:
            out[key] = {"ok": False, "compiled": compiled, "reason": reason}
            continue
        rows = meshlib.batch_sharding(mesh)
        h = jnp.asarray(rng.standard_normal((n, width)), jnp.bfloat16)
        states, slots, tables, cotangent = jax.device_put(
            (h, jnp.asarray(nbr), tables, g), (rows, rows, jax.tree.map(lambda _: rows, tables), rows))
        with interpreted():
            gathered, vjp = jax.vjp(lambda x: neighbor_gather(x, slots, tables), states)
            got = vjp(cotangent)[0]
        report = pk.gather_vjp_report(tables, nbr.shape, width, g.dtype, mesh)
        err = relative_err(got, take_vjp(nbr, g))
        exact = bool(jnp.all(gathered == jnp.take(h, nbr, axis=0)))
        out[key] = {
            "ok": exact and err <= 2.0 ** -6 and report["shards"] == n_dev, "compiled": compiled,
            "shards": report["shards"], "blocks": report["blocks"], "live_windows": report["live_windows"],
            "forward_exact": exact, "max_err": err,
        }
    return out


def _served_scan_runs(n_dev: int, on_tpu: bool) -> dict:
    """train_async on the mesh the program decides for itself (no mesh given:
    `parallel.mesh.mesh_for_run`, `{data: n}`), three runs of one
    configuration and shapes. Node rows and the pair batch must span the
    devices, a 1/n share each, and on TPU chips the gather's VJP must be the
    kernel's, over a sorted table a row shard. The first run builds the scan
    program; the second, on another cluster whose 1,000 hosts are no whole
    tiles and are placed at the first's rung (`placed_rows`: 1,024 rows, the
    sorted kernel all the same), and the third, on the first's
    again, must be served the kept one (`calls.traced` 0), and the third's
    losses must be the first's bit for bit: the kept executable holds nothing
    of the run that built it (the sorted table is an argument like the graph)."""
    import numpy as np

    from dragonfly2_tpu.trainer import synthetic, train_gnn
    from dragonfly2_tpu.trainer.metrics import TrainRunTelemetry

    cfg = train_gnn.GNNTrainConfig()
    sizes = dict(num_neighbors=16, num_pairs=65536)
    first = synthetic.make_cluster(num_nodes=1024, **sizes, seed=SEED)
    other = synthetic.make_cluster(num_nodes=1000, **sizes, seed=SEED + 1)
    losses, placements, calls = [], [], []
    for cluster, steps in ((first, 20), (other, 10), (first, 10)):
        tel = TrainRunTelemetry("gnn", batch_size=cfg.batch_size)
        _state, run_losses = asyncio.run(train_gnn.train_async(
            cfg, cluster.graph, cluster.pairs, steps=steps, telemetry=tel,
        ))
        losses.append(run_losses)
        placements.append(tel.placement)
        calls.append(tel.summary()["calls"])
    p, off_grid = placements[0], placements[1]
    graph, rows, vjp = p["graph"], p["batch_rows_per_device"], p["gather_vjp"]
    traced = [c["traced"] for c in calls]
    decided = {"rule": "one_device" if n_dev == 1 else "rows_over_data", "devices": n_dev}
    ok = (
        all(np.isfinite(run_losses).all() for run_losses in losses)
        and p["decision"] == {**decided, "hosts": 1024, "rows": 1024, "pad_pct": 0.0}
        and off_grid["decision"] == {**decided, "hosts": 1000, "rows": 1024, "pad_pct": 2.4}
        and p["mesh"] == {"data": n_dev}
        and len(graph["per_device_bytes"]) == n_dev
        and all(b * n_dev == graph["bytes"] for b in graph["per_device_bytes"])
        and rows * n_dev == cfg.batch_size
        and (not on_tpu or (vjp["path"] == "sorted_kernel" and vjp["shards"] == n_dev))
        and all(placement["gather_vjp"]["path"] == vjp["path"] for placement in placements)
        and traced == [1, 0, 0]
        and losses[2] == losses[0][:10] != losses[1]
    )
    return {
        "ok": bool(ok), "placement": p, "steps": len(losses[0]), "final_loss": losses[0][-1], "traced": traced,
        "off_grid": {"decision": off_grid["decision"], "gather_vjp.path": off_grid["gather_vjp"]["path"]},
        "first_ms": [c["first_ms"] for c in calls], "period_ms_p50": calls[0]["period_ms_p50"],
    }


def _child_main(argv: list[str]) -> int:
    kind, rest = argv[0], argv[1:]
    try:
        if kind == "scorer":
            out = _child_scorer(rest[0])
        elif kind == "device":
            out = _child_device(rest[0], int(rest[1]))
        else:
            raise SystemExit(f"unknown child {kind!r}")
    except Exception as e:  # the parent reports it; the traceback is in the child's log
        import traceback

        traceback.print_exc()
        out = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
    print(RESULT_PREFIX + json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


# ---- parent ----------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--child"]:
        return _child_main(argv[1:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--hosts", type=int, default=1024)
    ap.add_argument("--downloads", type=int, default=65536)
    ap.add_argument("--probes", type=int, default=40000)
    ap.add_argument("--gnn-steps", type=int, default=30)
    ap.add_argument("--mlp-steps", type=int, default=200)
    ap.add_argument("--gnn-hidden", type=int, default=None,
                    help="override the GNN width (default: TrainerConfig's 256)")
    ap.add_argument("--stage-mib", type=int, default=64)
    args = ap.parse_args(argv)

    try:
        from dragonfly2_tpu.utils import jaxenv
    except ImportError as e:
        _log(f"the repository is not beside this script: {e}")
        return 1
    t0 = time.monotonic()
    deadline = t0 + BUDGET_S
    cache_dir = jaxenv.compile_cache_dir()
    cache = {"dir": str(cache_dir), "entries_before": _cache_entries(cache_dir)}
    _log(f"compile cache {cache_dir}: {cache['entries_before']} entries before")

    phases: dict[str, dict] = {}
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as td:
        tmp = Path(td)
        phases["trainer"] = trainer = _trainer_phase(args, tmp, deadline)
        _log(f"trainer: {'ok' if trainer['ok'] else trainer}")
        phases["artifacts"] = _artifacts_phase(trainer)
        if phases["artifacts"]["ok"]:
            phases["scorer"] = _run_child(
                "scorer", ["--child", "scorer", trainer["gnn_artifact"]], tmp,
                timeout=max(30.0, min(300.0, deadline - time.monotonic())),
            )
        else:
            phases["scorer"] = {"ok": False, "error": "no artifact to score"}
        _log(f"scorer: {phases['scorer']}")
        phases["device"] = _run_child(
            "device", ["--child", "device", str(tmp), str(args.stage_mib)], tmp,
            timeout=max(30.0, min(400.0, deadline - time.monotonic())),
        )
        _log(f"device: {phases['device']}")

    device = trainer.get("device") or {}
    phases["platform"] = _platform_phase(device, phases["device"])
    cache["entries_after"] = _cache_entries(cache_dir)
    _log(f"compile cache {cache_dir}: {cache['entries_after']} entries after")
    ok = all(p["ok"] for p in phases.values())
    # the result line: exactly these keys, the device as the trainer's JAX saw it
    result = {
        "ok": ok,
        "device": {
            "platform": device.get("platform"),
            "kind": device.get("device_kind"),
            "count": device.get("device_count"),
        },
    }
    summary = {
        **result,
        "mesh": trainer.get("mesh"),
        "phases": {name: ("ok" if p["ok"] else "FAILED") for name, p in phases.items()},
        "detail": phases,
        "compile_cache": cache,
        "wall_s": round(time.monotonic() - t0, 1),
        "claim": None,
    }
    if not ok:
        _log(f"FAILED phases: {[name for name, p in phases.items() if not p['ok']]}")
    print(json.dumps(summary), file=sys.stderr, flush=True)
    if ok:
        print(json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
