"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One cell of BENCHMARK.json per call, one new process per run. This parent never
imports jax: it generates the seeded telemetry, starts the trainer the way it
is deployed (`dragonfly2_tpu.trainer.server`, flags only, inside
trainer_child.py — the one process that holds the chip), feeds it over its RPC
surface, times the window, stops it, and then has post_child.py run the plain
float32 reference (and, with --trace 1, reduce the profiler's trace). The last
line of stdout is the result object; nothing else is printed there.

What belongs to one cell is data found by name: configs/<config>.json (sizes,
and under "generator" the name of what makes its records),
generators/<generator>.py (`generate(cluster, seed)` -> the feeders: who sends
which downloads and probes), traffic/<traffic>.json (parameters, and under
"window" the name of its kind of window), windows/<window>.py (how the window
drives the feeders, what it reads end to end, its part of set-up, the traced
stretch, and which commits the checked run's pool held: traffic_driver.py
lists what a module provides), limits/<workload>.json,
layer_metrics/<metric>.py. No name has a default: a missing key or file is an
error that names what was looked for (exit 1, no result line).
No chip, a device kind without published peaks, or fewer chips than the cell
asks for is an error (exit 1, no result line), unless --cpu-rehearsal is
given: then the run is a rehearsal of the control flow, says so, and prints no
metric at all (a number from a CPU is never written under a metric's name).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(REPO))

from traffic_driver import Driver, find_named, load_file, server_step_flags  # noqa: E402  (no jax there)

# one run may take 360 s (1200 s the first time in a checkout, which compiles)
RUN_BUDGET_S = 1100.0


def log(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def load_peaks() -> dict:
    """Published peaks by device kind; a kind that is not there is an error."""
    return json.loads((HERE / "peaks.json").read_text())


def load_cell(benchmark_json: Path, workload: str) -> dict:
    bench = json.loads(benchmark_json.read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in {benchmark_json}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    here = REPO / bench["paths"][0]
    # the tests' cells keep their limits, and a test deployment its mix,
    # generator and window, beside their own BENCHMARK.json
    roots = [benchmark_json.resolve().parent, here]
    config = json.loads((REPO / cfg_entry["file"]).read_text())
    traffic = json.loads(find_named(roots, "traffic", f"{cell['traffic']}.json").read_text())
    if "generator" not in config:
        raise SystemExit(f"{cfg_entry['file']} names no \"generator\" (a file of generators/)")
    return {
        "bench": bench, "cell": cell, "config": config, "traffic": traffic, "roots": [str(r) for r in roots],
        "generator": load_file(find_named(roots, "generators", f"{config['generator']}.py")),
        "window": load_file(find_named(roots, "windows", f"{traffic['window']}.py")),
        "limits": json.loads(find_named(roots, "limits", f"{workload}.json").read_text()),
        "layer_dir": here / "layer_metrics",
    }


def metrics_of(bench: dict, section: str, workload: str, reported_e2e: set[str] | None = None) -> list[dict]:
    """The metrics of one section that this cell reports."""
    out = []
    for m in bench[section]:
        cells = m.get("workloads")
        if cells is not None and workload not in cells:
            continue
        if cells is None and reported_e2e is not None and m.get("moves") not in reported_e2e:
            continue
        out.append(m)
    return out


def read_layer_metric(layer_dir: Path, name: str, ctx: dict):
    return load_file(layer_dir / f"{name}.py").read(ctx)


def run_post_child(work: Path, spec: dict, timeout: float) -> dict:
    """The reference, and the trace reduction, in a process of their own that
    opens the chip only after the trainer has gone."""
    (work / "post_spec.json").write_text(json.dumps(spec))
    with open(work / "post.log", "wb") as err:
        proc = subprocess.Popen(
            [sys.executable, str(HERE / "post_child.py"), str(work / "post_spec.json")],
            cwd=REPO, stdout=subprocess.PIPE, stderr=err, start_new_session=True,
        )
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            out = b""
        finally:
            try:
                os.killpg(proc.pid, 9)
            except ProcessLookupError:
                pass
            proc.wait()
    from post_child import POST_PREFIX

    for line in reversed(out.decode(errors="replace").splitlines()):
        if line.startswith(POST_PREFIX):
            return json.loads(line[len(POST_PREFIX):])
    tail = "\n".join((work / "post.log").read_text(errors="replace").splitlines()[-30:])
    raise RuntimeError(f"post child rc={proc.returncode} gave no result:\n{tail}")


async def drive(trainer, address: str, cell: dict, feeders, args, trace_dir, deadline) -> dict:
    from dragonfly2_tpu.rpc.trainer import RemoteTrainerClient

    client = RemoteTrainerClient(address)
    try:
        status = await client.status()
        device = {"platform": status["platform"], "kind": status["device_kind"],
                  "count": status["device_count"]}
        if not args.cpu_rehearsal:
            if device["platform"] != "tpu":
                raise RuntimeError(f"the trainer runs on {device['platform']!r}, not on a chip")
            if device["kind"] not in load_peaks():
                raise RuntimeError(f"no published peaks for device kind {device['kind']!r}")
            if device["count"] < cell["cell"]["chips"]:
                raise RuntimeError(f"{device['count']} chips, the cell asks for {cell['cell']['chips']}")
        driver = Driver(client, trainer, cell["config"], cell["traffic"], feeders,
                        seconds=args.seconds, trace_dir=trace_dir, deadline=deadline)
        window = await driver.run(cell["window"])
        runs = (await client.train_history(limit=64))["runs"][::-1]
    finally:
        await client.close()
    return {"device": device, "window": window, "runs": runs, "checked": cell["window"].checked(window, runs)}


def measure(args, cell: dict, work: Path) -> dict:
    """Generate the records, start the trainer, drive the window, stop the
    trainer. Returns what the window saw, with the set-up's timeline."""
    from serverproc import TrainerProcess

    config, traffic = cell["config"], cell["traffic"]
    deadline = T_PROCESS_START + RUN_BUDGET_S
    t0 = time.monotonic()
    feeders = cell["generator"].generate(config["cluster"], args.seed)
    t_generated = time.monotonic()
    gnn_steps, mlp_steps = server_step_flags(config, traffic, args.seconds)
    flags = ["--port", "0", "--model-dir", str(work / "models"),
             "--gnn-steps", str(gnn_steps), "--mlp-steps", str(mlp_steps), *config["server_flags"]]
    env = {}
    if args.trace:
        # the program's own spans, every one of them, for the idle gaps' names;
        # and one malloc arena: `jax.profiler.stop_trace()` runs in the child's
        # control thread, and glibc gives a thread new to the allocator an arena
        # of its own, in which collecting the trace takes four times as long
        # (PERF.md, PR 32). An untraced run's environment is left as it is
        env = {"DRAGONFLY_TRACE_FILE": str(work / "spans.jsonl"), "DRAGONFLY_TRACE_SAMPLE": "1",
               "MALLOC_ARENA_MAX": "1"}
    trainer = TrainerProcess(REPO, flags, work / "trainer.log",
                             launcher=Path(args.launcher) if args.launcher else None, env=env)
    try:
        address = trainer.wait_ready(deadline)
        t_ready = time.monotonic()
        out = asyncio.run(drive(trainer, address, cell, feeders, args,
                                work / "trace" if args.trace else None, deadline))
        out["compiles"] = trainer.ctl("compiles")["events"]
    except Exception:
        log(f"trainer log:\n{trainer.log_tail()}")
        raise
    finally:
        trainer.stop()
    window, runs = out["window"], out["runs"]
    # the trainer's own reading (memory_stats peak_bytes_in_use at the end of
    # each run), taken before the reference opens the chip
    peaks_seen = [r["device_peak_bytes"] for r in runs if r.get("device_peak_bytes")]
    out["device"]["memory_peak_bytes"] = max(peaks_seen) if peaks_seen else None

    out["setup_split"] = {"python_start_s": t0 - T_PROCESS_START, "generate_s": t_generated - t0,
                          "server_ready_s": t_ready - t_generated, **cell["window"].setup_split(window, t_ready)}
    return out


def end_to_end(kind, window: dict, traffic: dict, runs: list) -> tuple[dict, dict, int, int]:
    """(end-to-end metrics by the host's clock, detail, attempted, failed);
    `kind` is the window's module, which says what the window reads."""
    read, more, attempted = kind.end_to_end(window, traffic)
    e2e = {"setup_s": window["window_start"] - T_PROCESS_START, **read}
    detail = {"window_s": window["window_s"], **more}
    failed = sum(1 for r in runs if r["status"] != "ok") + max(0, attempted - len(runs))

    def of_model(r: dict, m: str, key: str):
        return (r["models"].get(m) or {}).get(key) or {}

    detail["run_stages"] = [
        {"wall_s": r["wall_s"], "build_s": r["dataset"]["build_seconds"],
         **{f"{m}_{short}_s": of_model(r, m, "evaluation").get(f"{short}_seconds")
            for m in ("mlp", "gnn") for short in ("train", "export")},
         "gnn_stall_ms": of_model(r, "gnn", "calls").get("stall_ms")}
        for r in runs
    ]
    return e2e, detail, attempted, failed


def per_layer(cell: dict, workload: str, e2e_names: set, out: dict, post: dict, work: Path) -> tuple[dict, dict | None]:
    """(per-layer metrics of a traced run, breakdown): every reader of the
    cell's metrics is asked; one that finds nothing to read is left out."""
    import trace_reduce

    config, traffic, device = cell["config"], cell["traffic"], out["device"]
    view = breakdown = None
    if post.get("trace"):
        compact = json.loads(Path(post["trace"]["compact"]).read_text())
        spans = trace_reduce.read_spans(work / "spans.jsonl")
        stretch = cell["window"].traced_stretch(out["window"], config, traffic)
        view = trace_reduce.view_for(compact, stretch, out["window"], spans)
        device["busy_s"] = view.busy_s()
        device["window_s"] = view.window_s
        breakdown = {"device_ops": view.top_ops(), "idle_gaps": view.top_gaps()}
    ctx = {
        "config": config, "traffic": traffic, "window": out["window"], "runs": out["runs"],
        "compiles": out["compiles"], "view": view, "device": device,
        "peaks": load_peaks().get(device["kind"]),
    }
    sys.path.insert(0, str(cell["layer_dir"]))
    metrics = {}
    for m in metrics_of(cell["bench"], "per_layer", workload, e2e_names):
        value = read_layer_metric(cell["layer_dir"], m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, breakdown


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="rehearse the control flow without a chip; prints no metric")
    ap.add_argument("--benchmark-json", default=str(REPO / "BENCHMARK.json"),
                    help="the cells to read (the tests point this at tiny ones)")
    ap.add_argument("--launcher", default=None, help="(tests) a launcher that breaks the trainer")
    ap.add_argument("--control", default=None,
                    help="(limit readings) also read the lower-precision control, e.g. fp8")
    args = ap.parse_args(argv)

    cell = load_cell(Path(args.benchmark_json), args.workload)
    work = REPO / ".bench_work" / args.workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    try:
        result = one_run(args, cell, work)
    except Exception as e:
        log(f"FAILED: {type(e).__name__}: {e}")
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(result), flush=True)
    if any(v["correct"] and not v["known_to_pass"] for v in result.get("stand_ins", {}).values()):
        log("FAILED: the control, or a planted fault that the limits do not list as passing, came out correct")
        return 4
    return 0


def one_run(args, cell: dict, work: Path) -> dict:
    import correct as correctlib

    out = measure(args, cell, work)
    window, runs, device = out["window"], out["runs"], out["device"]
    e2e, detail, attempted, failed = end_to_end(cell["window"], window, cell["traffic"], runs)

    # the reference, and the trace's reduction, once the trainer has gone
    (work / "program.json").write_text(json.dumps(
        {"runs": runs, "step_events": window["step_events"], "checked": out["checked"]}))
    post = run_post_child(work, {
        "work": str(work), "config": cell["config"], "traffic": cell["traffic"], "seed": args.seed,
        "roots": cell["roots"], "trace_dir": str(work / "trace") if args.trace else None,
        "mlp_steps": server_step_flags(cell["config"], cell["traffic"], args.seconds)[1],
        "control": args.control, "cpu_rehearsal": args.cpu_rehearsal,
    }, timeout=max(60.0, T_PROCESS_START + RUN_BUDGET_S - time.monotonic()))
    compared, correct = correctlib.judge(post["readings"], cell["limits"])

    bench = cell["bench"]
    breakdown = None
    if args.trace:
        e2e_names = {m["name"] for m in metrics_of(bench, "end_to_end", args.workload)} & set(e2e)
        metrics, breakdown = per_layer(cell, args.workload, e2e_names, out, post, work)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(bench, "end_to_end", args.workload) if m["name"] in e2e}

    result = {"correct": bool(correct and failed == 0), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.cpu_rehearsal:
        # a rehearsal: what was read goes under its own key, never under `metrics`
        result["rehearsal"] = {"platform": device["platform"], "read": metrics}
        result["metrics"] = {}
    if breakdown is not None:
        result["breakdown"] = breakdown
    after_window = dict(post.get("timing", {}))
    trace = window.get("trace") or {}
    if "stop_s" in trace:
        # what the trainer's child took to answer `trace_stop`, and what it was given
        after_window.update(trace_stop_s=trace["stop_s"], trace_stop_limit_s=trace["stop_limit_s"])
    result.update(setup_split=out["setup_split"], detail=detail, after_window=after_window)
    if args.control:
        # the control and each planted fault, put in the program's place, go
        # through the same judge against the same limits
        extra = post["readings"].get("extra") or {}
        result["limit_readings"] = extra
        result["stand_ins"] = correctlib.judge_stand_ins(post["readings"], extra, cell["limits"])
        result["control_correct"] = result["stand_ins"]["control"]["correct"]
        result["fault_correct"] = {name[len("fault."):]: v["correct"]
                                   for name, v in result["stand_ins"].items() if name != "control"}
    result["compared"] = compared
    log(f"end to end: {e2e}")
    log(f"set-up split: {out['setup_split']}")
    for name, (value, limit) in compared.items():
        log(f"compared {name}: {value!r} (limit {limit!r}) {'ok' if correctlib.within(value, limit) else 'OVER'}")
    for name, verdict in result.get("stand_ins", {}).items():
        log(f"stand-in {name}: correct={verdict['correct']} over={verdict['over']}"
            + (" (listed as passing)" if verdict["known_to_pass"] else ""))
    log(f"correct={result['correct']} failed={failed}/{attempted}")
    return result


if __name__ == "__main__":
    sys.exit(main())
