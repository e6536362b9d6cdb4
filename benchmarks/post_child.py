"""After the trainer has gone: the plain reference over what the timed path
produced, and (for a traced run) the reduction of the profiler's trace.

    python benchmarks/post_child.py <post_spec.json>

Runs in a process of its own so that it may open the chip once the trainer's
process has released it — by then the device's peak memory has been read.
Prints one line, POST_PREFIX + JSON: {"readings": {...}, "trace": {...}|null,
"timing": {...}}. The readings are what correct.py holds against the cell's
limits:

  dataset_mismatch      entries of the published graph that differ from the
                        reference's build of the same raw records: neighbour
                        table, mask, host numbering, node and pair counts, and
                        node and edge features (float32 of float64 means; a
                        feature counts when it is off by more than two units in
                        its last place)
  loss_gap_max          over the first scan call's steps of every training run
                        of the window: |program's loss - reference's| / reference's
  gnorm_gap_first       the same for the first gradient's global norm, as the
                        optimizer gets it (before clipping)
  gnorm_gap_max         and for the largest over the first call's steps
  export_mismatch       head weights of scorer.dfsc that are not bit for bit
                        those of params.msgpack, wrong dimensions, a config.json
                        that names other widths
  export_embed_gap      largest |scorer.dfsc embedding - reference's float32
                        encoder over the published weights|
  published_loss_gap    |reference's loss of the PUBLISHED weights on the last
                        step's minibatch - the run's last reported loss| / that
                        loss: what was published is what was trained
  mlp_loss_gap_max      the MLP loop, every run of the window: over every step
                        the hook reports (each tenth, and the last), |program's
                        loss - reference's| / reference's; the reference
                        follows every step of the loop
  mlp_gnorm_gap_first   the same for the gradient norm of the first report
  mlp_update_gap        the published MLP weights' change from the initial
                        ones against the reference's after the same steps, by
                        the worst leaf (reference.update_gap)

With `control` in the spec the reference is also computed in that lower
precision and with each fault planted, and put in the program's place:
readings["extra"] holds `control.<number>` and `fault.<name>.<number>` for
run.py to judge against the same limits.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))
POST_PREFIX = "BENCHPOST "


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def trajectory_gaps(loss: list, grad_norm: list, ref: dict) -> dict:
    """A first call's losses and gradient norms against the reference's."""
    return {
        "loss_gap_max": max(_rel(a, b) for a, b in zip(loss, ref["loss"])),
        "gnorm_gap_first": _rel(grad_norm[0], ref["grad_norm"][0]),
        "gnorm_gap_max": max(_rel(a, b) for a, b in zip(grad_norm, ref["grad_norm"])),
    }


def step_reports(program: dict, model: str, steps: int) -> list[list[tuple]]:
    """Per training run of `model`, its reports up to step `steps` (step, loss, grad norm)."""
    runs: dict[int, list[tuple]] = {}
    for _t, run, m, n, loss, gnorm in program["step_events"]:
        if m == model and n <= steps:
            runs.setdefault(run, []).append((n, loss, gnorm))
    return [runs[k] for k in sorted(runs)]


def mlp_gaps(reports: list[tuple], published, ref: dict, reference) -> dict:
    """One MLP run's reports (step, loss, grad norm) and published weights
    against the reference's run of the same steps."""
    return {
        "mlp_loss_gap_max": max(_rel(loss, ref["loss"][n - 1]) for n, loss, _ in reports),
        "mlp_gnorm_gap_first": _rel(reports[0][2], ref["grad_norm"][reports[0][0] - 1]),
        "mlp_update_gap": reference.update_gap(published, ref),
    }


def as_reports(ref: dict, steps: list[int]) -> list[tuple]:
    """What the hook would have reported of a stand-in's run."""
    return [(n, ref["loss"][n - 1], ref["grad_norm"][n - 1]) for n in steps]


def readings_for(spec: dict, reference, feeders: list) -> tuple[dict, dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    config = spec["config"]
    model, opt = config["model"], config["optimizer"]["gnn"]
    program = json.loads((Path(spec["work"]) / "program.json").read_text())
    timing: dict = {}
    out: dict = {}

    # the window says which run is checked in full, and which feeders'
    # commits, in their order, the pool it trained on held
    checked = program["checked"]
    if checked is None:
        return {"error": "no run published a gnn model"}, timing
    run = program["runs"][checked["run"]]
    artifact = Path(run["models"]["gnn"]["artifact"])
    cl = config["cluster"]
    t = time.monotonic()
    commits = [(feeders[i]["downloads"], feeders[i]["probes"]) for i in checked["commits"]]
    dataset = reference.build_dataset(commits=commits, num_neighbors=model["num_neighbors"],
                                      chunk_rows=cl["chunk_rows"], pool_rows_cap=cl["pool_rows_cap"])
    timing["reference_dataset_s"] = time.monotonic() - t

    # ---- dataset build ----
    graph = reference.read_graph(artifact)
    n_ref = dataset["node_feats"].shape[0]
    mismatch = int(run["dataset"]["nodes"] != n_ref)
    mismatch += int(run["dataset"]["pairs"] != len(dataset["pairs"]["child"]))
    ref_hosts = {h.decode("utf-8", "replace"): i for i, h in enumerate(dataset["hosts"].tolist())}
    mismatch += int(graph["hosts"] != ref_hosts)
    for key in ("neighbors", "mask", "node_feats", "edge_feats"):
        got, want = graph[key], dataset[key]
        if got.shape != want.shape:
            mismatch += int(np.prod(want.shape))
        elif key in ("neighbors", "mask"):
            mismatch += int(np.count_nonzero(got != want))
        else:
            ulps = 2.0 * np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
            mismatch += int(np.count_nonzero(np.abs(got.astype(np.float64) - want) > ulps))
    out["dataset_mismatch"] = mismatch

    # ---- forward, loss, gradient, optimizer: the first scan call ----
    spc = opt["steps_per_call"]
    t = time.monotonic()
    ref = reference.follow_steps(config, dataset, spc)
    timing["reference_steps_s"] = time.monotonic() - t
    # every training run that the window says trained on the same pool is
    # held to the same first call
    trained_at = [i for i, r in enumerate(program["runs"]) if (r.get("models") or {}).get("gnn")]
    trained = [program["runs"][i] for i in trained_at]
    same_pool = {k for k, i in enumerate(trained_at) if i in checked["same_pool"]}
    reports = [r for i, r in enumerate(step_reports(program, "gnn", spc)) if i in same_pool]
    out.update(loss_gap_max=None, gnorm_gap_first=None, gnorm_gap_max=None)
    if reports and all([n for n, _, _ in r] == list(range(1, spc + 1)) for r in reports):
        per_run = [trajectory_gaps([x[1] for x in r], [x[2] for x in r], ref) for r in reports]
        out.update({k: max(g[k] for g in per_run) for k in per_run[0]})

    # ---- export ----
    t = time.monotonic()
    params = reference.read_params(artifact)
    dfsc = reference.read_dfsc(artifact / "scorer.dfsc")
    cfg_json = json.loads((artifact / "config.json").read_text())
    head = params["params"]["head"]
    pairs = [("w1", head["layers_0"]["kernel"]), ("b1", head["layers_0"]["bias"]),
             ("w2", head["layers_2"]["kernel"]), ("b2", head["layers_2"]["bias"]),
             ("w3", head["layers_4"]["kernel"]), ("b3", head["layers_4"]["bias"])]
    bad = sum(1 for name, want in pairs
              if dfsc[name].shape != want.shape or dfsc[name].tobytes() != np.asarray(want, np.float32).tobytes())
    h1, h2, _ = model["head_hidden"]
    bad += int(tuple(dfsc["dims"]) != (n_ref, model["embed_dim"], model["pair_features"], h1, h2))
    bad += int((cfg_json.get("hidden"), cfg_json.get("embed_dim"), cfg_json.get("num_layers"))
               != (model["hidden"], model["embed_dim"], model["num_layers"]))
    out["export_mismatch"] = bad
    ref_graph = {k: jnp.asarray(dataset[k]) for k in ("node_feats", "neighbors", "mask", "edge_feats")}
    dev_params = jax.tree.map(jnp.asarray, params)
    encode_jit = jax.jit(lambda p, g, precision: reference.encode(
        p, g, num_layers=model["num_layers"], precision=precision), static_argnums=2)

    def encode(p, precision):
        return encode_jit(p, ref_graph, precision)

    z_ref = encode(dev_params, "f32")
    out["export_embed_gap"] = (
        float(np.max(np.abs(dfsc["z"] - np.asarray(z_ref)))) if dfsc["z"].shape == z_ref.shape else None
    )
    gnn = run["models"]["gnn"]
    rows = reference.batch_indices_of_step(
        opt["sample_seed"], spc, gnn["steps"], model["pair_batch"], len(dataset["pairs"]["child"]))
    batch = {k: jnp.asarray(v[rows]) for k, v in dataset["pairs"].items()}
    published_loss = float(jnp.mean(jnp.square(
        reference.score(dev_params, z_ref, batch["child"], batch["parent"], batch["feats"]) - batch["label"])))
    out["published_loss_gap"] = _rel(published_loss, gnn["final_loss"]) if gnn.get("final_loss") else None
    timing["reference_export_s"] = time.monotonic() - t

    # ---- the MLP loop and its artifact ----
    t = time.monotonic()
    mlp_opt, mlp_steps = config["optimizer"]["mlp"], spec["mlp_steps"]
    due = sorted({*range(mlp_opt["report_every"], mlp_steps + 1, mlp_opt["report_every"]), mlp_steps})
    mlp_ref = reference.follow_mlp(config, dataset, mlp_steps)
    mlp_runs = [r for i, r in enumerate(trained) if i in same_pool]
    mlp_reports = [r for i, r in enumerate(step_reports(program, "mlp", mlp_steps)) if i in same_pool]
    out.update(mlp_loss_gap_max=None, mlp_gnorm_gap_first=None, mlp_update_gap=None)
    if (len(mlp_reports) == len(mlp_runs) and all((r["models"].get("mlp") or {}).get("artifact") for r in mlp_runs)
            and all([n for n, _, _ in r] == due for r in mlp_reports)):
        per_run = []
        for r, rep in zip(mlp_runs, mlp_reports):
            mlp_artifact = Path(r["models"]["mlp"]["artifact"])
            hidden = json.loads((mlp_artifact / "config.json").read_text()).get("hidden")
            published = reference.read_params(mlp_artifact) if hidden == list(model["mlp_hidden"]) else {}
            per_run.append(mlp_gaps(rep, published, mlp_ref, reference))
        if all(v is not None for g in per_run for v in g.values()):
            out.update({k: max(g[k] for g in per_run) for k in per_run[0]})
    timing["reference_mlp_s"] = time.monotonic() - t

    # ---- limit readings: the control and the planted faults (not part of a run) ----
    if spec.get("control"):
        t = time.monotonic()
        extra: dict = {}
        for name, kw in (("control", {"precision": spec["control"]}), ("fault.state_unchanged", {"fault": "state_unchanged"}),
                         ("fault.half_batch", {"fault": "half_batch"}), ("fault.mlp_leaf_unmoved", {"fault": "leaf_unmoved"})):
            stand_in = reference.follow_mlp(config, dataset, mlp_steps, **kw)
            gaps = mlp_gaps(as_reports(stand_in, due), stand_in["params"], mlp_ref, reference)
            extra.update({f"{name}.{k}": v for k, v in gaps.items()})
        # untrained MLP weights published: the update gap alone sees it
        extra["fault.stale_publish.mlp_update_gap"] = reference.update_gap(mlp_ref["init"], mlp_ref)
        unmoved = reference.follow_steps(config, dataset, spc, fault="leaf_unmoved")
        extra.update({f"fault.gnn_leaf_unmoved.{k}": v
                      for k, v in trajectory_gaps(unmoved["loss"], unmoved["grad_norm"], ref).items()})
        low = reference.follow_steps(config, dataset, spc, precision=spec["control"])
        extra.update({f"control.{k}": v for k, v in trajectory_gaps(low["loss"], low["grad_norm"], ref).items()})
        z_low = encode(dev_params, spec["control"])
        extra["control.export_embed_gap"] = float(jnp.max(jnp.abs(z_low - z_ref)))
        for fault in ("state_unchanged", "half_batch"):
            broken = reference.follow_steps(config, dataset, spc, fault=fault)
            extra.update({f"fault.{fault}.{k}": v
                          for k, v in trajectory_gaps(broken["loss"], broken["grad_norm"], ref).items()})
        # a publish of untrained weights: the reference's loss of the INITIAL
        # weights on the last step's minibatch against the run's last loss
        init = jax.tree.map(jnp.asarray, reference.init_params(model, opt["init_seed"]))
        z0 = encode(init, "f32")
        stale = float(jnp.mean(jnp.square(
            reference.score(init, z0, batch["child"], batch["parent"], batch["feats"]) - batch["label"])))
        extra["fault.stale_publish.published_loss_gap"] = _rel(stale, gnn["final_loss"])
        extra["fault.stale_publish.export_embed_gap"] = float(jnp.max(jnp.abs(z0 - z_ref)))
        extra["reference.loss"] = ref["loss"]
        extra["reference.grad_norm"] = ref["grad_norm"]
        extra["program.first_call"] = reports
        extra["reference.mlp"] = as_reports(mlp_ref, due)
        extra["program.mlp"] = mlp_reports
        out["extra"] = extra
        timing["control_s"] = time.monotonic() - t
    return out, timing


def main(argv: list[str]) -> int:
    spec = json.loads(Path(argv[0]).read_text())
    t0 = time.monotonic()
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    platform = jax.devices()[0].platform
    if platform != "tpu" and not spec["cpu_rehearsal"]:
        print(f"post child: on {platform!r}, not on the chip", file=sys.stderr)
        return 1

    import reference
    import trace_reduce
    from traffic_driver import find_named, load_file

    generator = load_file(find_named(spec["roots"], "generators", f"{spec['config']['generator']}.py"))
    readings, timing = readings_for(spec, reference, generator.generate(spec["config"]["cluster"], spec["seed"]))
    trace = None
    if spec["trace_dir"]:
        t = time.monotonic()
        trace = trace_reduce.reduce_run(spec)
        timing["trace_reduce_s"] = time.monotonic() - t
    timing["post_child_s"] = time.monotonic() - t0
    print(POST_PREFIX + json.dumps({"readings": readings, "trace": trace, "timing": timing}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
