"""The plain reference: what the trainer is supposed to compute, written out
straightforwardly in numpy (dataset build, export format) and `jax.numpy`
float32 at matmul precision "highest" (model, loss, gradient, optimizer).
It imports nothing of `dragonfly2_tpu` and takes nothing the program made: its
inputs are the seeded raw records and the configuration file's numbers. The
published artifacts are read only where the question is whether they agree
with each other and with what was trained (the export).

Stated by the configuration (configs/*.json `model`, `optimizer`):
GraphSAGE, K padded neighbours, per layer
    u = h Wn ; s = h Ws + bs ; v = e We                       (pre-projected)
    msg = gelu(u[nbr] + s[:, None] + v)                       [N, K, H]
    agg = sum(msg * mask) / (sum(mask) + 1e-6)
    h'  = LayerNorm(gelu(h Wd + bd + agg))
embeddings z = normalize(h Wz + bz); head on [zc, zp, zc*zp, pair feats]:
Dense-gelu-Dense-gelu-Dense-sigmoid; loss = mean squared error to the label.
Optimizer: clip by global norm 1.0, AdamW(b1 .9, b2 .999, eps 1e-8, weight
decay 1e-4 on every leaf), learning rate warm-up 0 -> 3e-3 over 100 steps.
Initial weights: the published parameter tree (artifact format 2) initialised
the flax way — lecun-normal kernels, zero biases, unit LayerNorm — from
PRNGKey(init_seed); the skeleton below only names the leaves so that the same
key gives the same numbers. Minibatches: `steps_per_call` keys split from a
key split off PRNGKey(sample_seed) per call, `randint` rows of the pair pool.

The MLP (configs/*.json `model.mlp_hidden`, `optimizer.mlp`): Dense-gelu per
hidden width, Dense(1), sigmoid; mean squared error to the label on the pair
features alone. Adam(b1 .9, b2 .999, eps 1e-8) at a fixed learning rate, no
clipping, no decay. It trains on the pair pool less a held-out tenth: rows
`default_rng(split_seed).permutation(n)[n // 10:]`; minibatches are
`default_rng(sample_seed).integers(0, n_train, batch)` drawn step by step;
initial weights the flax way from PRNGKey(init_seed).

`precision="fp8"` computes every matmul's two operands rounded to
float8_e4m3fn (the control: the nearest precision below the bfloat16 the
configuration states); everything else stays as it is.
"""

from __future__ import annotations

import collections
import json
import math
import struct
from functools import partial
from pathlib import Path

import numpy as np

GIB = float(1 << 30)
DFSC_MAGIC = 0x44465343


# --------------------------------------------------------------------------
# dataset build (numpy, float64 accumulation as the records' semantics state)
# --------------------------------------------------------------------------


def _intern(seq: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(ids in order of first occurrence, code of every element)."""
    uniq, first, inv = np.unique(seq, return_index=True, return_inverse=True)
    order = np.argsort(first, kind="stable")
    rank = np.empty(len(uniq), np.int64)
    rank[order] = np.arange(len(uniq))
    return uniq[order], rank[inv]


def _cat(arrays: list) -> np.ndarray:
    return arrays[0] if len(arrays) == 1 else np.concatenate(arrays)


def build_dataset(downloads: np.ndarray | None = None, probes: np.ndarray | None = None, *, num_neighbors: int,
                  uploads: int = 1, commits: list | None = None, chunk_rows: int | None = None,
                  pool_rows_cap: int = 0) -> dict:
    """Raw records -> host table, padded neighbour graph, pair pool, as the
    trainer's pool stands after `commits`, a list of (downloads, probes) in
    the order they were committed; `uploads=n` of one pair of arrays is that
    pair committed n times.

    Hosts are numbered by first occurrence over the commits as they are
    streamed: child then parent of every successful download that has a
    parent, then source then destination of every probe, commit by commit.
    Probe rows of one (src, dst) are averaged; each source keeps its
    `num_neighbors` lowest mean RTTs (ties by arrival). Node features 1 and 5
    are a parent's upload success rate and mean normalised bandwidth over all
    its download rows; pairs are the successful downloads, labelled
    min(1, bandwidth / GiB). Commits of the same arrays are read once and
    weighted by how often they came, less the common factor: n commits of one
    upload have the means and rates of one."""
    k = num_neighbors
    commits = [(downloads, probes)] * uploads if commits is None else commits
    times = collections.Counter((id(d), id(p)) for d, p in commits)
    common = math.gcd(*times.values())
    parts = list({(id(d), id(p)): (d, p, times[id(d), id(p)] // common) for d, p in commits}.values())
    downloads, probes = _cat([d for d, _, _ in parts]), _cat([p for _, p, _ in parts])
    w_down = _cat([np.full(len(d), w, np.float64) for d, _, w in parts])
    w_probe = _cat([np.full(len(p), w, np.float64) for _, p, w in parts])
    ok = downloads["success"] & (downloads["parent_host_id"] != b"")
    ids, is_probe, at = [], [], 0
    for d, p, _ in parts:
        part_ok = ok[at : at + len(d)]
        at += len(d)
        ids += [np.stack([d["child_host_id"][part_ok], d["parent_host_id"][part_ok]], 1).reshape(-1),
                np.stack([p["src_host_id"], p["dst_host_id"]], 1).reshape(-1)]
        is_probe += [np.zeros(len(ids[-2]), bool), np.ones(len(ids[-1]), bool)]
    hosts, codes = _intern(_cat(ids))
    is_probe = _cat(is_probe)
    n = max(len(hosts), 8)
    pair_codes, probe_codes = codes[~is_probe], codes[is_probe]
    child, parent, src, dst = pair_codes[0::2], pair_codes[1::2], probe_codes[0::2], probe_codes[1::2]

    # edges: mean of each statistic per (src, dst), first-occurrence order
    _, edge_of_row = _intern((src << 32) | dst)
    m = int(edge_of_row.max()) + 1 if len(edge_of_row) else 0
    stats = np.stack([
        probes["rtt_mean_ms"], probes["rtt_std_ms"], probes["rtt_min_ms"], probes["probe_count"],
    ], axis=1).astype(np.float64)
    count = np.bincount(edge_of_row, weights=w_probe, minlength=m)
    mean = np.stack([np.bincount(edge_of_row, weights=stats[:, c] * w_probe, minlength=m) for c in range(4)], 1)
    mean /= np.maximum(count, 1)[:, None]
    first_row = np.full(m, len(edge_of_row), np.int64)
    np.minimum.at(first_row, edge_of_row, np.arange(len(edge_of_row)))
    e_src, e_dst = src[first_row], dst[first_row]

    neighbors = np.zeros((n, k), np.int32)
    mask = np.zeros((n, k), np.float32)
    edge_feats = np.zeros((n, k, 4), np.float32)
    order = np.lexsort((np.arange(m), mean[:, 0], e_src))
    s_sorted = e_src[order]
    starts = np.flatnonzero(np.r_[True, s_sorted[1:] != s_sorted[:-1]])
    slot = np.arange(m) - np.repeat(starts, np.diff(np.r_[starts, m]))
    keep = slot < k
    rows, cols, sel = s_sorted[keep], slot[keep], order[keep]
    neighbors[rows, cols] = e_dst[sel]
    mask[rows, cols] = 1.0
    edge_feats[rows, cols, 0] = mean[sel, 0] / 100.0
    edge_feats[rows, cols, 1] = mean[sel, 1] / 100.0
    edge_feats[rows, cols, 2] = mean[sel, 2] / 100.0
    edge_feats[rows, cols, 3] = np.minimum(1.0, mean[sel, 3] / 30.0)

    # node features from every download row that names a parent the table knows
    node_feats = np.zeros((n, 12), np.float32)
    has_parent = downloads["parent_host_id"] != b""
    parent_ids = downloads["parent_host_id"][has_parent]
    sorter = np.argsort(hosts, kind="stable")
    sorted_hosts = hosts[sorter]
    pos = np.minimum(np.searchsorted(sorted_hosts, parent_ids), len(hosts) - 1)
    known = sorted_hosts[pos] == parent_ids
    pcode = sorter[pos][known]
    succ = downloads["success"][has_parent][known]
    weight = w_down[has_parent][known]
    bw = np.minimum(1.0, downloads["bandwidth_bps"][has_parent][known].astype(np.float64) / GIB)
    total = np.bincount(pcode, weights=weight, minlength=n)
    n_succ = np.bincount(pcode[succ], weights=weight[succ], minlength=n)
    bw_sum = np.bincount(pcode[succ], weights=(bw * weight)[succ], minlength=n)
    served = total > 0
    node_feats[served, 1] = n_succ[served] / total[served]
    node_feats[served, 5] = bw_sum[served] / total[served]

    label = np.minimum(1.0, downloads["bandwidth_bps"][ok].astype(np.float64) / GIB).astype(np.float32)
    pairs = {
        "child": child.astype(np.int32), "parent": parent.astype(np.int32),
        "feats": downloads["pair_features"][ok].astype(np.float32), "label": label,
    }
    # the rolling pair pool: a commit appends its chunks' pair rows, then the
    # oldest whole chunks go while the rest alone still covers the cap (0: none)
    chunks_of, at_row, at_pair = {}, 0, 0
    for d, p, _ in parts:
        rows = chunk_rows or len(d)
        sizes = [int(ok[at_row + i : at_row + min(i + rows, len(d))].sum()) for i in range(0, len(d), rows)]
        edges = at_pair + np.cumsum([0] + [s for s in sizes if s])
        chunks_of[id(d), id(p)] = list(zip(edges[:-1], edges[1:]))
        at_row, at_pair = at_row + len(d), edges[-1]
    pool, held = [], 0
    for d, p in commits:
        pool += chunks_of[id(d), id(p)]
        held += sum(b - a for a, b in chunks_of[id(d), id(p)])
        while pool_rows_cap > 0 and len(pool) > 1 and held - (pool[0][1] - pool[0][0]) >= pool_rows_cap:
            held -= pool[0][1] - pool[0][0]
            pool.pop(0)
    kept = _cat([np.arange(a, b) for a, b in pool])
    pairs = {k: v[kept] for k, v in pairs.items()}
    return {
        "hosts": hosts, "node_feats": node_feats, "neighbors": neighbors, "mask": mask,
        "edge_feats": edge_feats, "pairs": pairs,
    }


# --------------------------------------------------------------------------
# the published artifact formats (read only)
# --------------------------------------------------------------------------


def read_params(artifact: Path) -> dict:
    from flax.serialization import msgpack_restore

    return msgpack_restore((artifact / "params.msgpack").read_bytes())


def read_graph(artifact: Path) -> dict:
    z = np.load(artifact / "graph.npz")
    hosts = json.loads((artifact / "hosts.json").read_text())
    return {**{k: z[k] for k in ("node_feats", "neighbors", "mask", "edge_feats")}, "hosts": hosts}


def read_dfsc(path: Path) -> dict:
    """scorer.dfsc: 7 uint32 (magic, version, n, d, fp, h1, h2), then float32
    z[n, d], w1[3d+fp, h1], b1, w2[h1, h2], b2, w3[h2, 1], b3."""
    data = path.read_bytes()
    magic, version, n, d, fp, h1, h2 = struct.unpack_from("<7I", data, 0)
    if magic != DFSC_MAGIC:
        raise ValueError(f"{path}: bad magic {magic:#x}")
    shapes = [("z", (n, d)), ("w1", (3 * d + fp, h1)), ("b1", (h1,)), ("w2", (h1, h2)),
              ("b2", (h2,)), ("w3", (h2, 1)), ("b3", (1,))]
    out, off = {"version": version, "dims": (n, d, fp, h1, h2)}, 28
    for name, shape in shapes:
        size = int(np.prod(shape))
        out[name] = np.frombuffer(data, np.float32, size, off).reshape(shape)
        off += 4 * size
    if off != len(data):
        raise ValueError(f"{path}: {len(data) - off} trailing bytes")
    return out


# --------------------------------------------------------------------------
# model, loss, gradient, optimizer (jax.numpy, float32, precision highest)
# --------------------------------------------------------------------------


def init_params(model: dict, seed: int):
    """The published parameter tree, initialised the flax way from
    PRNGKey(seed). The modules below do no arithmetic worth the name: they
    exist so that every leaf gets the name, the shape and therefore the key
    that the tree's format (artifact format 2) gives it."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    hidden, embed, layers = model["hidden"], model["embed_dim"], model["num_layers"]
    h1, h2, h3 = model["head_hidden"]

    def dense(width, **kw):
        return nn.Dense(width, param_dtype=jnp.float32, **kw)

    class SAGELayer(nn.Module):
        @nn.compact
        def __call__(self, h, e):
            out = dense(hidden, use_bias=False, name="msg_nbr")(h) + dense(hidden, name="msg_self")(h)
            out = out + dense(hidden, use_bias=False, name="msg_edge")(e)
            return nn.LayerNorm(param_dtype=jnp.float32)(dense(hidden)(h) + out)

    class GraphSAGE(nn.Module):
        @nn.compact
        def __call__(self, x, e):
            h = dense(hidden)(x)
            for _ in range(layers):
                h = SAGELayer()(h, e)
            return dense(embed)(h)

    class TopoScorer(nn.Module):
        def setup(self):
            self.encoder = GraphSAGE()
            self.head = nn.Sequential([dense(h1), nn.gelu, dense(h2), nn.gelu, dense(h3)])

        def __call__(self, x, e, f):
            z = self.encoder(x, e)
            return self.head(jnp.concatenate([z, z, z, f], axis=-1))

    x = jnp.zeros((2, model["node_features"]))
    e = jnp.zeros((2, model["edge_features"]))
    f = jnp.zeros((2, model["pair_features"]))
    return jax.tree.map(np.asarray, TopoScorer().init(jax.random.PRNGKey(seed), x, e, f))


def _matmul(precision: str):
    import jax
    import jax.numpy as jnp

    if precision == "f32":
        return partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        def q(a):
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)

        return lambda a, b: jnp.matmul(q(a), q(b), precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def encode(params, graph, *, num_layers: int, precision: str = "f32", eps: float = 1e-6):
    """Node embeddings z[N, D]; one SAGE layer at a time under jax.checkpoint,
    so that a backward pass holds one layer's [N, K, H] tensors, not three."""
    import jax
    import jax.numpy as jnp

    mm = _matmul(precision)
    enc = params["params"]["encoder"]
    nbr, mask, edge = graph["neighbors"], graph["mask"], graph["edge_feats"]

    def gelu(x):
        return jax.nn.gelu(x, approximate=True)

    @jax.checkpoint
    def layer(p, h):
        u = mm(h, p["msg_nbr"]["kernel"])
        s = mm(h, p["msg_self"]["kernel"]) + p["msg_self"]["bias"]
        v = mm(edge, p["msg_edge"]["kernel"])
        msg = gelu(u[nbr] + s[:, None, :] + v)
        m = mask[..., None]
        agg = jnp.sum(msg * m, axis=1) / (jnp.sum(m, axis=1) + 1e-6)
        out = gelu(mm(h, p["Dense_0"]["kernel"]) + p["Dense_0"]["bias"] + agg)
        mean = jnp.mean(out, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(out - mean), axis=-1, keepdims=True)
        normed = (out - mean) * jax.lax.rsqrt(var + eps)
        return normed * p["LayerNorm_0"]["scale"] + p["LayerNorm_0"]["bias"]

    h = mm(graph["node_feats"], enc["Dense_0"]["kernel"]) + enc["Dense_0"]["bias"]
    for i in range(num_layers):
        h = layer(enc[f"SAGELayer_{i}"], h)
    z = mm(h, enc["Dense_1"]["kernel"]) + enc["Dense_1"]["bias"]
    return z / (jnp.linalg.norm(z, axis=-1, keepdims=True) + 1e-6)


def score(params, z, child, parent, feats, *, precision: str = "f32"):
    import jax
    import jax.numpy as jnp

    mm = _matmul(precision)
    head = params["params"]["head"]
    zc, zp = z[child], z[parent]
    x = jnp.concatenate([zc, zp, zc * zp, feats], axis=-1)
    x = jax.nn.gelu(mm(x, head["layers_0"]["kernel"]) + head["layers_0"]["bias"], approximate=True)
    x = jax.nn.gelu(mm(x, head["layers_2"]["kernel"]) + head["layers_2"]["bias"], approximate=True)
    return jax.nn.sigmoid((mm(x, head["layers_4"]["kernel"]) + head["layers_4"]["bias"])[:, 0])


def loss_fn(params, graph, batch, *, num_layers: int, precision: str = "f32"):
    import jax.numpy as jnp

    z = encode(params, graph, num_layers=num_layers, precision=precision)
    pred = score(params, z, batch["child"], batch["parent"], batch["feats"], precision=precision)
    return jnp.mean(jnp.square(pred - batch["label"]))


def learning_rate(sched: dict, count):
    """optax.warmup_cosine_decay_schedule, written out."""
    import jax.numpy as jnp

    warm = sched["init"] + (sched["peak"] - sched["init"]) * count / sched["warmup_steps"]
    t = jnp.clip((count - sched["warmup_steps"]) / (sched["decay_steps"] - sched["warmup_steps"]), 0.0, 1.0)
    decayed = sched["end"] + (sched["peak"] - sched["end"]) * 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return jnp.where(count < sched["warmup_steps"], warm, decayed)


def adamw_step(opt: dict, params, mu, nu, count, grads):
    """clip_by_global_norm -> adamw -> apply; returns (params, mu, nu, gnorm)."""
    import jax
    import jax.numpy as jnp

    a = opt["adamw"]
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.where(gnorm < opt["clip_global_norm"], 1.0, opt["clip_global_norm"] / gnorm)
    grads = jax.tree.map(lambda g: g * scale, grads)
    mu = jax.tree.map(lambda m, g: a["b1"] * m + (1 - a["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: a["b2"] * v + (1 - a["b2"]) * jnp.square(g), nu, grads)
    t = count + 1
    lr = learning_rate(opt["schedule"], count)

    def update(p, m, v):
        m_hat = m / (1 - a["b1"] ** t)
        v_hat = v / (1 - a["b2"] ** t)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + a["eps"]) + a["weight_decay"] * p)

    return jax.tree.map(update, params, mu, nu), mu, nu, gnorm


def batch_indices(sample_seed: int, steps_per_call: int, calls: int, batch: int, pool_rows: int,
                  *, only_last: bool = False) -> np.ndarray:
    """Row numbers of every minibatch of the first `calls` scan calls,
    [calls * steps_per_call, batch] (`only_last`: of the last call alone): per
    call one key is split off the running key and split again into one key
    per step."""
    import jax

    @jax.jit
    def one_call(key):
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, steps_per_call)
        return key, jax.vmap(lambda k: jax.random.randint(k, (batch,), 0, pool_rows))(keys)

    key = jax.random.PRNGKey(sample_seed)
    out = []
    for _ in range(calls):
        key, rows = one_call(key)
        if not only_last:
            out.append(np.asarray(rows))
    return np.asarray(rows) if only_last else np.concatenate(out)


def batch_indices_of_step(sample_seed: int, steps_per_call: int, step: int, batch: int, pool_rows: int) -> np.ndarray:
    """Row numbers of the minibatch of optimizer step `step` (1-based)."""
    call, within = divmod(step - 1, steps_per_call)
    return batch_indices(sample_seed, steps_per_call, call + 1, batch, pool_rows, only_last=True)[within]


def follow_steps(config: dict, dataset: dict, steps: int, *, precision: str = "f32",
                 fault: str | None = None) -> dict:
    """Train `steps` optimizer steps from the seed; returns each step's loss
    and gradient norm (as the optimizer gets it: before clipping).

    `fault` plants one of the faults the comparison must catch into this
    stand-in for the program: "state_unchanged" (the step returns its state as
    it got it), "half_batch" (half of the batch left out, the mean over the
    rest) or "leaf_unmoved" (one LayerNorm scale never updated)."""
    import jax
    import jax.numpy as jnp

    model, opt = config["model"], config["optimizer"]["gnn"]
    graph = {k: jnp.asarray(dataset[k]) for k in ("node_feats", "neighbors", "mask", "edge_feats")}
    pool = {k: jnp.asarray(v) for k, v in dataset["pairs"].items()}
    spc = opt["steps_per_call"]
    idx = batch_indices(opt["sample_seed"], spc, -(-steps // spc), model["pair_batch"], len(dataset["pairs"]["child"]))
    params = jax.tree.map(jnp.asarray, init_params(model, opt["init_seed"]))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    keep = model["pair_batch"] // 2 if fault == "half_batch" else model["pair_batch"]

    # graph and pool are arguments, not constants of the program: the same
    # compiled step serves every seed
    @jax.jit
    def step(params, mu, nu, count, rows, graph, pool):
        batch = {k: v[rows[:keep]] for k, v in pool.items()}
        loss, grads = jax.value_and_grad(loss_fn)(
            params, graph, batch, num_layers=model["num_layers"], precision=precision)
        return loss, adamw_step(opt, params, mu, nu, count, grads)

    losses, gnorms = [], []
    for i in range(steps):
        loss, (new_params, new_mu, new_nu, gnorm) = step(
            params, mu, nu, jnp.float32(i), jnp.asarray(idx[i]), graph, pool)
        if fault == "leaf_unmoved":
            new_params = with_leaf(new_params, GNN_UNMOVED_LEAF, leaf_of(params, GNN_UNMOVED_LEAF))
        if fault != "state_unchanged":
            params, mu, nu = new_params, new_mu, new_nu
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    return {"loss": losses, "grad_norm": gnorms}


# --------------------------------------------------------------------------
# the MLP bandwidth predictor (jax.numpy, float32, precision highest)
# --------------------------------------------------------------------------

GNN_UNMOVED_LEAF = ("params", "encoder", "SAGELayer_1", "LayerNorm_0", "scale")
MLP_UNMOVED_LEAF = ("params", "Dense_1", "bias")


def leaf_of(tree: dict, path: tuple):
    for key in path:
        tree = tree[key]
    return tree


def with_leaf(tree: dict, path: tuple, value) -> dict:
    """A copy of the nested dict with the leaf at `path` replaced."""
    if not path:
        return value
    return {**tree, path[0]: with_leaf(tree[path[0]], path[1:], value)}


def init_mlp_params(model: dict, seed: int):
    """The published MLP tree (Dense_0 .. Dense_n), initialised the flax way
    from PRNGKey(seed); as in init_params the module only names the leaves."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    widths = (*model["mlp_hidden"], 1)

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            for width in widths:
                x = nn.Dense(width, param_dtype=jnp.float32)(x)
            return x

    return jax.tree.map(np.asarray, MLP().init(jax.random.PRNGKey(seed), jnp.zeros((8, model["pair_features"]))))


def mlp_train_rows(n_pairs: int, opt: dict) -> np.ndarray:
    """Rows of the pair pool the MLP trains on: all but the held-out share."""
    perm = np.random.default_rng(opt["split_seed"]).permutation(n_pairs)
    n_eval = max(1, int(n_pairs * opt["holdout"])) if n_pairs > 1 else 0
    return perm[n_eval:] if n_eval < n_pairs else perm


def mlp_predict(params, x, *, precision: str = "f32"):
    import jax

    mm = _matmul(precision)
    layers = params["params"]
    for i in range(len(layers) - 1):
        x = jax.nn.gelu(mm(x, layers[f"Dense_{i}"]["kernel"]) + layers[f"Dense_{i}"]["bias"], approximate=True)
    last = layers[f"Dense_{len(layers) - 1}"]
    return jax.nn.sigmoid((mm(x, last["kernel"]) + last["bias"])[:, 0])


def follow_mlp(config: dict, dataset: dict, steps: int, *, precision: str = "f32",
               fault: str | None = None) -> dict:
    """Train the MLP `steps` Adam steps from the seeds; returns every step's
    loss and gradient norm, the initial and the final weights, and the first
    gradient's norm leaf by leaf. `fault` as in follow_steps; "leaf_unmoved"
    never updates one bias."""
    import jax
    import jax.numpy as jnp

    model, opt = config["model"], config["optimizer"]["mlp"]
    rows = mlp_train_rows(len(dataset["pairs"]["child"]), opt)
    feats = jnp.asarray(dataset["pairs"]["feats"][rows])
    label = jnp.asarray(dataset["pairs"]["label"][rows])
    batch = min(opt["batch"], len(rows))
    keep = batch // 2 if fault == "half_batch" else batch
    rng = np.random.default_rng(opt["sample_seed"])
    init = init_mlp_params(model, opt["init_seed"])
    params = jax.tree.map(jnp.asarray, init)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    a = opt["adam"]

    def loss_of(p, x, y):
        return jnp.mean(jnp.square(mlp_predict(p, x, precision=precision) - y))

    @jax.jit
    def step(params, mu, nu, t, idx, feats, label):
        loss, grads = jax.value_and_grad(loss_of)(params, feats[idx[:keep]], label[idx[:keep]])
        leaf_norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
        gnorm = jnp.sqrt(sum(jnp.square(n) for n in jax.tree.leaves(leaf_norms)))
        mu = jax.tree.map(lambda m, g: a["b1"] * m + (1 - a["b1"]) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: a["b2"] * v + (1 - a["b2"]) * jnp.square(g), nu, grads)

        def update(p, m, v):
            return p - opt["adam_lr"] * (m / (1 - a["b1"] ** t)) / (jnp.sqrt(v / (1 - a["b2"] ** t)) + a["eps"])

        return loss, gnorm, leaf_norms, jax.tree.map(update, params, mu, nu), mu, nu

    losses, gnorms, first_leaf_norms = [], [], None
    for i in range(steps):
        idx = jnp.asarray(rng.integers(0, len(rows), size=batch))
        loss, gnorm, leaf_norms, new_params, new_mu, new_nu = step(
            params, mu, nu, jnp.float32(i + 1), idx, feats, label)
        if fault == "leaf_unmoved":
            new_params = with_leaf(new_params, MLP_UNMOVED_LEAF, leaf_of(params, MLP_UNMOVED_LEAF))
        if fault != "state_unchanged":
            params, mu, nu = new_params, new_mu, new_nu
        if first_leaf_norms is None:
            first_leaf_norms = leaf_norms
        losses.append(loss)
        gnorms.append(gnorm)
    return {
        "loss": [float(x) for x in losses], "grad_norm": [float(x) for x in gnorms],
        "init": init, "params": jax.tree.map(np.asarray, params),
        "first_grad_leaf_norms": jax.tree.map(float, first_leaf_norms),
    }


def update_gap(published: dict, ref: dict) -> float | None:
    """The parameters' change by the worst leaf: |program's norm of (published
    - initial) - reference's| over the reference's norm of that leaf or of the
    median leaf, whichever is larger. A leaf whose first gradient in the
    reference is under a thousandth of the median leaf's moves by round-off
    alone and is left out. None where the trees do not match."""
    import jax

    flat = lambda tree: {jax.tree_util.keystr(k): np.asarray(v, np.float64)  # noqa: E731
                         for k, v in jax.tree_util.tree_leaves_with_path(tree)}
    pub, init, end, g1 = flat(published), flat(ref["init"]), flat(ref["params"]), flat(ref["first_grad_leaf_norms"])
    if pub.keys() != end.keys() or any(pub[k].shape != end[k].shape for k in end):
        return None
    moved = {k: float(np.linalg.norm(end[k] - init[k])) for k in end}
    median_move = float(np.median(list(moved.values())))
    median_grad = float(np.median([float(v) for v in g1.values()]))
    worst = 0.0
    for k in end:
        if float(g1[k]) < 1e-3 * median_grad:
            continue
        got = float(np.linalg.norm(pub[k] - init[k]))
        worst = max(worst, abs(got - moved[k]) / max(moved[k], median_move))
    return worst
