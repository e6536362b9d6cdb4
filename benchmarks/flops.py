"""Operations and bytes the algorithm needs, from the configuration's shapes
alone: no `cost_analysis()`, no recomputation counted, nothing measured.

Counted as FLOPs are the matrix products only (2 x m x k x n each): forward,
the weight gradient of every product, and the input gradient of every product
whose input depends on a parameter (the node and edge features do not, so the
first Dense and the edge projection have none). Elementwise work (the gather's
add, gelu, the masked mean, LayerNorm, the optimizer) is not counted: the MXU
peak is the peak the step's share is taken of.
"""

from __future__ import annotations


def _dims(config: dict) -> dict:
    m, c = config["model"], config["cluster"]
    return {
        "n": c["hosts"], "k": m["num_neighbors"], "h": m["hidden"], "d": m["embed_dim"],
        "f": m["node_features"], "e": m["edge_features"], "fp": m["pair_features"],
        "layers": m["num_layers"], "b": m["pair_batch"], "head": m["head_hidden"],
    }


def step_flops(config: dict) -> dict:
    """{"forward", "backward", "total"} matrix-product FLOPs of one optimizer step."""
    s = _dims(config)
    n, k, h, d, b = s["n"], s["k"], s["h"], s["d"], s["b"]
    # (forward FLOPs, whether the product's input needs a gradient)
    products = [(2 * n * s["f"] * h, False)]                 # node features -> hidden
    for _ in range(s["layers"]):
        products += [
            (2 * n * h * h, True),                           # msg_nbr
            (2 * n * h * h, True),                           # msg_self
            (2 * n * k * s["e"] * h, False),                 # msg_edge (edge features)
            (2 * n * h * h, True),                           # self Dense
        ]
    products.append((2 * n * h * d, True))                   # hidden -> embedding
    width = 3 * d + s["fp"]
    for out in s["head"]:
        products.append((2 * b * width * out, True))         # head (input: embeddings)
        width = out
    forward = sum(f for f, _ in products)
    backward = sum(f + (f if needs_input_grad else 0) for f, needs_input_grad in products)
    return {"forward": forward, "backward": backward, "total": forward + backward}


def scatter_floor(config: dict, peaks: dict) -> dict:
    """The gather's VJP, per optimizer step: per SAGE layer a scatter-add of
    the cotangent [N*K, H] (bf16) by N*K int32 row numbers into [N, H] (bf16).
    It must read the cotangent and the row numbers once and write the result
    once; its additions are N*K*H."""
    s = _dims(config)
    nk = s["n"] * s["k"]
    bytes_ = s["layers"] * (nk * s["h"] * 2 + nk * 4 + s["n"] * s["h"] * 2)
    flops = s["layers"] * nk * s["h"]
    return _floor(bytes_, flops, peaks)


def message_floor(config: dict, peaks: dict) -> dict:
    """Message build and reduce, per optimizer step, for an implementation
    that never writes the [N, K, H] message tensor: forward, per layer, read
    u, s [N, H] (bf16), the edge features [N, K, E], the neighbour table and
    the mask, and write the mean [N, H]; backward, read the same again with
    the mean's cotangent and write the cotangent of s (that of u is the
    scatter's). FLOPs: the edge projection, forward and its weight gradient."""
    s = _dims(config)
    n, k, h = s["n"], s["k"], s["h"]
    table = n * k * (s["e"] * 2 + 4 + 2)
    forward = 3 * n * h * 2 + table
    backward = 4 * n * h * 2 + table
    flops = s["layers"] * 2 * (2 * n * k * s["e"] * h)
    return _floor(s["layers"] * (forward + backward), flops, peaks)


def _floor(bytes_: int, flops: int, peaks: dict) -> dict:
    by_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    by_flops = flops / peaks["bf16_flops_per_s"]
    return {"bytes": bytes_, "flops": flops, "seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute"}


def is_scatter(config: dict, shapes: list) -> bool:
    """An op whose result is [N, H] and that takes N*K row numbers (s32) and
    an [N*K, H] operand: the gather's VJP."""
    s = _dims(config)
    n, nk, h = s["n"], s["n"] * s["k"], s["h"]
    if not shapes or shapes[0][1] != (n, h):
        return False
    operands = shapes[1:]
    return any(d == "s32" and dims in ((nk,), (nk, 1), (n, s["k"])) for d, dims in operands) and any(
        dims in ((nk, h), (n, s["k"], h)) for _, dims in operands)


def touches_messages(config: dict, shapes: list) -> bool:
    """An op with an [N, K, H] or [N*K, H] result or operand."""
    s = _dims(config)
    big = ((s["n"], s["k"], s["h"]), (s["n"] * s["k"], s["h"]))
    return any(dims in big for _, dims in shapes)
