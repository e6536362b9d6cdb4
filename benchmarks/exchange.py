"""Bytes the row-sharded step's exchange needs, from the configuration's
shapes and the number of chips alone (beside flops.py: nothing measured).

With node rows over `data`, a SAGE layer's neighbour gather reads rows of
`u[N, H]` (bf16) that live on other chips, and its VJP adds cotangent rows into
rows that live on other chips. The least that has to cross the interconnect,
per optimizer step and per chip: forward, per layer, an all-gather of `u`: the
chip receives the (chips-1)/chips of `N x H` it does not hold; backward, per
layer, a reduce-scatter of the `[N, H]` cotangent: the chip sends the
(chips-1)/chips of its partial sums that other chips own. Parameter
gradients (3 MB) and the pair rows' embeddings are not counted.
"""

from __future__ import annotations


def exchange_floor(config: dict, chips: int, ici_peaks: dict) -> dict:
    """{"bytes" a chip a step, "all_gathers", "reduce_scatters", "seconds" at the
    chip's aggregate ICI peak}; zero bytes on one chip."""
    m, n = config["model"], config["cluster"]["hosts"]
    if m["compute_dtype"] != "bfloat16":
        raise ValueError(f"no byte count for compute dtype {m['compute_dtype']!r}")
    table = n * m["hidden"] * 2
    moved = table * (chips - 1) // chips
    layers = m["num_layers"]
    bytes_ = 2 * layers * moved
    return {"bytes": bytes_, "all_gathers": layers, "reduce_scatters": layers,
            "seconds": bytes_ / ici_peaks["ici_bytes_per_s"]}
