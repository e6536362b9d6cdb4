"""flops.py against counts worked by hand for both configurations."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402

PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


# forward, by hand: 2*N*12*H + 3*(3*2*N*H*H + 2*N*16*4*H) + 2*N*H*D + head(B; 3D+16 -> 256 -> 128 -> 1)
HAND = {
    # 402,653,184 + 3*(51,539,607,552 + 2,147,483,648) + 8,589,934,592 + (822,083,584 + 134,217,728 + 524,288)
    "gnn-32k-512": (171_010_686_976, 171_010_686_976 + (171_010_686_976 - 402_653_184 - 3 * 2_147_483_648)),
    # 402,653,184 + 3*(25,769,803,776 + 2,147,483,648) + 4,294,967,296 + (838,860,800 + 268,435,456 + 1,048,576)
    "gnn-64k-256": (89_557_827_584, 89_557_827_584 + (89_557_827_584 - 402_653_184 - 3 * 2_147_483_648)),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_step_flops_by_hand(name):
    got = flops.step_flops(config(name))
    forward, backward = HAND[name]
    assert (got["forward"], got["backward"], got["total"]) == (forward, backward, forward + backward)


@pytest.mark.parametrize("name,scatter_bytes,message_bytes", [
    # scatter, per layer: N*K*H*2 + N*K*4 + N*H*2; messages: (3+4)*N*H*2 + 2*N*K*(4*2+4+2)
    ("gnn-32k-512", 3 * (536_870_912 + 2_097_152 + 33_554_432), 3 * (7 * 33_554_432 + 2 * 7_340_032)),
    ("gnn-64k-256", 3 * (536_870_912 + 4_194_304 + 33_554_432), 3 * (7 * 33_554_432 + 2 * 14_680_064)),
])
def test_floors_by_hand(name, scatter_bytes, message_bytes):
    cfg = config(name)
    s, m = flops.scatter_floor(cfg, PEAKS), flops.message_floor(cfg, PEAKS)
    assert s["bytes"] == scatter_bytes and s["bound"] == "memory"
    assert s["seconds"] == pytest.approx(scatter_bytes / 819e9)
    assert m["bytes"] == message_bytes and m["bound"] == "memory"
    assert m["flops"] == 3 * 2 * 2 * cfg["cluster"]["hosts"] * 16 * 4 * cfg["model"]["hidden"]


def test_shape_classes():
    cfg = config("gnn-32k-512")
    scatter = [("bf16", (32768, 512)), ("s32", (524288,)), ("bf16", (524288, 512))]
    gather = [("bf16", (524288, 512)), ("bf16", (32768, 512)), ("s32", (524288,))]
    conv = [("bf16", (32768, 16, 512)), ("bf16", (32768, 16, 4)), ("bf16", (4, 512))]
    dense = [("bf16", (32768, 512)), ("bf16", (32768, 512)), ("bf16", (512, 512))]
    assert flops.is_scatter(cfg, scatter) and not flops.is_scatter(cfg, gather)
    assert not flops.is_scatter(cfg, dense)
    assert all(flops.touches_messages(cfg, s) for s in (scatter, gather, conv))
    assert not flops.touches_messages(cfg, dense)
