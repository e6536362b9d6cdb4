"""The exchange's share of its roofline: the seconds the bytes a chip must
send and receive a step (exchange.exchange_floor: an all-gather of u[N, H]
and a reduce-scatter of its cotangent a SAGE layer) take at the chip's
published aggregate ICI peak (peaks_ici.json), over the seconds a step during
which an exchange was in flight (_mesh.exchange_in_flight_seconds_per_step:
`mesh.collective_ms` counts the exposed part alone, which overlap could push
under the transfer's own time; the span in flight it cannot)."""

import json
from pathlib import Path

import exchange
from _mesh import chips, exchange_in_flight_seconds_per_step

_PEAKS = Path(exchange.__file__).resolve().parent / "peaks_ici.json"


def read(ctx):
    seconds = exchange_in_flight_seconds_per_step(ctx)
    peaks = json.loads(_PEAKS.read_text()).get(ctx["device"]["kind"])
    if seconds is None or peaks is None or chips(ctx) < 2:
        return None
    return 100.0 * exchange.exchange_floor(ctx["config"], chips(ctx), peaks)["seconds"] / seconds
