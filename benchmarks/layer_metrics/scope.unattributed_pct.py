"""Share of the scan program's device time (`step.device_ms`) that is not in
a leaf op under a name of the vocabulary: leaf ops without one (copies the
compiler inserts without metadata) and the program's time in no leaf op (the
`while`'s own, the gaps between ops). The guard that the attribution stays
whole: the four `scope.*_ms` and this share of `step.device_ms` sum to it."""

from _common import scan_calls
from _scopes import step_ops


def read(ctx):
    ops = step_ops(ctx)
    if ops is None:
        return None
    named = sum(op[1] for op in ops if op[2] is not None)
    return 100.0 * (1.0 - named / sum(m[3] for m in scan_calls(ctx)))
