"""Peak device memory of the trainer process (memory_stats peak_bytes_in_use,
read before the reference runs), in GB."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return None if not peak or ctx["device"]["platform"] != "tpu" else peak / 1e9
