"""Which process may open the accelerator, and where compiled code is kept.

A TPU chip belongs to one process at a time. On a TPU-VM host the trainer is
the only service that trains; the scheduler, daemon, manager and the CLIs can
still reach JAX (the scheduler's JAX scorer fallback, `artifacts.load_gnn`'s
`model.init`), and the first backend use in any of them would open the chip
and take it from the trainer. So:

  pin_host_cpu()          every host-side `main()` calls this first.
  enable_compile_cache()  every entry point that opens the chip calls this
                          first.
  op_names_in_cache_key() around the first call of a program whose op names
                          the device trace is read by.
  device_report()         what the process that holds the chip says it holds.

Nothing here runs at import and the module itself never imports jax at the
top, so a JAX-free parent (chip_smoke.py) can use `compile_cache_dir()`.
"""

from __future__ import annotations

import contextlib
import os
import re
import sys
from pathlib import Path
from typing import Iterator

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — the path is part of the cache key, so it is one
# fixed place, never a tempdir, a pid or a timestamp (git-ignored)
_REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def pin_host_cpu() -> None:
    """Hold this process's JAX to the host CPU. Call before first backend
    use (backend choice freezes there); safe whether or not jax is imported
    yet. The env var also reaches any child this process spawns."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def compile_cache_dir() -> Path:
    """The persistent compile cache directory this checkout uses: wherever
    JAX_COMPILATION_CACHE_DIR points when it is set, else the fixed
    in-checkout path."""
    env = os.environ.get(CACHE_ENV)
    return Path(env) if env else _REPO_CACHE


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache and return its directory.
    With JAX_COMPILATION_CACHE_DIR set, JAX reads the directory itself and
    no other is set in code; otherwise the fixed in-checkout path is
    configured. Source file names in a program's metadata are made relative
    to the checkout, for `op_names_in_cache_key`."""
    import jax

    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex", re.escape(str(_REPO_CACHE.parent) + os.sep)
    )
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    return compile_cache_dir()


@contextlib.contextmanager
def op_names_in_cache_key() -> Iterator[None]:
    """A program first called (so compiled, or loaded from the persistent
    cache) inside this context gets a cache key that holds its op names and
    source lines. By default JAX leaves them out, and a cached program comes
    back with the names it was compiled with: the training step compiled
    before a scope existed (models/graphsage.STEP_SCOPES) would be served
    for the one that has it, and the device trace, which is read by those
    names, would not find them. The price, for the program so keyed: an edit
    that moves a line on its call path compiles once more (a checkout in
    another directory still hits: enable_compile_cache). Kept to the one
    program whose names are read: the many small ones keep their keys."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


def peak_device_bytes() -> int | None:
    """Highest `peak_bytes_in_use` over the local devices since the process
    started, or None where the backend keeps no memory statistics (CPU)."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def device_report() -> dict:
    """Platform, device kind and device count as JAX reports them. Opens the
    backend: call it only in the process that is meant to hold the device."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
