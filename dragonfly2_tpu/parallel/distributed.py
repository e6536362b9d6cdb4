"""Multi-process (multi-host) initialization.

North-star configs 3/4 run one JAX process per TPU-VM host (v5e-16 /
v5p-64); collectives ride ICI between chips and DCN between hosts. This
module is the process-bootstrap layer for that topology:

  initialize()              — jax.distributed wrapper (coordinator + N
                              processes), env- or argument-driven, with the
                              CPU-simulation knobs needed to exercise the
                              SAME code path on a laptop/CI: each process
                              hosts `local_device_count` virtual CPU devices
                              and cross-process collectives run over Gloo.
  launch_localhost()        — spawn an n-process cluster on 127.0.0.1 for
                              tests and dry runs (the "cluster-in-a-box"
                              strategy, SURVEY.md §4).

The reference has no multi-process compute story (its distribution plane is
gRPC + goroutines, SURVEY.md §2.4); this is where the TPU build adds one.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from dataclasses import dataclass
from typing import Sequence

_ENV_COORD = "DF_DIST_COORDINATOR"
_ENV_NPROCS = "DF_DIST_NUM_PROCESSES"
_ENV_PROC_ID = "DF_DIST_PROCESS_ID"
_ENV_LOCAL_DEVICES = "DF_DIST_LOCAL_DEVICES"


@dataclass
class DistributedConfig:
    """One process's view of the cluster. num_processes == 1 → no-op init."""

    coordinator_address: str = ""
    num_processes: int = 1
    process_id: int = 0
    # >0 → simulate this many virtual CPU devices in this process (CI mode);
    # 0 → use the real local platform (TPU chips on a pod host).
    local_device_count: int = 0

    @classmethod
    def from_env(cls) -> "DistributedConfig":
        return cls(
            coordinator_address=os.environ.get(_ENV_COORD, ""),
            num_processes=int(os.environ.get(_ENV_NPROCS, "1")),
            process_id=int(os.environ.get(_ENV_PROC_ID, "0")),
            local_device_count=int(os.environ.get(_ENV_LOCAL_DEVICES, "0")),
        )

    def env(self) -> dict[str, str]:
        return {
            _ENV_COORD: self.coordinator_address,
            _ENV_NPROCS: str(self.num_processes),
            _ENV_PROC_ID: str(self.process_id),
            _ENV_LOCAL_DEVICES: str(self.local_device_count),
        }


def initialize(cfg: DistributedConfig | None = None) -> None:
    """Initialize jax.distributed for this process (idempotent-ish: call once,
    before any other JAX use; backend selection freezes at first device touch).

    CPU-simulation mode (local_device_count > 0) must set the XLA flag and
    platform BEFORE the first backend initialization.
    """
    cfg = cfg or DistributedConfig.from_env()
    if cfg.local_device_count > 0:
        _force_cpu_devices(cfg.local_device_count)
    if cfg.num_processes <= 1:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=cfg.coordinator_address,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )


def _force_cpu_devices(count: int) -> None:
    """Give this process >= `count` virtual CPU devices.

    Must run before the first backend initialization (the flag is read once);
    an existing smaller count in XLA_FLAGS is raised in place so a process
    that inherited the test conftest's 8 can still request 16+. Where no
    platform is named the process is pinned to cpu (else a TPU host would
    pick the chip and the flag would do nothing); a platform the caller
    NAMED (jax_platforms / JAX_PLATFORMS) is never overridden — if it is not
    cpu the device-count check at the call site fails and says so.
    """
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={count}"
        ).strip()
    elif int(m.group(1)) < count:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={count}"
        )
    import jax

    if not (jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS")):
        jax.config.update("jax_platforms", "cpu")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_localhost(
    num_processes: int,
    module: str,
    *,
    local_devices: int = 4,
    extra_env: dict[str, str] | None = None,
    args: Sequence[str] = (),
    timeout: float = 600.0,
) -> list[subprocess.CompletedProcess]:
    """Run `python -m <module> <args>` as an n-process localhost cluster.

    Each process gets the DF_DIST_* env (coordinator on a free port) plus
    `local_devices` virtual CPU devices. Returns the completed processes in
    process-id order; raises if any exits nonzero.

    `timeout` is ONE wall-clock budget for the whole cluster, not a fresh
    allowance per process: a deadlocked collective stalls every process, and
    N sequential full timeouts would multiply the wait by N (a tier-1 run
    lost most of its budget to exactly that before this was a deadline).
    """
    import time as _time

    deadline = _time.monotonic() + timeout
    coord = f"127.0.0.1:{free_port()}"
    procs: list[subprocess.Popen] = []
    for pid in range(num_processes):
        cfg = DistributedConfig(
            coordinator_address=coord,
            num_processes=num_processes,
            process_id=pid,
            local_device_count=local_devices,
        )
        env = dict(os.environ)
        # scrub ambient single-process JAX config; the worker sets its own
        env.pop("JAX_PLATFORMS", None)
        env.pop("XLA_FLAGS", None)
        env.update(cfg.env())
        env.update(extra_env or {})
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", module, *args],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    done: list[subprocess.CompletedProcess] = []
    failed: list[str] = []
    for pid, p in enumerate(procs):
        try:
            remaining = max(1.0, deadline - _time.monotonic())
            out, err = p.communicate(timeout=remaining)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            failed.append(f"process {pid} timed out ({timeout}s cluster budget)")
        done.append(subprocess.CompletedProcess(p.args, p.returncode, out, err))
        if p.returncode != 0:
            failed.append(
                f"process {pid} rc={p.returncode}: {(err or '').strip()[-500:]}"
            )
    if failed:
        raise RuntimeError("localhost cluster failed:\n" + "\n".join(failed))
    return done
