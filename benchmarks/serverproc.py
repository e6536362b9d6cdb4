"""Start, question and stop the one child that holds the chip (trainer_child.py).

No jax here: a parent that has touched JAX holds the chip its child needs.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CTL_PREFIX = "BENCHCTL "
READY_PREFIX = "TRAINER_READY "


class TrainerProcess:
    def __init__(self, repo: Path, server_flags: list[str], log_path: Path,
                 *, launcher: Path | None = None, env: dict | None = None):
        assert "jax" not in sys.modules, "the harness imported jax"
        self.log_path = log_path
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, str(launcher or HERE / "trainer_child.py"), str(repo), "--", *server_flags],
            cwd=repo, stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self._log,
            start_new_session=True, env={**os.environ, **(env or {})},
        )
        self.address: str | None = None
        self._replies: queue.Queue[dict] = queue.Queue()
        self._reader = threading.Thread(target=self._read_stdout, daemon=True)
        self._reader.start()

    def _read_stdout(self) -> None:
        for raw in self.proc.stdout:
            line = raw.decode(errors="replace").rstrip("\n")
            if line.startswith(CTL_PREFIX):
                self._replies.put(json.loads(line[len(CTL_PREFIX):]))
            elif line.startswith(READY_PREFIX):
                self.address = line.split()[1]

    def wait_ready(self, deadline: float) -> str:
        while self.address is None:
            if self.proc.poll() is not None:
                raise RuntimeError(f"trainer exited rc={self.proc.returncode}:\n{self.log_tail()}")
            if time.monotonic() > deadline:
                raise RuntimeError(f"trainer not ready in time:\n{self.log_tail()}")
            time.sleep(0.05)
        return self.address

    def ctl(self, cmd: str, *, timeout: float = 120.0, **kw) -> dict:
        self.proc.stdin.write((json.dumps({"cmd": cmd, **kw}) + "\n").encode())
        self.proc.stdin.flush()
        try:
            out = self._replies.get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError(f"trainer child gave no reply to {cmd!r} in {timeout:.0f}s") from None
        if "error" in out:
            raise RuntimeError(f"trainer child failed {cmd!r}: {out['error']}")
        return out

    def log_tail(self, n: int = 30) -> str:
        try:
            return "\n".join(self.log_path.read_text(errors="replace").splitlines()[-n:])
        except OSError:
            return ""

    def stop(self) -> None:
        """SIGTERM (the server's own shutdown path), then SIGKILL to the whole
        group, and wait: nothing started here outlives the run."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass
        self._reader.join(timeout=5)
        self._log.close()
