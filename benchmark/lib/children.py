"""The harness's child processes: started, watched and always stopped.

The process that holds the chip runs the node and the harness's clock;
what feeds and probes the node lives in children that never import jax
(`JAX_PLATFORMS=cpu` in their environment besides, so that an import by
accident could not reach for the chip).  Each child is its own session:
stopping it kills its whole group (the source child's signing workers
with it).  A child that loses its parent reads end-of-file on stdin and
ends by itself.
"""

from __future__ import annotations

import json
import os
import select
import signal
import subprocess
import sys
import time


class Children:
    def __init__(self, root: str):
        self.root = root
        self.procs: list[subprocess.Popen] = []

    def start(self, module: str, *args: str) -> subprocess.Popen:
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=self.root)
        env.pop("XLA_FLAGS", None)
        p = subprocess.Popen(
            [sys.executable, "-m", module, *args], cwd=self.root, env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, bufsize=0,
            start_new_session=True)
        p.pending = b""               # bytes read past the last line
        self.procs.append(p)
        return p

    @staticmethod
    def send_json_line(p: subprocess.Popen, obj) -> None:
        p.stdin.write(json.dumps(obj).encode() + b"\n")

    @staticmethod
    def read_json_line(p: subprocess.Popen, timeout: float, what: str):
        """One JSON line from a child's stdout, or an error naming what
        was waited for (the child's death included)."""
        deadline = time.monotonic() + timeout
        while True:
            while b"\n" in p.pending:
                line, p.pending = p.pending.split(b"\n", 1)
                if line.lstrip().startswith(b"{"):
                    return json.loads(line)
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(f"no answer from {what} in {timeout:.0f}s")
            ready, _, _ = select.select([p.stdout], [], [], min(left, 1.0))
            if ready:
                chunk = os.read(p.stdout.fileno(), 1 << 20)
                if not chunk:
                    raise RuntimeError(f"{what} ended (exit code "
                                       f"{p.wait()}) without answering")
                p.pending += chunk

    def stop_all(self) -> None:
        """Kill every child's group and wait until each has ended."""
        for p in self.procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        for p in self.procs:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        for p in self.procs:
            p.wait()
            p.stdout.close()
        self.procs = []
