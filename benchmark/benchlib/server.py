"""The system under test: `python -m elasticsearch_tpu.rest.server`, started
as the harness's one child. The child holds the chip; the harness never
touches JAX while it lives."""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time

from .client import Client


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, root: str, data_path: str, log_path: str,
                 cache_dir: str, env: dict | None = None):
        self.root, self.data_path, self.log_path = root, data_path, log_path
        self.env = dict(os.environ, PYTHONPATH=root, PYTHONFAULTHANDLER="1",
                        JAX_COMPILATION_CACHE_DIR=cache_dir, **(env or {}))
        self.proc = None
        self.port = None

    def start(self, timeout: float = 300.0) -> Client:
        self.port = free_port()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "elasticsearch_tpu.rest.server",
                 "--port", str(self.port), "--data-path", self.data_path],
                cwd=self.root, env=self.env, stdout=log,
                stderr=subprocess.STDOUT)
        c = Client(self.port)
        t0 = time.perf_counter()
        while True:
            try:
                c.call("GET", "/")
                return c
            except (ConnectionError, OSError):
                if self.proc.poll() is not None:
                    raise RuntimeError(
                        f"the server exited with {self.proc.returncode}; "
                        f"log: {self.log_path}") from None
                if time.perf_counter() - t0 > timeout:
                    raise RuntimeError(
                        f"the server did not come up in {timeout:.0f} s") from None
                time.sleep(0.25)

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def stop(self) -> None:
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.proc = None

    def log_tail(self, lines: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                keep = [ln for ln in f.read().splitlines()
                        if "jax._src." not in ln]
        except OSError:
            return ""
        return "\n".join(keep[-lines:])
