"""The daemon as a child process, and the readers of what it says
about itself (/debug/vars, /metrics).  Copied in substance from
chip_smoke.py (PR 21), which later PRs may change.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from typing import Dict, Tuple


class HarnessFailure(Exception):
    """The run cannot produce a result.  Ends the run non-zero."""


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def base_env() -> dict:
    """The ambient environment minus every GUBER_* and BENCH_* setting:
    the configuration's file says what the daemon gets."""
    return {
        k: v for k, v in os.environ.items()
        if not k.startswith(("GUBER_", "BENCH_"))
    }


class DaemonChild:
    def __init__(self, launcher: str, root: str, env: dict, log_path: str):
        self.grpc_addr = f"127.0.0.1:{free_port()}"
        self.http_addr = f"127.0.0.1:{free_port()}"
        self.env = dict(
            env, GUBER_GRPC_ADDRESS=self.grpc_addr,
            GUBER_HTTP_ADDRESS=self.http_addr,
        )
        self.launcher, self.root, self.log_path = launcher, root, log_path
        self.proc = None
        self.t_listen = None  # time.time() when the gRPC port first took a connection

    def spawn(self) -> None:
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                [sys.executable, self.launcher], cwd=self.root, env=self.env,
                stdout=log, stderr=subprocess.STDOUT,
                stdin=subprocess.DEVNULL, start_new_session=True,
            )

    def wait_first_answer(self, payload: bytes, timeout: float) -> None:
        """Poll the gRPC listener until one GetRateLimits is answered."""
        import grpc

        from . import wire

        deadline = time.monotonic() + timeout
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise HarnessFailure(
                    f"daemon died with rc={rc} before answering:\n"
                    f"{self.log_tail()}"
                )
            if time.monotonic() > deadline:
                raise HarnessFailure(
                    f"daemon never answered in {timeout:.0f}s:\n"
                    f"{self.log_tail()}"
                )
            if self.t_listen is None:
                host, port = self.grpc_addr.rsplit(":", 1)
                try:
                    socket.create_connection((host, int(port)), 1.0).close()
                except OSError:
                    time.sleep(0.05)
                    continue
                self.t_listen = time.time()
            try:
                with grpc.insecure_channel(self.grpc_addr) as ch:
                    raw = ch.unary_unary(wire.METHOD)(payload, timeout=30.0)
            except grpc.RpcError:
                time.sleep(0.05)
                continue
            answers = wire.decode_response(raw)
            if len(answers) != 1 or answers[0].error:
                raise HarnessFailure(f"first answer wrong: {answers}")
            return

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return "(no log)"

    def debug_vars(self) -> dict:
        with urllib.request.urlopen(
            f"http://{self.http_addr}/debug/vars", timeout=60
        ) as r:
            return json.loads(r.read())

    def metrics(self) -> Dict[Tuple[str, Tuple], float]:
        with urllib.request.urlopen(
            f"http://{self.http_addr}/metrics", timeout=60
        ) as r:
            return parse_prom(r.read().decode())

    def cpu_seconds(self) -> float:
        """User + system CPU time of the daemon process so far."""
        with open(f"/proc/{self.proc.pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")

    def stop(self, timeout: float = 120.0) -> int:
        """SIGTERM and wait: the chip is free only when the process is
        gone.  Returns its exit code."""
        if self.proc is None:
            return 0
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.kill()
                raise HarnessFailure(f"daemon ignored SIGTERM for {timeout}s")
        rc, self.proc = self.proc.returncode, None
        return rc

    def kill(self) -> None:
        if self.proc is None:
            return
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait()
        self.proc = None


_PROM_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)")
_PROM_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prom(text: str) -> Dict[Tuple[str, Tuple], float]:
    """Prometheus text → {(sample name, sorted label pairs): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _PROM_LINE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_PROM_LABEL.findall(m.group(2) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            continue
    return out
