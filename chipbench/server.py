"""Format a data file and run the served ledger as a child process.

`Server` is copied from chip_smoke.py (PR 23) and started through
chipbench/server_launcher.py: `python -m tigerbeetle_tpu format`, then
`start --engine=<the configuration's engine>`, one replica, the only
process that starts a JAX backend. A configuration's `format_args` and
`start_args` ride on the two command lines as they stand. The harness
reads the lines `format` and `start` print.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCHER = os.path.join(ROOT, "chipbench", "server_launcher.py")


class BenchFailure(Exception):
    """The run cannot give a result (not: the result is incorrect)."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# What the harness itself puts on `format`'s and `start`'s command lines
# (the path besides): a configuration's `format_args` / `start_args`
# may name none of them.
HARNESS_ARGS = ("--cluster", "--replica", "--replica-count", "--addresses",
                "--engine", "--small", "--trace")


def format_argv(path: str, small: bool, extra: list[str] = ()) -> list[str]:
    return [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster=0",
            "--replica=0", "--replica-count=1",
            *(["--small"] if small else []), *extra, path]


def start_argv(port: int, path: str, *, engine: str, small: bool,
               span_trace: str | None, extra: list[str] = ()) -> list[str]:
    """The program's own arguments, as the launcher hands them on."""
    return ["start", f"--addresses=127.0.0.1:{port}", "--replica=0",
            f"--engine={engine}", *(["--small"] if small else []),
            *(["--trace", span_trace] if span_trace else []), *extra, path]


def format_data_file(path: str, small: bool,
                     extra: list[str] = ()) -> list[str]:
    """Runs `format`; the lines it printed. An argument it refuses
    fails the run in the program's own words."""
    done = subprocess.run(format_argv(path, small, extra), cwd=ROOT,
                          timeout=300, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    if done.returncode != 0:
        raise BenchFailure(f"format exited ({done.returncode}); it said:\n"
                           + done.stdout[-4000:])
    return done.stdout.splitlines()


class Server:
    """`start --engine=device` as a child: the one process on the chip."""

    def __init__(self, port: int, path: str, workdir: str, *, small: bool,
                 span_trace: str | None, profile: bool,
                 engine: str = "device", launcher: str | None = None,
                 start_args: list[str] = ()):
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        self.log_path = os.path.join(workdir, "server.log")
        self.lines: list[str] = []
        self.proc = subprocess.Popen(
            [sys.executable, launcher or LAUNCHER, "--workdir", workdir,
             *(["--profile"] if profile else []), "--",
             *start_argv(port, path, engine=engine, small=small,
                         span_trace=span_trace, extra=start_args)],
            cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self._cond = threading.Condition()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        with open(self.log_path, "w") as log:
            for line in self.proc.stdout:
                if not line.startswith("commit="):
                    log.write(line)
                    log.flush()
                with self._cond:
                    self.lines.append(line.rstrip("\n"))
                    self._cond.notify_all()
        with self._cond:
            self._cond.notify_all()

    def wait_line(self, pattern: str, timeout_s: float) -> re.Match:
        """First output line matching `pattern` (past lines included)."""
        rx = re.compile(pattern)
        deadline = time.monotonic() + timeout_s
        seen = 0
        with self._cond:
            while True:
                for line in self.lines[seen:]:
                    m = rx.search(line)
                    if m:
                        return m
                seen = len(self.lines)
                if self.proc.poll() is not None and not self._reader.is_alive():
                    raise BenchFailure(
                        f"server exited ({self.proc.returncode}) before "
                        f"printing /{pattern}/; log tail:\n" + self.tail())
                left = deadline - time.monotonic()
                if left <= 0:
                    raise BenchFailure(
                        f"server did not print /{pattern}/ within "
                        f"{timeout_s:.0f}s; log tail:\n" + self.tail())
                self._cond.wait(min(left, 1.0))

    def tail(self) -> str:
        return "\n".join(l for l in self.lines
                         if not l.startswith("commit="))[-4000:]

    def json_line(self, key: str) -> dict:
        for line in reversed(self.lines):
            if line.startswith('{"' + key + '"'):
                return json.loads(line)[key]
        raise BenchFailure(f"server printed no {key} record; log tail:\n"
                           + self.tail())

    def stop(self) -> tuple[dict, dict]:
        """Orderly shutdown; the program's shutdown record and the
        launcher's device record."""
        self.proc.send_signal(signal.SIGINT)
        try:
            self.proc.wait(timeout=180)
        except subprocess.TimeoutExpired:
            raise BenchFailure("server ignored SIGINT for 180s")
        self._reader.join(timeout=10)
        return self.json_line("shutdown"), self.json_line("chipbench_device")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)
        self._reader.join(timeout=10)
