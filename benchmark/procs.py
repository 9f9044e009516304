"""Child processes, HTTP and `/metrics` parsing for the parent of a
run. Stdlib only (the process shape is `chip_smoke.py`'s: the parent
stays off JAX, starts one chip-holding child at a time and reaps it)."""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple


class Fail(Exception):
    """The run cannot produce a result: exit non-zero, print none."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(url: str, body=None, timeout: float = 60.0, method=None
         ) -> Tuple[int, object]:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw, code = r.read(), r.status
    except urllib.error.HTTPError as e:
        raw, code = e.read(), e.code
    try:
        return code, json.loads(raw)
    except ValueError:
        return code, raw.decode("utf-8", "replace")


class Child:
    """One child process with its log file; always reaped."""

    def __init__(self, name: str, argv: List[str], env: Dict[str, str],
                 cwd: str, log_dir: str):
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            argv, cwd=cwd, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def tail(self, n: int = 30) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def wait(self, timeout: float) -> int:
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise Fail(f"{self.name} did not finish in {timeout:.0f} s:\n"
                       f"{self.tail()}")

    def stop(self, grace: float = 20.0) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                pass
        # the whole session, so that nothing a child started outlives it
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(10)
        except subprocess.TimeoutExpired:
            pass
        if not self._log.closed:
            self._log.close()


def wait_healthy(child: Child, url: str, timeout: float) -> Dict:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if child.proc.poll() is not None:
            raise Fail(f"{child.name} exited rc={child.proc.returncode} "
                       f"before serving:\n{child.tail()}")
        try:
            code, body = http(url + "/health", timeout=2.0)
            if code == 200 and isinstance(body, dict):
                return body
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.25)
    raise Fail(f"{child.name} not healthy after {timeout:.0f} s:\n"
               f"{child.tail()}")


_SAMPLE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})? ([0-9.eE+-]+|NaN|[+-]Inf)$")


def parse_metrics(text: str) -> Dict[str, float]:
    """Prometheus text -> {"name{labels}": value} (labels verbatim)."""
    out = {}
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                pass
    return out


def scrape(url: str) -> Dict[str, float]:
    code, text = http(url + "/metrics", timeout=10.0)
    if code != 200 or not isinstance(text, str):
        raise Fail(f"/metrics answered {code}")
    return parse_metrics(text)


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
