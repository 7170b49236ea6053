"""Backend `model`: the whole served path as one child process,
`python -m ggrmcp_tpu gateway --tpu --config <written file>` (gateway,
gRPC over a Unix socket, sidecar, batcher, paged KV, jitted model), the
path chip_smoke.py proved. The harness never imports JAX; this child is
the one process that holds the chip while it lives.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

GENERATE = "ggrmcp_tpu_generateservice_generate"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def stop_process(proc: subprocess.Popen) -> None:
    """SIGTERM the child's process group, wait, SIGKILL what remains."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def child_env(root: str, cpu: bool) -> dict:
    """The child's environment. The compile cache stays where
    JAX_COMPILATION_CACHE_DIR says, else the program's own fixed
    `<checkout>/.jax_cache`. On the chip JAX_PLATFORMS=cpu is removed:
    the program then refuses to start without a TPU."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    elif env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        del env["JAX_PLATFORMS"]
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    return env


class Stack:
    """One gateway+sidecar child for one run."""

    def __init__(self, root: str, out_dir: str, config: dict, cpu: bool,
                 ready_s: float) -> None:
        self.root, self.cpu, self.ready_s = root, cpu, ready_s
        self.port = free_port()
        os.makedirs(out_dir, exist_ok=True)
        self.log_path = os.path.join(out_dir, "stack.log")
        self.cfg_path = os.path.join(out_dir, "serving_config.json")
        with open(self.cfg_path, "w") as f:
            json.dump(config, f, indent=1)
        self.proc: subprocess.Popen | None = None
        self._log = None

    def start(self) -> None:
        cmd = [sys.executable, "-m", "ggrmcp_tpu", "gateway", "--tpu",
               "--config", self.cfg_path, "--http-port", str(self.port)]
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            cmd, cwd=self.root, env=child_env(self.root, self.cpu),
            stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        deadline = time.monotonic() + self.ready_s
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"the stack exited with code {self.proc.returncode} "
                    f"before it served (no TPU?); last lines of "
                    f"{self.log_path}:\n{self.log_tail(8)}"
                )
            try:
                tools = self.rpc("tools/list", {})["tools"]
                if any(t["name"] == GENERATE for t in tools):
                    return
            except (OSError, RuntimeError, ValueError):
                pass  # not listening yet, or the sidecar not discovered
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"the stack was not ready after {self.ready_s:.0f} s"
                )
            time.sleep(0.5)

    def rpc(self, method: str, params: dict, timeout: float = 5.0) -> dict:
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/",
            data=json.dumps({
                "jsonrpc": "2.0", "method": method, "id": 1, "params": params,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            reply = json.loads(resp.read())
        if "error" in reply:
            raise RuntimeError(f"{method}: {reply['error']}")
        return reply["result"]

    def stop(self) -> None:
        if self.proc is not None:
            stop_process(self.proc)
            self.proc = None
        if self._log is not None:
            self._log.close()
            self._log = None

    def log_tail(self, lines: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(
                    "  | " + ln[:300].rstrip() + "\n"
                    for ln in f.readlines()[-lines:]
                )
        except OSError:
            return ""


def launch(root: str, out_dir: str, config: dict, cpu: bool,
           ready_s: float) -> Stack:
    stack = Stack(root, out_dir, config, cpu, ready_s)
    try:
        stack.start()
    except BaseException:
        stack.stop()
        raise
    return stack
