"""The system under test as a child process: ``python -m
vllm_distributed_tpu serve`` started as a user would start it, with the
configuration's own arguments.  The parent that calls this never
initialises a JAX backend while the child lives: a chip belongs to one
process at a time.
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ServerError(RuntimeError):
    pass


def _die_with_parent() -> None:
    """Runs in the child before exec: should the benchmark be killed
    outright, the kernel kills the server too (PR_SET_PDEATHSIG), so a
    run never leaves a process holding the chip."""
    import ctypes

    ctypes.CDLL(None).prctl(1, signal.SIGKILL)


def parse_metrics(text: str) -> dict[str, float]:
    """Sum of each sample name over a Prometheus exposition (labels
    folded together), plus ``name{label="v"}`` entries as written."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = re.match(r"([^\s{]+)(\{[^}]*\})?\s+(\S+)", line)
        if not m:
            continue
        try:
            v = float(m.group(3))
        except ValueError:
            continue
        out[m.group(1)] = out.get(m.group(1), 0.0) + v
        if m.group(2):
            out[m.group(1) + m.group(2)] = v
    return out


class Server:
    def __init__(self, config: dict, seed: int, out_dir: str, *,
                 trace: bool, extra_args: list[str], env: dict) -> None:
        self.out_dir = out_dir
        self.log_path = os.path.join(out_dir, "server.log")
        model_dir = os.path.join(out_dir, "model")
        os.makedirs(model_dir, exist_ok=True)
        with open(os.path.join(model_dir, "config.json"), "w") as f:
            json.dump(config["config"], f)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.argv = [
            sys.executable, "-m", "vllm_distributed_tpu", "serve", model_dir,
            "--load-format", "dummy", "--skip-tokenizer-init",
            "--seed", str(seed), "--host", "127.0.0.1",
            "--port", str(self.port), "--disable-log-requests",
            *config["server_args"], *extra_args,
        ]
        self.profile_dir = os.path.join(out_dir, "profile")
        if trace:
            self.argv += ["--enable-tracing", "--profile-dir",
                          self.profile_dir]
        self.env = dict(os.environ, PYTHONUNBUFFERED="1", **env)
        if trace:
            self.env.update(config.get("trace_env", {}))
        self.proc: subprocess.Popen | None = None
        self.t_spawn = 0.0

    def start(self) -> None:
        with open(self.log_path, "wb") as log:
            self.t_spawn = time.monotonic()
            self.proc = subprocess.Popen(
                self.argv, cwd=REPO, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
                preexec_fn=_die_with_parent,
            )

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def stop(self) -> int | None:
        """SIGTERM, wait; SIGKILL the group if it will not go.  Returns
        the exit code."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        return self.proc.returncode

    def log_facts(self) -> dict:
        """What the server's own log says about how it runs."""
        with open(self.log_path, errors="replace") as f:
            log = f.read()
        facts: dict = {}
        m = re.search(
            r"kernel backends: attention=(\S+) kv_write=(\S+) "
            r"quant_matmul=(\S+) aot_cache=(\S+)", log)
        if m:
            facts["backends"] = dict(zip(
                ("attention", "kv_write", "quant_matmul", "aot_cache"),
                m.groups()))
        m = re.search(
            r"KV pool: (\d+) pages × (\d+) tokens \(([\d.]+) GiB", log)
        if m:
            facts["kv_pool_pages"] = int(m.group(1))
            facts["kv_page_tokens"] = int(m.group(2))
            facts["kv_pool_gib"] = float(m.group(3))
        return facts
