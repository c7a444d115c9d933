"""What every generator shares: the child process that holds the chip, the
compile-cache counter, and small arithmetic. Stdlib only: the process that
imports this never touches JAX.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")

NO_DEVICE_RC = 3  # chip_child.py's exit code for "not the chips the cell asks for"


class BenchFailure(Exception):
    """The run cannot give a result; the message says why. run.py exits
    non-zero and prints no result line."""


class NoDevice(BenchFailure):
    """JAX found no accelerator, or not the chips the cell asks for."""


def log(msg: str) -> None:
    print(f"benchmarks: {msg}", file=sys.stderr, flush=True)


def percentile(values, q: float) -> float:
    """Linear-interpolated q-th percentile (0-100); same rule as numpy's
    default and as ``bench._percentile``, which this replaces for the
    benchmark (PERF.md section 7 lists the original for deletion)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def load_module(path: str):
    """Import one file of the benchmark by its path: a reader, a reference.
    They are found by name from ``BENCHMARK.json`` and the data files, so
    none of them is imported by a fixed ``import`` statement."""
    name = "benchmarks_" + os.path.relpath(path, HERE)[:-3].replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def compile_cache_dir() -> str:
    """Where the run's persistent compilation cache lives: the directory the
    environment names, else the program's own default inside the checkout
    (``runtime/distributed.DEFAULT_COMPILE_CACHE_DIR``). A fixed path: the
    path is part of the cache's key."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_compile_cache")


def compile_cache_entries() -> int:
    """Programs in the persistent cache. The program caches every compile
    (thresholds at zero), so a difference across the window is the number
    of compilations inside it."""
    try:
        return sum(1 for n in os.listdir(compile_cache_dir()) if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0


def child_env(allow_cpu: bool, chips: int) -> dict:
    env = dict(os.environ)
    if allow_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if chips > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                + f" --xla_force_host_platform_device_count={chips}")
    else:
        # Whatever was inherited: the child runs on the TPU or fails.
        env["JAX_PLATFORMS"] = "tpu"
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


class Child:
    """One ``chip_child.py`` process: the program behind the benchmark's
    launcher. Its stderr is read line by line on a thread, each line stamped
    with the harness's monotonic clock as it arrives, copied to a log file,
    and offered to ``on_line``."""

    def __init__(self, *, role: str, run_dir: str, workload: str, chips: int,
                 config_path: str, spec: dict, argv: list[str],
                 allow_cpu: bool = False, on_line=None):
        os.makedirs(run_dir, exist_ok=True)
        self.run_dir = run_dir
        self.on_line = on_line
        self._next_id = 0
        cmd = [sys.executable, os.path.join(HERE, "chip_child.py"),
               "--role", role, "--out", run_dir, "--chips", str(chips),
               "--workload", workload, "--config", config_path,
               "--verdicts", os.path.join(OUT, "rehearsal" if allow_cpu else "reference"),
               "--spec", json.dumps(spec)]
        if allow_cpu:
            cmd.append("--allow-cpu")
        cmd += ["--"] + argv
        log(f"$ {' '.join(cmd)}")
        self.stdout_path = os.path.join(run_dir, "child.stdout")
        self._stdout = open(self.stdout_path, "w")
        self._log = open(os.path.join(run_dir, "child.stderr"), "w")
        # Its own session, so that stop() reaches everything it started.
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=child_env(allow_cpu, chips),
            stdin=subprocess.PIPE, stdout=self._stdout, stderr=subprocess.PIPE,
            text=True, start_new_session=True)
        self._reader = threading.Thread(target=self._read_stderr, daemon=True)
        self._reader.start()

    def _read_stderr(self) -> None:
        for line in self.proc.stderr:
            t = time.monotonic()
            self._log.write(line)
            self._log.flush()
            if self.on_line is not None:
                self.on_line(t, line)

    def check_alive(self, what: str) -> None:
        rc = self.proc.poll()
        if rc is None:
            return
        if rc == NO_DEVICE_RC and not os.path.exists(
                os.path.join(self.run_dir, "device.json")):
            raise NoDevice("the child found no accelerator, or not the chips the cell asks for")
        raise BenchFailure(f"the child exited with code {rc} {what}; see "
                           f"{os.path.join(self.run_dir, 'child.stderr')}")

    def wait_file(self, name: str, timeout_s: float, what: str) -> dict:
        path = os.path.join(self.run_dir, name)
        deadline = time.monotonic() + timeout_s
        while not os.path.exists(path):
            self.check_alive(f"before writing {name}")
            if time.monotonic() > deadline:
                raise BenchFailure(f"{what}: no {name} within {timeout_s:.0f}s")
            time.sleep(0.02)
        with open(path) as f:
            return json.load(f)

    def command(self, op: str, timeout_s: float = 120.0, **kw) -> dict:
        """Send one command to the launcher's control thread; wait for its
        reply."""
        self._next_id += 1
        cid = self._next_id
        self.proc.stdin.write(json.dumps({"id": cid, "op": op, **kw}) + "\n")
        self.proc.stdin.flush()
        reply = self.wait_file(f"reply-{cid}.json", timeout_s, f"command {op}")
        if not reply.get("ok"):
            raise BenchFailure(f"command {op} failed in the child: {reply.get('error')}")
        return reply

    def stop(self, term_timeout_s: float = 0.0) -> None:
        """End the child and everything it started, and wait for it.
        ``term_timeout_s`` > 0 tries SIGTERM first (the server drains)."""
        if self.proc.poll() is None and term_timeout_s > 0:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=term_timeout_s)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._reader.join(timeout=5)
        for fh in (self._stdout, self._log, self.proc.stdin, self.proc.stderr):
            try:
                fh.close()
            except OSError:
                pass


def device_block(child: Child) -> dict:
    """The result line's ``device``: as the child reported it. A traced run
    adds ``busy_s`` and ``window_s`` once the trace is reduced."""
    dev = child.wait_file("device.json", 10, "device")
    return {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
            "memory_peak_bytes": child.command("memory")["memory_peak_bytes"]}


def model_override_args(config: dict, role: str) -> list[str]:
    """``X=Y`` ModelConfig overrides of a configuration for a role, in the
    order the program applies them."""
    merged = {**config.get("model_overrides", {}),
              **config.get(f"{role}_overrides", {})}
    return [f"{k}={v}" for k, v in merged.items()]
