"""The pricing daemon (``python -m repro.serve``) as a run starts it: a
child pinned to the CPU, so that only the benchmark's process holds the
chip, with its socket, invariant cache and log under the run's own output
directory.  Paths are relative to the checkout, the child's working
directory, which keeps the socket path short."""
from __future__ import annotations

import os
import subprocess
import sys
import time

START_TIMEOUT_S = 120


def spawn(root: str, out_dir: str) -> tuple:
    """(process, socket path, log file) of a fresh daemon, not waited
    for: the caller traces while it boots, then calls ``ready``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    sock = os.path.join(out_dir, "serve.sock")
    log = open(os.path.join(out_dir, "serve.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "--socket", sock,
         "--cache-path", os.path.join(out_dir, "serve.invcache")],
        env=env, cwd=root, stdout=log, stderr=subprocess.STDOUT)
    return proc, sock, log


def ready(root: str, proc, sock: str, log) -> None:
    """Wait until the daemon listens on ``sock``."""
    deadline = time.monotonic() + START_TIMEOUT_S
    while not os.path.exists(os.path.join(root, sock)):
        if proc.poll() is not None:
            raise RuntimeError(f"pricing daemon exited {proc.returncode}; "
                               f"see {log.name}")
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"pricing daemon did not start in {START_TIMEOUT_S} s")
        time.sleep(0.02)


def stop(proc, client) -> None:
    """Ask the daemon to drain and exit; kill it if it will not; wait."""
    try:
        if client is not None:
            client.shutdown_server()
            proc.wait(timeout=30)
    except Exception:
        pass
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
