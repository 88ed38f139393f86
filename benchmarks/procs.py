"""Process hygiene of a benchmark run: everything the run starts is tagged
through the environment, found again whoever its parent is by then, and
gone before the result is printed. Copied from `chip_smoke.py`
(`started_processes` / `stop_everything`), which stays the smoke test."""

from __future__ import annotations

import os
import signal
import time

MARK = "RAY_TPU_BENCH_RUN"


def tag_this_tree() -> None:
    """Every process started after this inherits the tag."""
    os.environ[MARK] = str(os.getpid())


def started_processes() -> dict:
    """{pid: command} of the live processes this run started."""
    want = f"{MARK}={os.getpid()}".encode()
    found = {}
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                if want not in f.read().split(b"\0"):
                    continue        # a zombie reads empty: it is not counted
            with open(f"/proc/{name}/cmdline", "rb") as f:
                found[int(name)] = f.read().replace(b"\0", b" ").decode()[:120]
        except OSError:
            continue                # gone while we looked
    return found


def stop_everything() -> dict:
    """Cluster down, forge templates stopped, nothing this run started
    still alive. Returns what had to be killed ({} when the system's own
    shutdown left nothing)."""
    import ray_tpu
    from ray_tpu.core import worker_forge

    ray_tpu.shutdown()
    worker_forge.kill_templates()
    deadline = time.monotonic() + 10
    while (left := started_processes()) and time.monotonic() < deadline:
        time.sleep(0.1)
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    deadline = time.monotonic() + 5
    while left and started_processes() and time.monotonic() < deadline:
        time.sleep(0.1)
    return left
