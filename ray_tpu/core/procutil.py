"""Kill hygiene: the one "stop a process and see it reaped"
(`stop_process`: the raylet's worker pool, the forge template and
`kill_group`'s tail), and the process-group form of it shared by every
entrypoint supervisor.

`kill_group` was factored out of `job_submission/manager.py` (the PR-4
kill handshake) so the per-node job agent and the legacy in-GCS
JobManager escalate identically: SIGTERM the group, wait out a grace
window keyed on GROUP liveness (not the direct child's), then SIGKILL
survivors and confirm the group is gone before returning.
"""

from __future__ import annotations

import logging
import os
import subprocess
import time
from typing import Any, NamedTuple, Optional

logger = logging.getLogger(__name__)

# Seconds past its SIGKILL (which cannot be trapped: this is the kernel's
# teardown alone) a process is waited for before the caller is told it is
# still there.
GONE_BY_S = 2.0
# The same for a process that holds TPU chips: it closes them in that
# teardown and nobody else can open them until it is reaped. Measured on
# v5e (PERF.md §6, PR 47): 4.8-6.1 s with one chip open, 18.0-23.1 s with
# four, so about 5.3 s a chip and ~42 s for a host of eight. The bound is
# over five times the four-chip reading and only keeps a hung kernel from
# hanging shutdown() for ever.
CHIP_GONE_BY_S = 120.0


class Stopped(NamedTuple):
    signal: str     # the last one it took to go: "term" or "kill"
    wait_s: float   # from the first signal to the reap, or to giving up
    reaped: bool


def stop_process(proc: Any, grace_s: float, gone_by_s: float = GONE_BY_S,
                 term_sent_at: Optional[float] = None) -> Stopped:
    """SIGTERM `proc` (anything Popen-shaped), give it `grace_s` to exit,
    SIGKILL it if it has not, and wait until `wait()` returns or
    `gone_by_s` more have passed: a process is gone when it is REAPED.
    Past `gone_by_s` it has been killed and is not (the kernel is still
    closing its files): `reaped=False`, for the caller to log
    (`unreaped`). A caller that stops several signals them all first
    and passes the `time.monotonic()` of that, so the waits overlap."""
    def _send(signal_fn):
        try:
            signal_fn()
        except OSError:
            pass  # exited and reaped since we looked
    t0 = term_sent_at
    if t0 is None:
        t0 = time.monotonic()
        _send(proc.terminate)
    sent, reaped = "term", True
    try:
        proc.wait(timeout=max(0.0, t0 + grace_s - time.monotonic()))
    except subprocess.TimeoutExpired:
        sent = "kill"
        _send(proc.kill)
        try:
            proc.wait(timeout=gone_by_s)
        except subprocess.TimeoutExpired:
            reaped = False
    return Stopped(sent, time.monotonic() - t0, reaped)


def unreaped(what: str, pid: int, gone_by_s: float) -> str:
    """The WARNING of a caller whose `stop_process` came back unreaped,
    with the state letter of `/proc/<pid>/stat` (`D`: in the kernel,
    uninterruptible; `Z`: the main thread has exited; `?`: unreadable)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            state = f.read().rpartition(")")[2].split()[0]
    except (OSError, IndexError):
        state = "?"
    return (f"{what} {pid} is not reaped {gone_by_s:.0f} s after SIGKILL "
            f"(state {state}): whatever it holds open stays held")


def kill_group(proc: subprocess.Popen, grace_s: float = 3.0) -> None:
    """SIGTERM the entrypoint's process group, then SIGKILL whatever
    part of it outlives grace_s: a TERM-trapping driver must not
    survive shutdown or park the waiting runner thread forever.

    The direct child is the `sh -c` wrapper (shell=True), and its
    death says nothing about the group — the shell dies on TERM
    while a TERM-trapping python driver it spawned survives in the
    same group. So the escalation is keyed on GROUP liveness, probed
    with killpg(pgid, 0): while any member lives the pgid (== the
    leader's pid, via start_new_session=True) cannot be recycled, so
    a positive probe means the KILL lands on our group, never on a
    stranger whose group reused a freed pid. The probe and the
    signal cannot be fully atomic — the residual window is the
    microseconds between them, within which the whole pid space
    would have to wrap for the signal to land elsewhere."""
    def _sig(sig, fallback):
        try:
            os.killpg(proc.pid, sig)
        except OSError:
            try:
                fallback()
            except OSError:
                pass  # exited and reaped in between
    _sig(15, proc.terminate)
    if not wait_group_dead(proc, grace_s):
        _sig(9, proc.kill)
        # Confirm the group is actually gone before returning: callers
        # join the killing thread as their proof of kill delivery, and
        # one that exits the process the moment we return must not race
        # the SIGKILLed survivors' death. Bounded — SIGKILL cannot be
        # trapped, so this only waits out the kernel teardown and
        # init's zombie reap.
        wait_group_dead(proc, GONE_BY_S)
    # The direct child: reaped along the way as a rule, seen reaped here.
    if not stop_process(proc, grace_s=0.0).reaped:
        logger.warning(unreaped("entrypoint", proc.pid, GONE_BY_S))


def wait_group_dead(proc: subprocess.Popen, timeout_s: float) -> bool:
    """Poll until no member of the entrypoint's process group remains
    (killpg(pgid, 0) -> ESRCH), reaping the direct child along the
    way. False if the group still has members after timeout_s."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            os.killpg(proc.pid, 0)
        except OSError:
            return True  # whole group exited (and was reaped)
        if time.monotonic() >= deadline:
            return False
        if proc.returncode is None:
            try:
                proc.wait(timeout=0.1)  # reap the shell + pace the poll
            except subprocess.TimeoutExpired:
                pass
        else:
            time.sleep(0.05)  # child reaped; poll surviving group
