"""A chip has one holder at a time, and the program waits for the
hand-over at both ends (ISSUE 47): `procutil.stop_process` is the one
"stop a process and see it reaped", `WorkerPool.kill_all` waits for a
chip-holding worker until it IS reaped, and `claim_devices()` waits for a
device node that is still being let go. No chip here: the processes are
Popen-shaped fakes on a fake clock, the device nodes files under
`tmp_path`.
"""

import errno
import logging
import os
import signal
import subprocess
import sys
import types

import pytest

from ray_tpu import _jax_env
from ray_tpu.core import procutil
from ray_tpu.core.ids import WorkerID
from ray_tpu.core.raylet import Raylet, WorkerHandle, WorkerPool
from ray_tpu.observability import tracing


class FakeClock:
    """`time` as the code under test sees it: sleeping is advancing."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self):
        return self.now

    def sleep(self, s):
        self.now += s


class FakeProc:
    """Popen-shaped: ignores SIGTERM, and is reaped `reaped_after` seconds
    after `kill()` (never, for None). Waiting advances the clock."""

    def __init__(self, clock, pid, reaped_after):
        self.clock, self.pid, self.reaped_after = clock, pid, reaped_after
        self.returncode = None
        self.killed_at = None
        self.signals = []

    def poll(self):
        if self.returncode is None and self.killed_at is not None \
                and self.reaped_after is not None \
                and self.clock.now >= self.killed_at + self.reaped_after:
            self.returncode = -9
        return self.returncode

    def terminate(self):
        self.signals.append("term")

    def kill(self):
        self.signals.append("kill")
        if self.killed_at is None:
            self.killed_at = self.clock.now

    def wait(self, timeout=None):
        if self.poll() is not None:
            return self.returncode
        if self.killed_at is not None and self.reaped_after is not None and \
                self.killed_at + self.reaped_after <= self.clock.now + timeout:
            self.clock.now = self.killed_at + self.reaped_after
            return self.poll()
        self.clock.now += timeout
        raise subprocess.TimeoutExpired("fake", timeout)


@pytest.fixture()
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(procutil, "time", c)
    return c


@pytest.fixture()
def fresh_ring():
    tracing._forget_lifecycle_for_tests()
    yield
    tracing._forget_lifecycle_for_tests()


def _pool_with(monkeypatch, clock, proc, tpu_chips):
    """A pool whose one worker is `proc`, on the fake clock."""
    import ray_tpu.core.raylet as raylet_mod

    monkeypatch.setattr(raylet_mod, "time", clock)
    pool = WorkerPool(types.SimpleNamespace(
        _terminate=Raylet._terminate, _see_reaped=Raylet._see_reaped))
    wid = WorkerID.from_random()
    pool._workers[wid] = WorkerHandle(worker_id=wid, pid=proc.pid, proc=proc,
                                      tpu_chips=tpu_chips)
    return pool


def _exit_spans():
    spans, _ = tracing.LIFECYCLE.snapshot()
    return [s for s in spans if s["name"] == "worker.exit"]


def test_kill_all_returns_after_a_chip_holder_is_reaped(
        monkeypatch, clock, fresh_ring):
    """Reaped 7 s after SIGKILL: at the parent kill_all gave up after 2 s
    and returned with the worker, and its chips, still there."""
    proc = FakeProc(clock, pid=4242, reaped_after=7.0)
    pool = _pool_with(monkeypatch, clock, proc, tpu_chips=(0, 1, 2, 3))
    t0 = clock.now
    pool.kill_all()
    assert proc.returncode is not None, "kill_all returned before the reap"
    assert proc.signals == ["term", "kill"]
    assert clock.now - t0 == pytest.approx(3.0 + 7.0)
    (span,) = _exit_spans()
    assert span["attrs"] == {"pid": 4242, "tpu_chips": 4, "signal": "kill",
                             "wait_s": 10.0, "reaped": True}
    assert span["end"] - span["start"] == pytest.approx(10.0)


def test_kill_all_gives_a_chip_holder_up_at_the_bound_and_says_so(
        monkeypatch, clock, fresh_ring, caplog):
    proc = FakeProc(clock, pid=4243, reaped_after=None)
    pool = _pool_with(monkeypatch, clock, proc, tpu_chips=(0,))
    t0 = clock.now
    with caplog.at_level(logging.WARNING, logger="ray_tpu.core.raylet"):
        pool.kill_all()
    assert clock.now - t0 == pytest.approx(3.0 + procutil.CHIP_GONE_BY_S)
    (span,) = _exit_spans()
    assert span["attrs"]["reaped"] is False
    assert span["attrs"]["signal"] == "kill"
    (warning,) = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert "4243" in warning.getMessage()
    assert "not reaped" in warning.getMessage()


def test_a_worker_without_chips_is_given_up_after_seconds_not_a_minute(
        monkeypatch, clock, fresh_ring, caplog):
    proc = FakeProc(clock, pid=4244, reaped_after=None)
    pool = _pool_with(monkeypatch, clock, proc, tpu_chips=())
    t0 = clock.now
    with caplog.at_level(logging.WARNING, logger="ray_tpu.core.raylet"):
        pool.kill_all()
    assert clock.now - t0 == pytest.approx(3.0 + procutil.GONE_BY_S)
    assert procutil.GONE_BY_S <= 5.0 < procutil.CHIP_GONE_BY_S
    assert _exit_spans() == []      # the span is the chip-holder's
    assert sum("4244" in r.getMessage() for r in caplog.records) == 1


def test_kill_all_signals_every_worker_before_it_waits_for_any(
        monkeypatch, clock, fresh_ring):
    """The waits overlap: two TERM-deaf workers cost one grace window."""
    procs = [FakeProc(clock, pid=4250 + i, reaped_after=0.5) for i in (0, 1)]
    pool = _pool_with(monkeypatch, clock, procs[0], tpu_chips=())
    wid = WorkerID.from_random()
    pool._workers[wid] = WorkerHandle(worker_id=wid, pid=procs[1].pid,
                                      proc=procs[1])
    t0 = clock.now
    pool.kill_all()
    assert [p.signals for p in procs] == [["term", "kill"]] * 2
    assert clock.now - t0 == pytest.approx(3.0 + 0.5 + 0.5)


# --------------------------------------------------------------------- #
# stop_process on real processes (the copy the forge template and
# kill_group's tail used to have)
# --------------------------------------------------------------------- #

_DEAF = ("import signal, sys, time; "
         "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
         "print('up', flush=True); time.sleep(60)")
_POLITE = "import time; print('up', flush=True); time.sleep(60)"


@pytest.mark.parametrize("code, signal_name", [(_POLITE, "term"),
                                               (_DEAF, "kill")],
                         ids=["leaves-on-term", "deaf-to-term"])
def test_stop_process_sees_a_real_process_reaped(code, signal_name):
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE)
    assert proc.stdout.readline().strip() == b"up"
    stopped = procutil.stop_process(proc, grace_s=0.5)
    proc.stdout.close()
    assert stopped.signal == signal_name and stopped.reaped
    assert proc.returncode == (-signal.SIGTERM if signal_name == "term"
                               else -signal.SIGKILL)
    assert not os.path.exists(f"/proc/{proc.pid}")
    assert stopped.wait_s < 5.0


def test_stop_process_does_not_pretend(clock):
    proc = FakeProc(clock, pid=1, reaped_after=None)
    stopped = procutil.stop_process(proc, grace_s=2.0, gone_by_s=1.0)
    assert stopped == procutil.Stopped("kill", 3.0, False)
    said = procutil.unreaped("tester", os.getpid(), 1.0)
    assert f"tester {os.getpid()} is not reaped 1 s after SIGKILL " \
        "(state R)" in said
    assert "(state ?)" in procutil.unreaped("x", 2 ** 22 + 1, 1.0)


# --------------------------------------------------------------------- #
# the other end: a process with a grant waits for its device nodes
# --------------------------------------------------------------------- #


@pytest.fixture()
def accel_root(tmp_path, monkeypatch):
    """Two chips under the accel driver, and this process granted both."""
    for n in (0, 1):
        (tmp_path / f"accel{n}").write_bytes(b"")
    monkeypatch.setenv(_jax_env.GRANT_ENV, "2")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    c = FakeClock()
    monkeypatch.setattr(_jax_env, "time", c)
    return tmp_path, c


def _opener(monkeypatch, answers):
    """`_open_node` that raises the listed errnos in turn, then opens."""
    opened = []

    def fake(node):
        opened.append(node)
        if answers:
            code = answers.pop(0)
            raise OSError(code, os.strerror(code), node)
    monkeypatch.setattr(_jax_env, "_open_node", fake)
    return opened


def test_claim_devices_waits_for_a_busy_chip_and_starts_the_backend_once(
        accel_root, monkeypatch, fresh_ring):
    root, clock = accel_root
    opened = _opener(monkeypatch, [errno.EBUSY, errno.EBUSY])
    starts = []
    monkeypatch.setattr(_jax_env, "enable_compilation_cache", lambda: "")
    monkeypatch.setattr(_jax_env, "device_info", lambda: (
        starts.append(1),
        {"platform": "tpu", "device_kind": "fake", "n_devices": 2})[1])
    real = _jax_env.wait_for_granted_chips
    monkeypatch.setattr(_jax_env, "wait_for_granted_chips",
                        lambda: real(dev_root=str(root), sys_root=str(root)))
    info = _jax_env.claim_devices()
    assert info["n_devices"] == 2 and len(starts) == 1
    assert opened == [str(root / "accel0")] * 3 + [str(root / "accel1")]
    spans, _ = tracing.LIFECYCLE.snapshot()
    (span,) = [s for s in spans if s["name"] == "jax.claim_devices"]
    assert span["attrs"]["chip_wait_s"] == pytest.approx(0.2)
    assert span["attrs"]["backend_s"] == pytest.approx(0.0, abs=0.05)


def test_a_chip_busy_past_the_bound_is_an_error_that_names_the_node(
        accel_root, monkeypatch):
    root, clock = accel_root
    _opener(monkeypatch, [errno.EBUSY] * 10_000)
    t0 = clock.now
    with open(root / "accel0"), \
            pytest.raises(RuntimeError, match="accel0.*still busy") as e:
        _jax_env.wait_for_granted_chips(str(root), str(root))
    assert clock.now - t0 == pytest.approx(procutil.CHIP_GONE_BY_S, abs=0.2)
    assert f"held by pid {os.getpid()} (" in str(e.value)   # /proc/*/fd
    with pytest.raises(RuntimeError, match="not reaped yet"):
        _jax_env.wait_for_granted_chips(str(root), str(root))


@pytest.mark.parametrize("code", [errno.EACCES, errno.ENOENT],
                         ids=["EACCES", "ENOENT"])
def test_any_other_error_of_a_device_node_is_raised_at_once(
        accel_root, monkeypatch, code):
    root, clock = accel_root
    opened = _opener(monkeypatch, [code])
    t0 = clock.now
    with pytest.raises(OSError) as e:
        _jax_env.wait_for_granted_chips(str(root), str(root))
    assert e.value.errno == code and len(opened) == 1 and clock.now == t0


def test_a_process_with_no_grant_opens_nothing(accel_root, monkeypatch):
    root, clock = accel_root
    monkeypatch.delenv(_jax_env.GRANT_ENV)
    opened = _opener(monkeypatch, [])
    assert _jax_env.wait_for_granted_chips(str(root), str(root)) == 0.0
    assert opened == []


def test_one_granted_chip_of_a_vfio_host_probes_its_own_group(
        tmp_path, monkeypatch):
    """`TPU_VISIBLE_CHIPS` as the grant sets it narrows the nodes: each
    Google PCI function's IOMMU group under /dev/vfio, in the order of
    the groups' numbers (the runtime's chip order on the v5e 2x2 host)."""
    dev, sys_root = tmp_path / "dev", tmp_path / "sys"
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").write_bytes(b"")
    for addr, group in [("0000:00:04.0", "10"), ("0000:00:05.0", "3")]:
        pci = sys_root / "bus/pci/devices" / addr
        pci.mkdir(parents=True)
        (pci / "vendor").write_text("0x1ae0\n")
        (sys_root / "kernel/iommu_groups" / group).mkdir(parents=True)
        os.symlink(sys_root / "kernel/iommu_groups" / group,
                   pci / "iommu_group")
        (dev / "vfio" / group).write_bytes(b"")
    assert _jax_env.tpu_device_nodes(str(dev), str(sys_root)) == [
        str(dev / "vfio" / "3"), str(dev / "vfio" / "10")]
    from ray_tpu.core.node import detect_tpu_chips

    monkeypatch.delenv("RAY_TPU_NUM_TPUS", raising=False)
    assert detect_tpu_chips(str(dev), str(sys_root)) == 2
    monkeypatch.setenv(_jax_env.GRANT_ENV, "1")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "1")
    opened = _opener(monkeypatch, [])
    assert _jax_env.wait_for_granted_chips(str(dev), str(sys_root)) == 0.0
    assert opened == [str(dev / "vfio" / "10")]
    monkeypatch.undo()
    # The real open, on a node nobody holds: nothing to wait for.
    monkeypatch.setenv(_jax_env.GRANT_ENV, "1")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    assert _jax_env.wait_for_granted_chips(str(dev), str(sys_root)) == 0.0
