"""The JAX seam of a worker process: which devices it may use, and where
its compiled programs are kept.

Platform selection is the plain `JAX_PLATFORMS` variable, which jax reads
at import. The raylet sets it at spawn (`core/raylet.py`
`_spawn_env_delta`): `cpu` for a worker without a TPU grant, `tpu,cpu` for
one with, so a granted worker never inherits a parent's `cpu` pin and jax
fails at backend start-up when the chip cannot be opened. No second knob
exists: nothing on the installed stack rewrites `jax_platforms` behind the
variable's back.
"""

from __future__ import annotations

import errno
import glob
import os
import time
from typing import Dict, List

from ray_tpu.core.procutil import CHIP_GONE_BY_S

GRANT_ENV = "RAY_TPU_GRANTED_TPU"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# One fixed path inside the checkout (git-ignored). The directory is part
# of the cache key, so it is never derived from a temp name, pid or time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def compilation_cache_dir() -> str:
    """The persistent compile-cache directory this program uses, computed
    without importing jax."""
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory. Where `JAX_COMPILATION_CACHE_DIR` is set jax has already
    read it and no directory is set in code; otherwise the cache goes to
    `DEFAULT_CACHE_DIR`. Every entry is kept, however small or quick: a
    fresh process on a chip pays each cold compile again otherwise."""
    import jax

    if not os.environ.get(CACHE_ENV):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return compilation_cache_dir()


def device_info() -> Dict[str, object]:
    """What this process computes on, as jax reports it. Initializes the
    backend; every stats/report path that names a device reads this
    (so a process that computes without a grant to claim, a CPU replica,
    has its programs timed from here on: `observability/compile.py`)."""
    from ray_tpu.observability import compile as _compile

    _compile.install()
    import jax

    devices = jax.local_devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices)}


def granted_tpu_chips() -> int:
    """Chips the raylet granted this process at spawn (0 = none)."""
    return int(os.environ.get(GRANT_ENV) or 0)


_GOOGLE_PCI_VENDOR = "0x1ae0"


def tpu_device_nodes(dev_root: str = "/dev",
                     sys_root: str = "/sys") -> List[str]:
    """The device node of every local TPU chip, found without importing
    jax, in the runtime's chip order (`TPU_VISIBLE_CHIPS=2` opened
    `/dev/vfio/2` on a host whose PCI order is 2, 3, 1, 0: the node's
    number, not the bus's).

    A chip is a device node: `/dev/accel<N>` under the accel driver, or
    under vfio the `/dev/vfio/<group>` node of a Google PCI function's
    IOMMU group. Neither half alone is right: the PCI bus lists every
    function of the board even when the VM was handed one group (a
    one-chip v5e machine shows four), and `/dev/vfio/*` also matches the
    `vfio` control node.
    """
    accels = glob.glob(os.path.join(dev_root, "accel[0-9]*"))
    if accels:
        return sorted(accels, key=lambda p: int(p.rpartition("accel")[2]))
    groups = []
    for dev in glob.glob(os.path.join(sys_root, "bus/pci/devices/*")):
        try:
            with open(os.path.join(dev, "vendor")) as f:
                vendor = f.read().strip()
            group = os.path.basename(
                os.readlink(os.path.join(dev, "iommu_group")))
        except OSError:
            continue  # unreadable entry, or a function outside the IOMMU
        if vendor == _GOOGLE_PCI_VENDOR and os.path.exists(
                os.path.join(dev_root, "vfio", group)):
            groups.append(int(group))
    return [os.path.join(dev_root, "vfio", str(g)) for g in sorted(groups)]


def _open_node(node: str) -> None:
    os.close(os.open(node, os.O_RDWR))


def _holder_of(node: str) -> str:
    """Who has `node` open, as far as `/proc/*/fd` shows."""
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            if os.readlink(fd) == node:
                pid = fd.split("/")[2]
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode()[:120]
                return f"held by pid {pid} ({cmd.strip()})"
        except OSError:
            continue  # gone while we looked, or not ours to read
    return ("no process shows it among its open files: one that was "
            "killed and is not reaped yet is still closing it")


def wait_for_granted_chips(dev_root: str = "/dev",
                           sys_root: str = "/sys") -> float:
    """Before the backend starts: see that the device nodes this
    process's grant lets it open CAN be opened. A chip has one holder at a
    time, and the holder before (a worker that was stopped, a program that
    was killed) keeps it until the kernel has closed its files, seconds
    after it died; the runtime fails outright on such a chip. On EBUSY,
    and on nothing else, wait and look again, for as long as the other end
    waits for a chip-holder to be reaped. Returns the seconds waited: 0.0
    in every ordinary start, and without a grant, which opens nothing."""
    if not granted_tpu_chips():
        return 0.0
    nodes = tpu_device_nodes(dev_root, sys_root)
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        nodes = [nodes[int(i)] for i in visible.split(",")
                 if int(i) < len(nodes)]
    t0 = time.monotonic()
    deadline = t0 + CHIP_GONE_BY_S
    busy = False
    for node in nodes:
        while True:
            try:
                _open_node(node)
                break
            except OSError as e:
                if e.errno != errno.EBUSY:
                    raise
                busy = True
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"TPU device node {node} is still busy after "
                        f"{CHIP_GONE_BY_S:.0f} s: {_holder_of(node)}") from e
                time.sleep(0.1)
    return time.monotonic() - t0 if busy else 0.0


def claim_devices() -> Dict[str, object]:
    """Start-up of a process that is about to compute with jax: enable
    the compile cache, then hold the process to its grant. A worker
    spawned with a TPU grant must see exactly the granted chips on
    platform `tpu` — anything else (a CPU fallback, a neighbour's chips)
    is an error here, not a slow or wrong number later. Returns
    `device_info()` plus the cache directory.

    This is also where a process's start-up timeline learns about jax:
    the call is a lifecycle span (`jax.claim_devices`, with the seconds
    of the jax import, of the wait for a chip that was still being let
    go, and of the backend coming up as attributes), and
    from here on every program's trace, lowering and compile is timed
    (`observability/compile.py`)."""
    from ray_tpu.observability import compile as _compile
    from ray_tpu.observability import tracing as _tracing

    with _tracing.get_tracer().lifecycle_span(
            "jax.claim_devices", always=True) as span:
        t0 = time.monotonic()
        _compile.install()          # imports jax
        t1 = time.monotonic()
        cache_dir = enable_compilation_cache()
        chip_wait_s = wait_for_granted_chips()
        span.set_attr("chip_wait_s", round(chip_wait_s, 3))
        info = device_info()        # starts the backend, once
        span.set_attr("jax_import_s", round(t1 - t0, 3))
        span.set_attr("backend_s",
                      round(time.monotonic() - t1 - chip_wait_s, 3))
        span.set_attr("platform", info["platform"])
        span.set_attr("n_devices", info["n_devices"])
        granted = granted_tpu_chips()
        if granted and (info["platform"] != "tpu"
                        or info["n_devices"] != granted):
            raise RuntimeError(
                f"worker was granted {granted} TPU chip(s) but jax reports "
                f"{info['n_devices']} local device(s) on platform "
                f"{info['platform']!r} ({info['device_kind']}); "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
                f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r}")
    return {**info, "compilation_cache_dir": cache_dir}
