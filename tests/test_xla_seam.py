"""The seams between this framework and the chip, on a CPU host: chip
counting, the environment a granted worker starts with, chip hand-over
between processes, the compile-cache directory, and the context mesh of a
sharded step. No chip is needed: the node advertises fake chips and no
granted worker here ever starts a jax backend."""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu import _jax_env
from ray_tpu.core.node import detect_tpu_chips

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_host(root, pci_groups, vfio_nodes, accel=0):
    """A /dev + /sys tree: one Google PCI function per iommu group in
    `pci_groups`, plus a non-Google function; `vfio_nodes` under /dev/vfio."""
    dev, sys_ = root / "dev", root / "sys"
    (dev / "vfio").mkdir(parents=True)
    for node in vfio_nodes:
        (dev / "vfio" / node).touch()
    for i in range(accel):
        (dev / f"accel{i}").touch()
    pci = sys_ / "bus/pci/devices"
    for slot, group in enumerate(list(pci_groups) + ["9"]):
        fn = pci / f"0000:00:{slot + 8:02x}.0"
        fn.mkdir(parents=True)
        vendor = "0x1ae0" if slot < len(pci_groups) else "0x8086"
        (fn / "vendor").write_text(vendor + "\n")
        os.symlink(f"../../../kernel/iommu_groups/{group}",
                   fn / "iommu_group")
    return str(dev), str(sys_)


def test_detect_tpu_chips_counts_openable_chips(tmp_path, monkeypatch):
    monkeypatch.delenv("RAY_TPU_NUM_TPUS", raising=False)
    # The one-chip v5e machine: four functions on the bus, one group
    # handed to the VM, plus the vfio control node.
    one = _fake_host(tmp_path / "one", "0123", ["3", "vfio"])
    four = _fake_host(tmp_path / "four", "0123",
                      ["0", "1", "2", "3", "vfio"])
    none = _fake_host(tmp_path / "none", "", ["vfio", "9"])
    accel = _fake_host(tmp_path / "accel", "", [], accel=4)
    assert detect_tpu_chips(*one) == 1
    assert detect_tpu_chips(*four) == 4
    assert detect_tpu_chips(*none) == 0
    assert detect_tpu_chips(*accel) == 4


class EnvProbe:
    """Reports its process's view without touching jax."""

    def view(self):
        keys = ("JAX_PLATFORMS", "RAY_TPU_GRANTED_TPU", "TPU_VISIBLE_CHIPS")
        return {"pid": os.getpid(),
                **{k: os.environ.get(k) for k in keys}}


def _tpu_free(raylet):
    return raylet.resources.snapshot()[1].get("TPU", 0.0)


def test_grant_names_the_platform_and_chips_follow_the_pid(monkeypatch):
    """A granted worker names the TPU platform even under a CPU-pinned
    parent; an ungranted one is pinned to CPU; one chip of several is
    narrowed to that chip; and a killed holder's TPU share (and chip)
    is re-granted only once its pid has exited."""
    assert os.environ["JAX_PLATFORMS"] == "cpu"  # the parent's own pin
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=2)
    try:
        raylet = ray_tpu._global_node.raylet
        probe = ray_tpu.remote(EnvProbe)
        plain = ray_tpu.get(probe.remote().view.remote())
        assert plain["JAX_PLATFORMS"] == "cpu"
        assert plain["RAY_TPU_GRANTED_TPU"] is None

        whole = probe.options(num_tpus=2).remote()
        view = ray_tpu.get(whole.view.remote())
        assert view["JAX_PLATFORMS"] == "tpu,cpu"
        assert view["RAY_TPU_GRANTED_TPU"] == "2"
        assert view["TPU_VISIBLE_CHIPS"] is None  # whole host: no narrowing
        assert _tpu_free(raylet) == 0.0

        # Hold back the holder's SIGTERM so its exit is ours to time.
        monkeypatch.setattr(raylet, "_terminate", lambda handle: None)
        ray_tpu.kill(whole)
        got = []  # creation blocks its caller until the chips are free
        waiter = threading.Thread(target=lambda: got.append(ray_tpu.get(
            probe.options(num_tpus=1).remote().view.remote())), daemon=True)
        waiter.start()
        time.sleep(0.7)
        assert os.path.exists(f"/proc/{view['pid']}")
        assert _tpu_free(raylet) == 0.0, "TPU share re-granted before exit"
        assert not got
        monkeypatch.undo()
        os.kill(view["pid"], signal.SIGTERM)
        waiter.join(timeout=20)
        (first,) = got
        assert not os.path.exists(f"/proc/{view['pid']}")
        assert first["JAX_PLATFORMS"] == "tpu,cpu"
        assert first["TPU_VISIBLE_CHIPS"] in ("0", "1")

        # The second single chip is the other index, held at the same time.
        second = ray_tpu.get(probe.options(num_tpus=1).remote().view.remote())
        assert {first["TPU_VISIBLE_CHIPS"],
                second["TPU_VISIBLE_CHIPS"]} == {"0", "1"}
    finally:
        ray_tpu.shutdown()


def test_unsupported_grants_are_refused_by_name():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=4)
    try:
        with pytest.raises(ValueError, match="whole chips"):
            ray_tpu.remote(EnvProbe).options(num_tpus=0.5).remote()
        actor = ray_tpu.remote(EnvProbe).options(num_tpus=2).remote()
        with pytest.raises(Exception, match="one chip or the whole host"):
            ray_tpu.get(actor.view.remote(), timeout=20)
    finally:
        ray_tpu.shutdown()


def test_granted_worker_without_the_chip_fails_at_start(monkeypatch):
    """No hidden fallback: granted chips that jax does not show are an
    error in the call every chip-holding process makes at start-up."""
    monkeypatch.setattr(_jax_env, "enable_compilation_cache", lambda: "")
    monkeypatch.setenv(_jax_env.GRANT_ENV, "1")
    with pytest.raises(RuntimeError, match="granted 1 TPU chip"):
        _jax_env.claim_devices()  # this process: 8 CPU devices


def _fresh_process(code, **env):
    merged = {k: v for k, v in os.environ.items()
              if k != _jax_env.CACHE_ENV}
    merged.update(env, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=merged,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-800:]
    return out.stdout.strip().splitlines()[-1]


_CACHE_PROBE = """
import jax
sets = []
real = jax.config.update
jax.config.update = lambda k, v: (sets.append(k), real(k, v))[1]
from ray_tpu._jax_env import enable_compilation_cache
d = enable_compilation_cache()
print(d, jax.config.jax_compilation_cache_dir,
      "jax_compilation_cache_dir" in sets)
"""


def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path):
    placed = str(tmp_path / "placed")
    assert _fresh_process(_CACHE_PROBE, **{_jax_env.CACHE_ENV: placed}) \
        == f"{placed} {placed} False"
    fixed = os.path.join(REPO, ".jax_cache")
    runs = {_fresh_process(_CACHE_PROBE) for _ in range(2)}
    assert runs == {f"{fixed} {fixed} True"}


def test_sharded_gpt2_step_keeps_its_constraints(monkeypatch):
    """Under a mesh the activation constraints must reach the lowered
    program (they compiled to nothing while flax could not see the mesh),
    and flash attention is traced on each device's own shard."""
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.gpt2 import (GPT2, GPT2Config, init_sharded,
                                     make_train_step)
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
    cfg = dataclasses.replace(GPT2Config.tiny(seq=128), n_embd=256, n_head=4)
    model = GPT2(cfg)
    mesh = build_mesh(MeshSpec({"dp": 4, "tp": 2}))
    params = init_sharded(model, mesh, (8, 128))
    opt = optax.sgd(1e-2)
    ids = jax.ShapeDtypeStruct((8, 128), jnp.int32)
    step = make_train_step(model, opt, mesh=mesh, donate=False)
    attention.reset_pallas_status()
    lowered = step.lower(params, jax.eval_shape(opt.init, params),
                         {"input_ids": ids, "labels": ids})
    n = len(re.findall(r"sharding_constraint|@Sharding", lowered.as_text()))
    assert n > 0, "the sharded step lowered without a sharding constraint"
    shapes = {(e["pass"], tuple(e["shape"]))
              for e in attention.pallas_status()}
    # [b/dp, h/tp, s, d]: the kernels never see the global [8, 4, 128, 64].
    assert shapes == {("fwd", (2, 2, 128, 64)), ("bwd", (2, 2, 128, 64))}
