"""Serve layer: deploy/route/batch/autoscale/HTTP round-trip.

Mirrors the reference's serve test strategy (`serve/tests/` —
test_deployment_state for reconcile, test_autoscaling_policy for scaling,
plus e2e HTTP tests) at the scale of one in-process cluster.
"""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture()
def serve_cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_deploy_and_call(serve_cluster):
    @serve.deployment(num_replicas=2)
    class Echo:
        def __init__(self, prefix):
            self._prefix = prefix

        def __call__(self, payload):
            return f"{self._prefix}:{payload}"

    handle = serve.run(Echo.bind("echo"))
    results = ray_tpu.get([handle.remote(i) for i in range(8)])
    assert results == [f"echo:{i}" for i in range(8)]

    st = serve.status()
    assert st["Echo"]["target"] == 2
    assert len(st["Echo"]["replicas"]) == 2


def test_function_deployment_and_methods(serve_cluster):
    @serve.deployment
    def double(payload):
        return payload * 2

    handle = serve.run(double.bind())
    assert ray_tpu.get(handle.remote(21)) == 42

    @serve.deployment
    class Multi:
        def __call__(self, x):
            return ("call", x)

        def other(self, x):
            return ("other", x)

    h2 = serve.run(Multi.bind())
    assert ray_tpu.get(h2.remote(1)) == ("call", 1)
    assert ray_tpu.get(h2.other.remote(2)) == ("other", 2)


def test_batching_collects_concurrent_requests(serve_cluster):
    @serve.deployment(max_concurrent_queries=16)
    class Batcher:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.1)
        async def __call__(self, items):
            # Return the batch size each item rode in — proof of batching.
            return [len(items)] * len(items)

    handle = serve.run(Batcher.bind())
    refs = [handle.remote(i) for i in range(8)]
    sizes = ray_tpu.get(refs)
    # At least some requests must have shared a batch.
    assert max(sizes) > 1


def test_replica_failure_recovers(serve_cluster):
    @serve.deployment(num_replicas=1)
    class Fragile:
        def __call__(self, payload):
            return payload

        def pid(self, _=None):
            import os

            return os.getpid()

    handle = serve.run(Fragile.bind())
    pid = ray_tpu.get(handle.pid.remote(None))

    # Kill the replica out from under the controller.
    replica = ray_tpu.get_actor("SERVE_REPLICA::Fragile#0",
                                namespace="serve")
    ray_tpu.kill(replica)

    # The controller's health check must replace it and serving resume.
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            new_pid = ray_tpu.get(handle.pid.remote(None), timeout=5.0)
            if new_pid != pid:
                break
        except Exception:
            time.sleep(0.2)
    else:
        pytest.fail("replica was not replaced after kill")


def test_autoscaling_up_and_down(serve_cluster):
    @serve.deployment(
        max_concurrent_queries=2,
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1, max_replicas=3, target_ongoing_requests=1.0,
            upscale_delay_s=0.2, downscale_delay_s=1.0),
    )
    class Slow:
        def __call__(self, payload):
            time.sleep(0.4)
            return payload

    handle = serve.run(Slow.bind())
    assert serve.status()["Slow"]["target"] == 1

    # Sustained pressure: many concurrent requests -> scale up.
    refs = [handle.remote(i) for i in range(16)]
    deadline = time.time() + 20
    scaled_up = False
    while time.time() < deadline:
        if serve.status()["Slow"]["target"] > 1:
            scaled_up = True
            break
        time.sleep(0.1)
    assert scaled_up, "deployment did not scale up under load"
    ray_tpu.get(refs)

    # Idle -> back down to min_replicas.
    deadline = time.time() + 20
    while time.time() < deadline:
        if serve.status()["Slow"]["target"] == 1:
            break
        time.sleep(0.2)
    else:
        pytest.fail("deployment did not scale back down when idle")


def test_http_proxy_round_trip(serve_cluster):
    @serve.deployment(num_replicas=2, route_prefix="/math")
    class Adder:
        def __call__(self, payload):
            return {"sum": payload["a"] + payload["b"]}

    serve.run(Adder.bind())
    port = serve.http_port()

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/math",
        data=json.dumps({"a": 2, "b": 3}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        body = json.loads(resp.read())
    assert body == {"result": {"sum": 5}}

    # Unknown route -> 404.
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/nope", timeout=30)
        pytest.fail("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_handle_composition_between_deployments(serve_cluster):
    @serve.deployment
    class Inner:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Outer:
        def __init__(self, inner):
            self._inner = inner

        def __call__(self, x):
            return ray_tpu.get(self._inner.remote(x)) * 10

    serve.run(Inner.bind())
    outer = serve.run(Outer.bind(serve.get_deployment_handle("Inner")))
    assert ray_tpu.get(outer.remote(4)) == 50


def test_llm_server_deployment_batches(serve_cluster):
    """Eight concurrent requests to the one language-model deployment share
    decode steps: the engine's step ledger says more than one row a step."""
    from ray_tpu.inference import LLMServer

    # Generous deploy budget: the replica's first requests jit-compile a
    # tiny Llama's two programs, which can exceed the 60s default when the
    # host is loaded.
    handle = serve.run(LLMServer.bind(
        "tiny", 64, 4,
        engine_config={"batch_slots": 4, "block_size": 8, "num_blocks": 33,
                       "max_blocks_per_seq": 8, "prefill_chunk": 8}),
        timeout_s=180.0)
    refs = [handle.remote({"ids": [1, 2, 3 + i], "max_new_tokens": 4})
            for i in range(8)]
    outs = ray_tpu.get(refs)
    for i, out in enumerate(outs):
        assert out["ids"][:3] == [1, 2, 3 + i]
        assert len(out["ids"]) == 7
    m = ray_tpu.get(handle.metrics.remote(None))
    assert m["requests_finished"] == 8
    steps = m["steps"]
    assert steps["decode"] >= 1
    assert steps["decode_rows"] / steps["decode"] > 1.0, \
        "batching never engaged"


def test_deployment_graph_composition(serve_cluster):
    """Bound deployments as init args deploy as a graph (children first)
    and arrive as live DeploymentHandles (reference deployment graphs)."""

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return 2 * x

    @serve.deployment
    class Adder:
        def __init__(self, offset):
            self.offset = offset

        def __call__(self, x):
            return x + self.offset

    @serve.deployment
    class Driver:
        def __init__(self, doubler, adder):
            self.doubler = doubler
            self.adder = adder

        def __call__(self, x):
            d = ray_tpu.get(self.doubler.remote(x))
            return ray_tpu.get(self.adder.remote(d))

    handle = serve.run(Driver.bind(Doubler.bind(), Adder.bind(100)))
    assert ray_tpu.get(handle.remote(7)) == 114
    # Name collision across distinct bindings is rejected.
    with pytest.raises(ValueError):
        serve.run(Driver.options(name="D2").bind(
            Adder.bind(1), Adder.bind(2)))
    # Container-nested bindings (a LIST of bound models) deploy too.
    @serve.deployment
    class Ensemble:
        def __init__(self, models):
            self.models = models

        def __call__(self, x):
            return sum(ray_tpu.get(m.remote(x)) for m in self.models)

    ens = serve.run(Ensemble.bind([
        Adder.options(name="AddA").bind(1),
        Adder.options(name="AddB").bind(2)]))
    assert ray_tpu.get(ens.remote(10)) == 23


def test_graph_init_args_pass_through_untouched(serve_cluster):
    """Init args with no nested bindings keep their exact types (dict
    subclasses included); bindings hidden in sets fail loudly at deploy
    time instead of reaching the replica as inert pickled data."""
    import collections

    @serve.deployment
    class KeepsDefaultDict:
        def __init__(self, counts):
            self.counts = counts

        def __call__(self, key):
            self.counts[key].append(1)
            return len(self.counts[key])

    dd = collections.defaultdict(list)
    h = serve.run(KeepsDefaultDict.bind(dd))
    assert ray_tpu.get(h.remote("a")) == 1
    assert ray_tpu.get(h.remote("a")) == 2  # default_factory survived

    @serve.deployment
    class Adder:
        def __init__(self, n):
            self.n = n

        def __call__(self, x):
            return x + self.n

    @serve.deployment
    class SetEnsemble:
        def __init__(self, models):
            self.models = models

    with pytest.raises(ValueError, match="un-substituted"):
        serve.run(SetEnsemble.bind({Adder.bind(1), Adder.bind(2)}))


def test_schema_build_validate_deploy(serve_cluster, tmp_path):
    """serve.build -> edit -> deploy_config round trip (reference
    serve build / REST deploy), with per-deployment overrides applied."""
    import serve_app_mod

    from ray_tpu.serve.schema import build, deploy_config, validate_config

    cfg = build(serve_app_mod.app)
    deps = {d["name"] for d in cfg["applications"][0]["deployments"]}
    assert deps == {"Doubler", "Pipeline"}

    config = {
        "applications": [{
            "name": "default",
            "import_path": "serve_app_mod:app",
            "deployments": [
                {"name": "Doubler", "num_replicas": 2,
                 "max_concurrent_queries": 16},
            ],
        }],
    }
    validate_config(config)
    handle = deploy_config(config)
    assert ray_tpu.get(handle.remote(10)) == 25  # 2*10 + 5

    st = serve.status()
    assert st["Doubler"]["target"] == 2  # override applied
    # The module-level objects were not mutated by the override.
    assert serve_app_mod.Doubler.config.num_replicas == 1

    with pytest.raises(ValueError, match="unknown deployment option"):
        validate_config({"applications": [{
            "import_path": "serve_app_mod:app",
            "deployments": [{"name": "Doubler", "replicas": 2}]}]})
    with pytest.raises(ValueError, match="import_path"):
        validate_config({"applications": [{"name": "x"}]})


def test_serve_cli_deploy_and_status(serve_cluster, tmp_path):
    """The serve CLI deploys from YAML against a running cluster."""
    import yaml

    from ray_tpu.scripts.cli import main

    cfg = {"applications": [{"name": "default",
                             "import_path": "serve_app_mod:app"}]}
    path = str(tmp_path / "serve.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    addr = ray_tpu._require_runtime().gcs.address
    main(["--address", f"{addr[0]}:{addr[1]}", "serve", "deploy", path])
    handle = serve.get_deployment_handle("Pipeline")
    assert ray_tpu.get(handle.remote(1)) == 7


def test_controller_crash_recovery(serve_cluster):
    """The controller's state lives in the GCS KV: killing the controller
    actor and touching the API again rebuilds deployments and re-adopts
    (or respawns) replicas without redeploying (reference controller.py:75
    checkpointed state + kv_store.py)."""
    from ray_tpu.serve import _get_or_create_controller

    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, payload):
            return f"echo:{payload}"

    handle = serve.run(Echo.bind())
    assert ray_tpu.get(handle.remote("a")) == "echo:a"

    controller = _get_or_create_controller(create=False)
    ray_tpu.kill(controller)
    time.sleep(1.0)

    # Any API touch creates a fresh controller which restores from the KV.
    deadline = time.monotonic() + 90
    status = {}
    while time.monotonic() < deadline:
        try:
            status = serve.status()
            reps = status.get("Echo", {}).get("replicas", {})
            if sum(1 for s in reps.values() if s == "RUNNING") >= 2:
                break
        except Exception:
            pass
        time.sleep(0.5)
    reps = status.get("Echo", {}).get("replicas", {})
    assert sum(1 for s in reps.values() if s == "RUNNING") >= 2, status

    # And traffic flows again through a fresh handle.
    h2 = serve.get_deployment_handle("Echo")
    assert ray_tpu.get(h2.remote("b"), timeout=60) == "echo:b"


def test_per_node_proxies_and_replacement():
    """EveryNode proxy placement: one managed proxy per alive node,
    health-checked and replaced when killed (reference http_state.py:110
    HTTPProxyStateManager)."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.serve import _get_or_create_controller
    from ray_tpu.serve.controller import SERVE_NAMESPACE

    ray_tpu.shutdown()
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    cluster.add_node(num_cpus=4)
    cluster.wait_for_nodes()
    cluster.connect()
    try:
        serve.start(http_port=0, proxy_location="EveryNode")

        @serve.deployment(num_replicas=1)
        class Hello:
            def __call__(self, payload):
                return "hi"

        serve.run(Hello.bind())
        controller = _get_or_create_controller(create=False)

        def proxy_view(min_alive, timeout=60):
            deadline = time.monotonic() + timeout
            view = {}
            while time.monotonic() < deadline:
                view = ray_tpu.get(controller.proxy_status.remote(),
                                   timeout=30)
                if sum(1 for v in view.values() if v["alive"]) >= min_alive:
                    return view
                time.sleep(0.5)
            return view

        view = proxy_view(2)
        alive = [v for v in view.values() if v["alive"]]
        assert len(alive) == 2, view
        # Each proxy serves HTTP on its own port.
        for v in alive:
            url = f"http://127.0.0.1:{v['port']}/Hello"
            req = urllib.request.Request(url, data=json.dumps("x").encode(),
                                         headers={"Content-Type":
                                                  "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.read().decode() == "hi"

        # Kill one managed proxy: the controller replaces it.
        victim_node = next(iter(view))
        victim = ray_tpu.get_actor(f"SERVE_PROXY::{victim_node[:16]}",
                                   namespace=SERVE_NAMESPACE)
        ray_tpu.kill(victim)
        view2 = proxy_view(2, timeout=90)
        assert sum(1 for v in view2.values() if v["alive"]) == 2, view2
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def test_batch_queue_stop_fails_pending_and_cancels_flusher():
    """Satellite: _BatchQueue.stop() must cancel the flusher task and
    fail every parked future — queued AND mid-batch — instead of leaking
    them past replica shutdown."""
    import asyncio

    from ray_tpu.serve.batching import _BatchQueue

    async def main():
        started = asyncio.Event()
        release = asyncio.Event()

        async def fn(items):
            started.set()
            await release.wait()
            return items

        q = _BatchQueue(fn, max_batch_size=2, batch_wait_timeout_s=10.0)
        t1 = asyncio.ensure_future(q.submit(1))
        t2 = asyncio.ensure_future(q.submit(2))
        await started.wait()           # flusher is mid-batch, parked in fn
        flusher = q._flusher
        assert q.stop() == 2
        with pytest.raises(RuntimeError, match="shut down"):
            await t1
        with pytest.raises(RuntimeError, match="shut down"):
            await t2
        for _ in range(5):             # let the cancellation land
            await asyncio.sleep(0)
        assert flusher.done()
        # A stopped queue refuses new work instead of parking it forever.
        with pytest.raises(RuntimeError, match="stopped"):
            await q.submit(3)

    asyncio.run(main())


def test_replica_teardown_stops_batch_queue_and_runs_shutdown_hook():
    """prepare_shutdown tears down user-side resources: batch queues are
    stopped (their parked callers fail fast) and __serve_shutdown__ runs."""
    import asyncio

    from ray_tpu.serve.replica import Replica

    events = []

    class User:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=30.0)
        async def __call__(self, items):
            return items

        def __serve_shutdown__(self):
            events.append("shutdown")

    async def main():
        rep = Replica("D", User, (), {})
        task = asyncio.ensure_future(
            rep.handle_request("__call__", (1,), {}))
        await asyncio.sleep(0.05)      # flusher parked in its batch wait
        await rep.prepare_shutdown(timeout_s=0.2)
        with pytest.raises(RuntimeError, match="shut down"):
            await task
        assert events == ["shutdown"]

    asyncio.run(main())
