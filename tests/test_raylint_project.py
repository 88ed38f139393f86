"""raylint whole-program tests: RL014-RL017, the incremental cache, the
SARIF/exit-code contract, the unused-suppression audit, and the mutation
negative-controls.

The fixture pairs follow test_raylint.py's discipline (flag the bad
snippet, stay quiet on the prescribed fix).  The mutation controls are
the important novelty: they lint a COPY of the live package with one
real registration / knob declaration / confinement annotation deleted
and assert the corresponding rule fires — proving the project graph
resolves the actual codebase, not just these fixtures.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import pytest

from ray_tpu.analysis.engine import lint_file, lint_paths_full

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "ray_tpu")


def write_tree(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def lint_tree(tmp_path, files, rules=None):
    root = write_tree(tmp_path, files)
    return lint_paths_full([str(root)], rules).findings


def rule_ids(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------------------ RL014

RL014_SERVER = """
    class Gcs:
        def __init__(self, server):
            server.register("get_thing", self._handle_get)
            server.register_raw("blob_get", self._handle_blob)
            server.register_instance(self, prefix="client_")

        def _handle_get(self, conn, data):
            return {"ok": True}

        def _handle_blob(self, conn, payload):
            return payload

        def handle_hello(self, conn, data=None):
            return {}
"""


def test_rl014_flags_unregistered_call(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/server.py": RL014_SERVER,
        "pkg/client.py": 'def f(c):\n    return c.call("get_thingg", {})\n',
    }, rules=["RL014"])
    unregistered = [f for f in findings if "no server registers" in f.message]
    assert len(unregistered) == 1
    assert "get_thingg" in unregistered[0].message
    assert unregistered[0].path.endswith("client.py")


def test_rl014_quiet_on_registered_call_and_prefix_expansion(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/server.py": RL014_SERVER,
        "pkg/client.py": """
            def f(c):
                c.call("get_thing", {})
                c.call_raw("blob_get", b"x")
                return c.call("client_hello")
        """,
    }, rules=["RL014"])
    assert findings == []


def test_rl014_flags_lane_mismatch_both_directions(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/server.py": RL014_SERVER,
        "pkg/client.py": """
            def f(c):
                c.call_raw("get_thing", b"x")   # pickled handler, raw call
                return c.call("blob_get", {})   # raw handler, pickled call
        """,
    }, rules=["RL014"])
    mismatches = [f for f in findings if "lane mismatch" in f.message]
    assert len(mismatches) == 2


def test_rl014_flags_handler_arity(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/server.py": """
            class Srv:
                def __init__(self, server):
                    server.register("narrow", self._narrow)

                def _narrow(self, conn):
                    return {}
        """,
        "pkg/client.py": 'def f(c):\n    return c.call("narrow", {})\n',
    }, rules=["RL014"])
    assert any("handler(conn, data)" in f.message for f in findings)


def test_rl014_quiet_on_conn_data_signatures(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/server.py": """
            class Srv:
                def __init__(self, server):
                    server.register("a", self._a)
                    server.register("b", lambda conn, data: {})

                def _a(self, conn, data=None):
                    return {}
        """,
        "pkg/client.py": """
            def f(c):
                c.call("a")
                return c.call("b")
        """,
    }, rules=["RL014"])
    assert findings == []


def test_rl014_flags_dead_endpoint(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/server.py": """
            def serve(server, handler):
                server.register("orphan", handler)
        """,
    }, rules=["RL014"])
    assert rule_ids(findings) == ["RL014"]
    assert "dead endpoint" in findings[0].message


def test_rl014_dead_quiet_on_literal_reference_elsewhere(tmp_path):
    # A dispatch-table mention counts: wrappers like
    # `self._call("collective_take", ...)` reach endpoints the
    # call-site index can't see.
    findings = lint_tree(tmp_path, {
        "pkg/server.py": """
            def serve(server, handler):
                server.register("orphan", handler)
        """,
        "pkg/client.py": 'METHODS = ["orphan"]\n',
    }, rules=["RL014"])
    assert findings == []


def test_rl014_dead_quiet_on_direct_handler_call(tmp_path):
    # In-process injectors call handle_* methods directly (the chaos
    # plane idiom) — that is a live reference.
    findings = lint_tree(tmp_path, {
        "pkg/server.py": """
            class Srv:
                def __init__(self, server):
                    server.register_instance(self)

                def handle_kill(self, conn, data):
                    return {}
        """,
        "pkg/injector.py": """
            def inject(srv):
                return srv.handle_kill(None, {})
        """,
    }, rules=["RL014"])
    assert findings == []


def test_rl014_register_instance_covers_inherited_and_nonself(tmp_path):
    # The runtime expands dir(obj): inherited handle_* methods and
    # register_instance on a non-self object both register — the index
    # must agree (same-file resolution).
    findings = lint_tree(tmp_path, {
        "pkg/server.py": """
            class Base:
                def handle_ping2(self, conn, data=None):
                    return {}

            class Gateway:
                def handle_gw_put(self, conn, data):
                    return {}

            class Srv(Base):
                def __init__(self, server):
                    server.register_instance(self)
                    gw = Gateway()
                    server.register_instance(gw, prefix="x_")
        """,
        "pkg/client.py": """
            def f(c):
                c.call("ping2")
                return c.call("x_gw_put", {})
        """,
    }, rules=["RL014"])
    assert findings == [], [f.render() for f in findings]


def test_rl014_suppression_with_reason(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/server.py": """
            def serve(server, handler):
                server.register("orphan", handler)  # raylint: disable=RL014 — external caller
        """,
    }, rules=["RL014"])
    assert findings == []


# ------------------------------------------------------------------ RL015

RL015_CONFIG = """
    _TABLE = {}

    def _flag(name, type_, default, doc=""):
        _TABLE[name] = (type_, default, doc)

    _flag("alpha", int, 1, "used and documented")
    _flag("beta", int, 2, "declared but never read")
"""


def test_rl015_flags_undeclared_read_and_write(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/config.py": RL015_CONFIG,
        "pkg/user.py": """
            from pkg.config import GLOBAL_CONFIG

            def f():
                GLOBAL_CONFIG.gama = 3
                return GLOBAL_CONFIG.alpha + GLOBAL_CONFIG.delta
        """,
        "docs/CONFIG.md": "alpha beta\n",
    }, rules=["RL015"])
    msgs = [f.message for f in findings]
    assert any("read of undeclared config knob 'delta'" in m for m in msgs)
    assert any("write to undeclared config knob 'gama'" in m for m in msgs)
    # beta: declared, never read
    assert any("'beta' is declared but never read" in m for m in msgs)
    assert len(findings) == 3


def test_rl015_quiet_on_declared_read_and_methods(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/config.py": RL015_CONFIG,
        "pkg/user.py": """
            from pkg.config import GLOBAL_CONFIG

            def f():
                GLOBAL_CONFIG.refresh()
                GLOBAL_CONFIG.alpha = 5
                return GLOBAL_CONFIG.alpha + GLOBAL_CONFIG.beta
        """,
        "docs/CONFIG.md": "alpha beta\n",
    }, rules=["RL015"])
    assert findings == []


def test_rl015_flags_knob_missing_from_docs(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/config.py": RL015_CONFIG,
        "pkg/user.py": """
            from pkg.config import GLOBAL_CONFIG

            def f():
                return GLOBAL_CONFIG.alpha + GLOBAL_CONFIG.beta
        """,
        "docs/CONFIG.md": "alpha only\n",
    }, rules=["RL015"])
    assert rule_ids(findings) == ["RL015"]
    assert "'beta' is missing from the docs" in findings[0].message


def test_rl015_docs_check_skipped_without_docs_dir(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/config.py": RL015_CONFIG,
        "pkg/user.py": """
            from pkg.config import GLOBAL_CONFIG

            def f():
                return GLOBAL_CONFIG.alpha + GLOBAL_CONFIG.beta
        """,
    }, rules=["RL015"])
    assert findings == []


# ------------------------------------------------------------------ RL016

RL016_BAD_ESCAPE = """
    class Lane:
        def __init__(self):
            self._chans = {}  # raylint: confine=loop

        def _touch(self):
            self._chans["x"] = 1

        def go(self, loop):
            loop.run_in_executor(None, self._touch)
"""

RL016_GOOD_ESCAPE = """
    class Lane:
        def __init__(self):
            self._chans = {}  # raylint: confine=loop

        def _resolve(self):
            return open("/dev/null")

        def go(self, loop):
            self._chans["x"] = 1
            return loop.run_in_executor(None, self._resolve)
"""


def test_rl016_flags_confined_attr_in_executor_target(tmp_path):
    findings = lint_tree(tmp_path, {"pkg/lane.py": RL016_BAD_ESCAPE},
                         rules=["RL016"])
    assert rule_ids(findings) == ["RL016"]
    assert "_chans" in findings[0].message
    assert "escape" in findings[0].message


def test_rl016_quiet_on_escape_not_touching_confined_state(tmp_path):
    assert lint_tree(tmp_path, {"pkg/lane.py": RL016_GOOD_ESCAPE},
                     rules=["RL016"]) == []


def test_rl016_flags_one_hop_reach(tmp_path):
    findings = lint_tree(tmp_path, {"pkg/lane.py": """
        import threading

        class Lane:
            def __init__(self):
                self._chans = {}  # raylint: confine=loop

            def _touch(self):
                self._chans.pop("x", None)

            def _work(self):
                self._touch()

            def go(self):
                threading.Thread(target=self._work, daemon=True).start()
    """}, rules=["RL016"])
    assert rule_ids(findings) == ["RL016"]


def test_rl016_flags_closure_escape(tmp_path):
    findings = lint_tree(tmp_path, {"pkg/lane.py": """
        class Lane:
            def __init__(self):
                self._chans = {}  # raylint: confine=loop

            def go(self, loop):
                def work():
                    self._chans["x"] = 1
                loop.run_in_executor(None, work)
    """}, rules=["RL016"])
    assert rule_ids(findings) == ["RL016"]


def test_rl016_flags_unannotated_sibling(tmp_path):
    findings = lint_tree(tmp_path, {"pkg/lane.py": """
        class Lane:
            def __init__(self):
                self._chans = {}  # raylint: confine=loop
                self._depths = {}

            def on_req(self, rid):
                self._depths[rid] = 1
    """}, rules=["RL016"])
    assert rule_ids(findings) == ["RL016"]
    assert "_depths" in findings[0].message
    assert "annotate" in findings[0].message


def test_rl016_sibling_quiet_when_annotated_or_locked(tmp_path):
    assert lint_tree(tmp_path, {"pkg/a.py": """
        class Lane:
            def __init__(self):
                self._chans = {}  # raylint: confine=loop
                # raylint: confine=loop
                self._depths = {}

            def on_req(self, rid):
                self._depths[rid] = 1
    """}, rules=["RL016"]) == []
    # A class with a lock has a mixed discipline: unannotated state is
    # presumed lock-protected, not loop-confined.
    assert lint_tree(tmp_path, {"pkg/b.py": """
        import threading

        class Lane:
            def __init__(self):
                self._lock = threading.Lock()
                self._chans = {}  # raylint: confine=loop
                self._depths = {}

            def on_req(self, rid):
                with self._lock:
                    self._depths[rid] = 1
    """}, rules=["RL016"]) == []


def test_rl016_quiet_without_annotations(tmp_path):
    # No confine markers, no contract: RL016 has nothing to enforce.
    assert lint_tree(tmp_path, {"pkg/lane.py": """
        class Lane:
            def __init__(self):
                self._chans = {}

            def _touch(self):
                self._chans["x"] = 1

            def go(self, loop):
                loop.run_in_executor(None, self._touch)
    """}, rules=["RL016"]) == []


# ------------------------------------------------------------------ RL017

RL017_BAD_DELEGATE = """
    from ray_tpu.core.rpc import DEFERRED

    class Srv:
        def handle_fetch(self, conn, data):
            self._begin(conn, conn.current_msg_id)
            return DEFERRED

        def _begin(self, conn, mid):
            self.log(mid)   # bookkeeping only: nobody can ever reply
"""

RL017_GOOD_DELEGATE_PARKS = """
    from ray_tpu.core.rpc import DEFERRED

    class Srv:
        def handle_fetch(self, conn, data):
            self._begin(conn, conn.current_msg_id)
            return DEFERRED

        def _begin(self, conn, mid):
            self._waiters.append((conn, mid))
"""

RL017_BAD_UNGUARDED_CLOSURE = """
    from ray_tpu.core.rpc import DEFERRED

    class Srv:
        def handle_fetch(self, conn, data):
            self._begin(conn, conn.current_msg_id, data)
            return DEFERRED

        def _begin(self, conn, mid, data):
            def done(result):
                payload = transform(result)
                conn.reply(mid, "fetch", payload)
            self.executor.submit(done)
"""

RL017_GOOD_GUARDED_CLOSURE = """
    from ray_tpu.core.rpc import DEFERRED

    class Srv:
        def handle_fetch(self, conn, data):
            self._begin(conn, conn.current_msg_id, data)
            return DEFERRED

        def _begin(self, conn, mid, data):
            def done(result):
                try:
                    conn.reply(mid, "fetch", transform(result))
                except Exception as e:
                    conn.reply(mid, "fetch", None, error=str(e))
            self.executor.submit(done)
"""


def test_rl017_flags_delegate_that_never_replies(tmp_path):
    path = tmp_path / "srv.py"
    path.write_text(textwrap.dedent(RL017_BAD_DELEGATE))
    findings = lint_file(str(path), rule_ids=["RL017"])
    assert rule_ids(findings) == ["RL017"]
    assert "_begin" in findings[0].message


def test_rl017_quiet_when_delegate_parks(tmp_path):
    path = tmp_path / "srv.py"
    path.write_text(textwrap.dedent(RL017_GOOD_DELEGATE_PARKS))
    assert lint_file(str(path), rule_ids=["RL017"]) == []


def test_rl017_flags_unguarded_closure_in_delegate(tmp_path):
    # RL001's blind spot: the closure lives in the helper, which does
    # not itself return DEFERRED.
    path = tmp_path / "srv.py"
    path.write_text(textwrap.dedent(RL017_BAD_UNGUARDED_CLOSURE))
    findings = lint_file(str(path), rule_ids=["RL017"])
    assert rule_ids(findings) == ["RL017"]
    assert "can raise before replying" in findings[0].message


def test_rl017_quiet_on_guarded_closure_in_delegate(tmp_path):
    path = tmp_path / "srv.py"
    path.write_text(textwrap.dedent(RL017_GOOD_GUARDED_CLOSURE))
    assert lint_file(str(path), rule_ids=["RL017"]) == []


def test_rl017_flags_no_visible_completion_path(tmp_path):
    path = tmp_path / "srv.py"
    path.write_text(textwrap.dedent("""
        from ray_tpu.core.rpc import DEFERRED

        def handle_take(conn, data):
            validate(data)
            return DEFERRED
    """))
    findings = lint_file(str(path), rule_ids=["RL017"])
    assert rule_ids(findings) == ["RL017"]
    assert "nothing visible" in findings[0].message


def test_rl017_quiet_on_subscripted_park(tmp_path):
    # The gcs collective idiom: the park call's receiver is a subscript
    # (`slot["waiters"].append(...)`) and the msg id rides inline as
    # `conn.current_msg_id` — both must register as a park.
    path = tmp_path / "srv.py"
    path.write_text(textwrap.dedent("""
        from ray_tpu.core.rpc import DEFERRED

        def handle_take(conn, data, rec):
            slot = rec["mailbox"].setdefault(data["key"], {"waiters": []})
            slot["waiters"].append((conn, conn.current_msg_id))
            return DEFERRED
    """))
    assert lint_file(str(path), rule_ids=["RL017"]) == []


def test_rl017_handoff_counts_only_for_the_connection(tmp_path):
    # Passing the conn onward is a handoff (one-hop contract reached);
    # passing only the msg id is bookkeeping.
    path = tmp_path / "srv.py"
    path.write_text(textwrap.dedent("""
        from ray_tpu.core.rpc import DEFERRED

        class Srv:
            def handle_fetch(self, conn, data):
                self._begin(conn, conn.current_msg_id)
                return DEFERRED

            def _begin(self, conn, mid):
                self._transport.send_later(conn, mid)
    """))
    assert lint_file(str(path), rule_ids=["RL017"]) == []


# ------------------------------------------------------------------ RL023
# (whole-program: PartitionSpec literals vs the union of declared mesh
# axes, joined over the per-file jax_extract summaries)

RL023_MESH = """
    import numpy as np
    from jax.sharding import Mesh

    def build(devices):
        return Mesh(np.asarray(devices).reshape(2, 4), ("dp", "tp"))
"""


def test_rl023_flags_undeclared_axis(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/mesh.py": RL023_MESH,
        "pkg/model.py": """
            from jax.sharding import PartitionSpec as P

            SPEC = P("dp", "model")
        """,
    }, rules=["RL023"])
    assert rule_ids(findings) == ["RL023"]
    assert "'model'" in findings[0].message
    assert findings[0].path.endswith("model.py")


def test_rl023_flags_trailing_none_spec(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/mesh.py": RL023_MESH,
        "pkg/model.py": """
            from jax.sharding import PartitionSpec as P

            SPEC = P("dp", None)
        """,
    }, rules=["RL023"])
    assert rule_ids(findings) == ["RL023"]
    assert "trailing" in findings[0].message


def test_rl023_quiet_on_declared_axes(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/mesh.py": RL023_MESH,
        "pkg/model.py": """
            from jax.sharding import PartitionSpec as P

            ROWS = P("dp", "tp")
            INNER = P(None, "tp")
            PAIR = P(("dp", "tp"))
        """,
    }, rules=["RL023"])
    assert findings == []


def test_rl023_axis_check_needs_a_declared_mesh(tmp_path):
    # With no mesh declaration anywhere in the tree there is nothing to
    # check axis names against; only the trailing-None check stays live.
    findings = lint_tree(tmp_path, {
        "pkg/model.py": """
            from jax.sharding import PartitionSpec as P

            SPEC = P("anything")
        """,
    }, rules=["RL023"])
    assert findings == []


def test_rl023_shardspec_kwargs_declare_multi_axes(tmp_path):
    # A multi-axis gang ShardSpec(tp=, pp=, sp=) is a mesh declaration:
    # specs over those axes are quiet, a name no spec anywhere declares
    # still fires.
    findings = lint_tree(tmp_path, {
        "pkg/gang.py": """
            from ray_tpu.shardgroup import ShardSpec

            SPEC = ShardSpec(tp=4, pp=2)
        """,
        "pkg/model.py": """
            from jax.sharding import PartitionSpec as P

            STAGE = P("pp", "tp")
            BAD = P("pp", "sp")
        """,
    }, rules=["RL023"])
    assert rule_ids(findings) == ["RL023"]
    assert "'sp'" in findings[0].message


def test_rl023_shardspec_size_one_axis_is_not_declared(tmp_path):
    # shardgroup's mesh_axes drops size-1 axes, so a literal pp=1 must
    # not license P("pp") — but a RUNTIME width (pp=n) may be > 1 and
    # counts as declared.
    base = {
        "pkg/model.py": """
            from jax.sharding import PartitionSpec as P

            STAGE = P("pp")
        """,
    }
    findings = lint_tree(tmp_path, {
        **base,
        "pkg/gang.py": """
            from ray_tpu.shardgroup import ShardSpec

            SPEC = ShardSpec(tp=2, pp=1)
        """,
    }, rules=["RL023"])
    assert rule_ids(findings) == ["RL023"]
    assert "'pp'" in findings[0].message

    findings = lint_tree(tmp_path, {
        **base,
        "pkg/gang.py": """
            from ray_tpu.shardgroup import ShardSpec

            def spec(n):
                return ShardSpec(tp=2, pp=n)
        """,
    }, rules=["RL023"])
    assert findings == []


def test_rl023_meshspec_axes_kwarg_declares(tmp_path):
    findings = lint_tree(tmp_path, {
        "pkg/mesh.py": """
            from ray_tpu.parallel.mesh import MeshSpec

            SPEC = MeshSpec(axes={"dp": 2, "tp": 4})
        """,
        "pkg/model.py": """
            from jax.sharding import PartitionSpec as P

            ROWS = P("dp", "tp")
        """,
    }, rules=["RL023"])
    assert findings == []


def test_rl023_finding_cites_the_owning_rule_pattern(tmp_path):
    # A hit inside a match_partition_rules table names the rule's regex,
    # so a bad axis in a 30-row table is attributable at a glance.
    findings = lint_tree(tmp_path, {
        "pkg/mesh.py": RL023_MESH,
        "pkg/rules.py": """
            from jax.sharding import PartitionSpec as P

            RULES = (
                (r"embed$", P("tp")),
                (r"wq/kernel$", P(None, "model")),
            )
        """,
    }, rules=["RL023"])
    assert rule_ids(findings) == ["RL023"]
    assert "wq/kernel$" in findings[0].message
    assert "'model'" in findings[0].message


# ------------------------------------------- mutation negative-controls


def copy_package(tmp_path) -> str:
    dst = str(tmp_path / "ray_tpu")
    shutil.copytree(PKG, dst, ignore=shutil.ignore_patterns(
        "__pycache__", ".raylint_cache", "_native", "*.so"))
    return dst


def mutate(root: str, rel: str, needle: str, replacement: str) -> None:
    path = os.path.join(root, rel)
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    assert needle in src, f"mutation target vanished from {rel}: {needle!r}"
    with open(path, "w", encoding="utf-8") as f:
        f.write(src.replace(needle, replacement, 1))


def test_mutation_removing_live_registration_fires_rl014(tmp_path):
    root = copy_package(tmp_path)
    # direct_call is the task fast path: the owner pushes specs at it
    # from core/direct_task.py, so dropping the registration must
    # surface as an unregistered call site.
    mutate(root, "core/worker.py",
           'self.direct_server.register("direct_call", '
           'self._handle_direct_call)',
           "pass")
    findings = [f for f in lint_paths_full([root], ["RL014"]).findings
                if '"direct_call"' in f.message]
    assert findings, "RL014 did not notice the removed registration"


def test_mutation_removing_live_knob_declaration_fires_rl015(tmp_path):
    root = copy_package(tmp_path)
    mutate(root, "core/config.py",
           '_flag("rpc_call_timeout_s", float, 120.0, '
           '"Default RPC call timeout")',
           "")
    findings = [f for f in lint_paths_full([root], ["RL015"]).findings
                if "rpc_call_timeout_s" in f.message]
    assert findings, "RL015 did not notice the removed knob declaration"
    assert any("undeclared" in f.message for f in findings)


def test_mutation_removing_confine_annotation_fires_rl016(tmp_path):
    root = copy_package(tmp_path)
    mutate(root, "tenancy/admission.py",
           "self._queues: Dict[tuple, Deque[_Waiter]] = {}  "
           "# raylint: confine=loop",
           "self._queues: Dict[tuple, Deque[_Waiter]] = {}")
    findings = [f for f in lint_paths_full([root], ["RL016"]).findings
                if "_queues" in f.message]
    assert findings, "RL016 did not notice the dropped annotation"


def test_mutation_traced_branch_in_jitted_epoch_fires_rl020(tmp_path):
    root = copy_package(tmp_path)
    # The KL tail-pick in the jitted scan epoch is dict-KEY membership
    # (static); branching on the traced KL VALUE instead is the classic
    # retrace hazard.
    mutate(root, "rllib/learner.py",
           'if "kl" in metrics:',
           'if metrics["kl"].mean() > 0:')
    findings = [f for f in lint_paths_full([root], ["RL020"]).findings
                if "traced" in f.message]
    assert findings, "RL020 did not notice the traced-value branch"


def test_mutation_dropping_sync_suppression_fires_rl021(tmp_path):
    root = copy_package(tmp_path)
    # The rollout loop's per-step device_get is the env-step contract
    # and carries a reasoned suppression; deleting the comment proves
    # RL021 resolves the live loop, not just fixtures.
    mutate(root, "rllib/rollout.py",
           "host = jax.device_get(out)  # raylint: disable=RL021 — "
           "per-step sync is the env-step contract",
           "host = jax.device_get(out)")
    findings = [f for f in lint_paths_full([root], ["RL021"]).findings
                if "sample" in f.message]
    assert findings, "RL021 did not notice the unsuppressed loop sync"


def test_mutation_removing_donate_rebind_guard_fires_rl022(tmp_path):
    root = copy_package(tmp_path)
    # The draft-prefill lockstep rebinds the donated draft arenas in
    # the same statement — the RL022 guard. Bind the result to a temp
    # and keep an alias read of the donated name instead.
    mutate(root, "inference/engine.py",
           "            self._draft_arenas = self._call(\n"
           '                "draft_prefill", self._draft_prefill_fn,\n'
           "                self._draft_params, self._draft_arenas, "
           "*args[:4])",
           "            fresh = self._call(\n"
           '                "draft_prefill", self._draft_prefill_fn,\n'
           "                self._draft_params, self._draft_arenas, "
           "*args[:4])\n"
           "            self._draft_sync = self._draft_arenas\n"
           "            self._draft_arenas = fresh")
    findings = [f for f in lint_paths_full([root], ["RL022"]).findings
                if "_draft_arenas" in f.message]
    assert findings, "RL022 did not notice the read of the donated arenas"


def test_mutation_adding_trailing_none_spec_fires_rl023(tmp_path):
    root = copy_package(tmp_path)
    # Reintroduce the PR-8 bug shape: a trailing literal None on the
    # ring-attention shard_map spec.
    mutate(root, "ops/ring_attention.py",
           "spec = P(data_axes, None, sp_axis)",
           "spec = P(data_axes, None, sp_axis, None)")
    findings = [f for f in lint_paths_full([root], ["RL023"]).findings
                if "trailing" in f.message
                and f.path.endswith("ring_attention.py")]
    assert findings, "RL023 did not notice the trailing-None spec"


# The shape of a static-batch sampler deployment: a jitted closure that
# reads `self._max_seq`, set once in the constructor. The package holds no
# such closure since the sampler examples went (its jitted closures read no
# `self._...`), so the mutation is hosted in a tree of the test's own.
RL024_SAMPLER = """
    import jax
    import jax.numpy as jnp


    class Sampler:
        def __init__(self, max_seq):
            self._max_seq = max_seq

            def decode_step(tok, lens):
                return tok, jnp.where(lens < self._max_seq - 1, lens + 1, lens)

            self._decode = jax.jit(decode_step)

        def __call__(self, prompts):
            pad = 8
            pad = min(pad, self._max_seq)
            return self._decode(jnp.zeros((pad,), jnp.int32),
                                jnp.ones((pad,), jnp.int32))
"""


def test_mutation_steady_state_write_to_captured_attr_fires_rl024(tmp_path):
    root = str(write_tree(tmp_path, {"pkg/__init__.py": "",
                                     "pkg/sampler.py": RL024_SAMPLER}))
    assert lint_paths_full([root], ["RL024"]).findings == []
    # The sampler's jitted decode_step closure captures self._max_seq;
    # rebinding it per batch makes the capture stale (jit burned the
    # first-trace value in).
    mutate(root, "pkg/sampler.py",
           "pad = min(pad, self._max_seq)",
           "pad = min(pad, self._max_seq)\n        self._max_seq = pad")
    findings = [f for f in lint_paths_full([root], ["RL024"]).findings
                if "_max_seq" in f.message]
    assert findings, "RL024 did not notice the stale jit capture"


def test_project_rules_see_whole_package_from_subset_paths():
    """Linting one file (or a subdirectory) must not produce
    partial-graph false positives: the graph is built over the owning
    package closure, findings reported only for the requested paths."""
    res = lint_paths_full([os.path.join(PKG, "core", "worker.py")],
                          ["RL014"])
    assert res.findings == [], [f.render() for f in res.findings]
    res = lint_paths_full([os.path.join(PKG, "core")], ["RL015"])
    assert res.findings == [], [f.render() for f in res.findings]


# --------------------------------------------------- incremental cache


def test_incremental_subset_run_does_not_evict_cache(tmp_path):
    """A --incremental run over a subset must leave the rest of the
    tree's cache entries intact (pruning is for deleted files only)."""
    cache_dir = str(tmp_path / "cache")
    root = write_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/server.py": RL014_SERVER,
        "pkg/client.py": """
            def f(c):
                c.call("get_thing", {})
                c.call_raw("blob_get", b"x")
                return c.call("client_hello")
        """,
    })
    full = lint_paths_full([str(root)], incremental=True,
                           cache_dir=cache_dir)
    assert full.findings == [] and full.cache_misses == 3
    sub = lint_paths_full([str(root / "pkg" / "client.py")],
                          incremental=True, cache_dir=cache_dir)
    assert sub.findings == []
    again = lint_paths_full([str(root)], incremental=True,
                            cache_dir=cache_dir)
    assert again.cache_misses == 0, "subset run evicted unrelated entries"


def test_incremental_warm_run_is_identical_and_fast(tmp_path):
    cache_dir = str(tmp_path / "cache")
    paths = [os.path.join(PKG, "core"), os.path.join(PKG, "serve"),
             os.path.join(PKG, "tenancy")]
    t0 = time.perf_counter()
    cold = lint_paths_full(paths, incremental=True, cache_dir=cache_dir)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = lint_paths_full(paths, incremental=True, cache_dir=cache_dir)
    warm_s = time.perf_counter() - t0
    assert warm.cache_misses == 0 and warm.cache_hits == cold.cache_misses
    assert [f.as_dict() for f in warm.findings] == \
        [f.as_dict() for f in cold.findings]
    # The acceptance bound is <25% of the cold run; the sandbox ratio is
    # ~5%, so 50% here keeps the assertion meaningful without flaking
    # on a noisy 2-core box.
    assert warm_s < 0.5 * cold_s, (cold_s, warm_s)


def test_incremental_detects_edit_and_reanalyzes_one_file(tmp_path):
    cache_dir = str(tmp_path / "cache")
    client = ('def f(c):\n    c.call_raw("blob_get", b"x")\n'
              '    c.call("client_hello")\n'
              '    return c.call("{}", {{}})\n')
    root = write_tree(tmp_path, {
        "pkg/server.py": RL014_SERVER,
        "pkg/client.py": client.format("get_thing"),
    })
    cold = lint_paths_full([str(root)], incremental=True,
                           cache_dir=cache_dir)
    assert cold.findings == []
    (root / "pkg/client.py").write_text(client.format("get_thingg"))
    warm = lint_paths_full([str(root)], ["RL014"], incremental=True,
                           cache_dir=cache_dir)
    assert warm.cache_misses == 1 and warm.cache_hits == 1
    assert any("get_thingg" in f.message for f in warm.findings)


def test_incremental_jax_extract_only_change_updates_rl023(tmp_path):
    """An edit that only changes a file's `jax_extract` section (one
    PartitionSpec axis literal — no per-file rule cares) must flow
    through the cached summaries into the RL023 project join."""
    cache_dir = str(tmp_path / "cache")
    model = ('from jax.sharding import PartitionSpec as P\n\n'
             'SPEC = P("dp", "{}")\n')
    root = write_tree(tmp_path, {"pkg/mesh.py": RL023_MESH})
    (root / "pkg" / "model.py").write_text(model.format("tp"))
    cold = lint_paths_full([str(root)], incremental=True,
                           cache_dir=cache_dir)
    assert cold.findings == [] and cold.cache_misses == 2
    (root / "pkg" / "model.py").write_text(model.format("model"))
    warm = lint_paths_full([str(root)], incremental=True,
                           cache_dir=cache_dir)
    assert warm.cache_misses == 1 and warm.cache_hits == 1
    assert any(f.rule == "RL023" and "'model'" in f.message
               for f in warm.findings)


def test_incremental_cache_invalidates_on_rule_change(tmp_path, monkeypatch):
    from ray_tpu.analysis import engine

    cache_dir = str(tmp_path / "cache")
    root = write_tree(tmp_path, {"pkg/a.py": "x = 1\n"})
    cold = lint_paths_full([str(root)], incremental=True,
                           cache_dir=cache_dir)
    assert cold.cache_misses == 1
    monkeypatch.setattr(engine, "_tool_fingerprint", lambda: "changed")
    rerun = lint_paths_full([str(root)], incremental=True,
                            cache_dir=cache_dir)
    assert rerun.cache_misses == 1, "stale cache survived a rule change"


# -------------------------------------------------- CLI contract: SARIF,
# exit codes, unused suppressions, timings


def run_cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.analysis", *args],
        capture_output=True, text=True, cwd=cwd)


def test_cli_sarif_output_and_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import threading

        def spawn():
            threading.Thread(target=print).start()
    """))
    proc = run_cli([str(bad), "--format", "sarif"])
    assert proc.returncode == 1  # findings -> 1
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "raylint"
    results = run["results"]
    assert results and results[0]["ruleId"] == "RL005"
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] == 5
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    rules_meta = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"RL001", "RL014", "RL017",
            "RL020", "RL021", "RL022", "RL023", "RL024"} <= rules_meta

    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert run_cli([str(good), "--format", "sarif"]).returncode == 0  # clean
    assert run_cli([str(good), "--rules", "RL999"]).returncode == 2  # usage


def test_cli_retired_rl006_errors_with_pointer(tmp_path):
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    proc = run_cli([str(good), "--rules", "RL006"])
    assert proc.returncode == 2
    assert "retired" in proc.stderr and "RL020" in proc.stderr


def test_cli_unknown_rule_hints_at_catalog(tmp_path):
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    proc = run_cli([str(good), "--rules", "RL999"])
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr
    assert "--list-rules" in proc.stderr


def test_cli_list_rules_catalog():
    proc = run_cli(["--list-rules"])
    assert proc.returncode == 0
    for rid in ("RL001", "RL014", "RL020", "RL021",
                "RL022", "RL023", "RL024"):
        assert rid in proc.stdout, rid
    assert "scope:" in proc.stdout
    assert "[file]" in proc.stdout and "[project]" in proc.stdout
    # The retired alias stays documented in the catalog.
    assert "RL006" in proc.stdout
    assert "superseded by RL020" in proc.stdout


def test_cli_unused_suppression_report(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent("""
        import threading

        def spawn():
            threading.Thread(target=print).start()  # raylint: disable=RL005

        def clean():
            return 1  # raylint: disable=RL002
    """))
    proc = run_cli([str(mod), "--report-unused-suppressions"])
    assert proc.returncode == 1
    assert "unused suppression of RL002" in proc.stderr
    assert "RL005" not in proc.stderr  # that one still fires -> used
    # The audit needs the full rule set.
    proc = run_cli([str(mod), "--report-unused-suppressions",
                    "--rules", "RL005"])
    assert proc.returncode == 2


def test_cli_rules_subset_still_reports_syntax_errors(tmp_path):
    # --rules must never let an unparseable file lint clean: RL000 is
    # always in scope.
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    proc = run_cli([str(bad), "--rules", "RL001"])
    assert proc.returncode == 1
    assert "RL000" in proc.stdout


def test_quoted_marker_is_documentation_not_a_directive(tmp_path):
    # A marker preceded by a backtick/quote (docstrings, rule-catalog
    # comments) neither suppresses nor counts for the audit.
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent('''
        """Suppress with a trailing ``# raylint: disable=RL005``."""
        import threading

        def spawn():
            # the idiom is `# raylint: disable=RL005` with a reason
            threading.Thread(target=print).start()
    '''))
    proc = run_cli([str(mod), "--report-unused-suppressions"])
    assert proc.returncode == 1
    assert "RL005" in proc.stdout          # finding NOT suppressed
    assert "unused suppression" not in proc.stderr  # mentions not audited


def test_cli_timings_table(tmp_path):
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    proc = run_cli([str(good), "--timings"])
    assert proc.returncode == 0
    assert "raylint timings" in proc.stderr
    assert "RL014" in proc.stderr


def test_package_has_no_unused_suppressions():
    """Satellite contract: every `# raylint: disable=` comment in the
    package still earns its keep."""
    proc = run_cli(["ray_tpu/", "--report-unused-suppressions"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
