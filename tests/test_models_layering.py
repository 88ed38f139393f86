"""`ray_tpu/models/` is a layer whose arrows point one way: a model file
imports `_nn` (what the model files share), `_served` (the served-model
contract) and `ray_tpu.ops`, and no other model file; `ops/` knows of no
model and no engine; `inference/` is HANDED a model and imports none. And
the contract is code: every class that answers `paged_step` is a
`PagedModel`, and `PagedModel` names everything the engine reads."""

import ast
import glob
import importlib
import inspect
import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.models._served import PagedModel

PKG = os.path.dirname(ray_tpu.__file__)

# (importing file, imported module) -> why the arrow is allowed
EXCEPTIONS = {
    ("models/moe.py", "ray_tpu.models.gpt2"):
        "next_token_loss and make_train_step: one owner (gpt2.py), three "
        "importers, and no served model reads them",
    ("models/qwen3_next.py", "ray_tpu.models.gpt2"):
        "next_token_loss: as moe.py",
    ("models/llama.py", "ray_tpu.models.gpt2"):
        "mesh_shardings_for, for the tp placement of a flax module",
    ("models/__init__.py", "*"):
        "the package's front door re-exports the zoo "
        "(tests/test_models.py::test_models_package_imports)",
    ("inference/api.py", "ray_tpu.models.llama"):
        "LLMServer's preset: the one place a deployment names a family",
}
SHARED = ("ray_tpu.models._nn", "ray_tpu.models._served")


def _files(sub):
    return sorted(os.path.relpath(p, PKG) for p in glob.glob(
        os.path.join(PKG, sub, "*.py")))


def _is_module(name):
    path = os.path.join(os.path.dirname(PKG), *name.split("."))
    return os.path.isfile(path + ".py") or os.path.isdir(path)


def _imports(rel, pkg=PKG):
    """Every `ray_tpu` module a file imports, at any depth of nesting
    (`from ray_tpu.models import sdar` is an import of
    `ray_tpu.models.sdar`)."""
    with open(os.path.join(pkg, rel)) as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module and not \
                node.level:
            out.add(node.module)
            out.update(name for name in (
                f"{node.module}.{a.name}" for a in node.names)
                if _is_module(name))
    return {m for m in out if m.startswith("ray_tpu.")}


def _under(module, package):
    return module == package or module.startswith(package + ".")


def _model_imports(rel):
    """The model files a file imports, less the layer's shared bottom and
    the named exceptions."""
    return sorted(
        m for m in _imports(rel)
        if _under(m, "ray_tpu.models") and m != "ray_tpu.models"
        and m not in SHARED and (rel, m) not in EXCEPTIONS
        and (rel, "*") not in EXCEPTIONS)


@pytest.mark.parametrize("rel", _files("models"))
def test_a_model_file_imports_no_other_model_file(rel):
    bad = _model_imports(rel)
    assert not bad, f"{rel} imports {bad}: share through models/_nn.py"
    if os.path.basename(rel) in ("_nn.py", "_served.py"):
        inner = sorted(m for m in _imports(rel)
                       if not _under(m, "ray_tpu.ops"))
        assert not inner, f"{rel} is the bottom of the layer: {inner}"


@pytest.mark.parametrize("rel", _files("ops"))
def test_ops_know_no_model_and_no_engine(rel):
    bad = sorted(m for m in _imports(rel)
                 if _under(m, "ray_tpu.models")
                 or _under(m, "ray_tpu.inference"))
    assert not bad, f"{rel} imports {bad}"


@pytest.mark.parametrize("rel", _files("inference"))
def test_inference_is_handed_its_model(rel):
    bad = _model_imports(rel)
    assert not bad, f"{rel} imports {bad}: the engine is handed a model"


def test_every_exception_is_still_needed():
    """An arrow that has gone leaves no entry behind."""
    for rel, module in EXCEPTIONS:
        assert module == "*" or module in _imports(rel), (rel, module)


# ------------------------------------------------------- the contract


def _model_classes():
    found = []
    for rel in _files("models"):
        name = os.path.basename(rel)[:-3]
        if name.startswith("__"):
            continue
        module = importlib.import_module(f"ray_tpu.models.{name}")
        found += [cls for _, cls in inspect.getmembers(module, inspect.isclass)
                  if cls.__module__ == module.__name__]
    return found


SERVED = [cls for cls in _model_classes() if "paged_step" in vars(cls)]


def test_the_served_models_are_found():
    assert {c.__name__ for c in SERVED} >= {
        "Llama", "FalconH1", "Brumby", "DeepseekV3", "Ouro", "SDAR",
        "PagedModel"}


@pytest.mark.parametrize("cls", SERVED, ids=lambda c: c.__name__)
def test_a_class_that_answers_paged_step_is_a_paged_model(cls):
    assert issubclass(cls, PagedModel)
    # ... and does not re-spell a default the base already states
    for name in ("prefix_restores", "slot_state_bytes", "pageless_context",
                 "paged_step_with_chunk", "decode_block", "cache_counters"):
        if cls is not PagedModel and name in vars(cls):
            assert vars(cls)[name] is not vars(PagedModel)[name], (cls, name)


class TwoAnswers(PagedModel):
    """`paged_cache` and `paged_step` ALONE: logits are a token's embedding
    through a head, the cache a count of the steps. Everything else the
    engine reads must have a default in `PagedModel`."""

    vocab, width = 32, 4

    def paged_cache(self, num_blocks, block_size, mesh=None,
                    batch_slots=None):
        import jax.numpy as jnp

        return {"steps": jnp.zeros((), jnp.int32)}

    def paged_step(self, params, ids, cache, block_tables, row_pos,
                   write_mask, adapters=None, slots=None, last_idx=None):
        import jax.numpy as jnp

        hidden = jnp.asarray(params["embed"])[ids]
        if last_idx is not None:
            hidden = jnp.take_along_axis(
                hidden, last_idx[:, None, None], axis=1)[:, 0]
        return hidden @ jnp.asarray(params["head"]), \
            {"steps": cache["steps"] + 1}


def test_paged_model_names_everything_the_engine_reads():
    """A model that derives from `PagedModel` and answers the two required
    questions serves two requests to the end and `stats()` answers: an
    attribute the engine starts to read without a default in the base
    fails here, so the contract cannot grow by `getattr` unseen."""
    rng = np.random.default_rng(3)
    params = {"embed": rng.standard_normal((32, 4)).astype(np.float32),
              "head": rng.standard_normal((4, 32)).astype(np.float32)}
    model = TwoAnswers()
    engine = InferenceEngine(
        EngineConfig(batch_slots=2, block_size=4, num_blocks=17,
                     max_blocks_per_seq=8, prefill_chunk=8),
        model=model, params=params)
    reqs = [engine.add_request([1, 2, 3], max_new_tokens=5),
            engine.add_request(list(range(4, 15)), max_new_tokens=3)]
    engine.run_until_idle()
    for req, n in zip(reqs, (5, 3)):
        assert req.state == "FINISHED" and len(req.generated) == n
    # a token's logits are its own embedding's: greedy decoding is a chain
    chain = np.argmax(params["embed"] @ params["head"], axis=-1)
    assert reqs[0].generated[1] == chain[reqs[0].generated[0]]
    stats = engine.stats()
    assert stats["requests_finished"] == 2
    assert stats["steps"]["decode"] >= 1
    engine.check_no_leaks()
    # and the engine source asks the model for nothing by `getattr`
    with open(os.path.join(PKG, "inference", "engine.py")) as f:
        tree = ast.parse(f.read())
    asked = [ast.unparse(n) for n in ast.walk(tree)
             if isinstance(n, ast.Call) and getattr(n.func, "id", "")
             == "getattr" and "model" in ast.unparse(n.args[0]).lower()]
    assert asked == []


def test_the_defaults_refuse_by_the_models_own_name():
    class Mesh:
        axis_names = ("tp",)
        devices = np.zeros((2,))

    model = TwoAnswers()
    for ask in (lambda: model.place_on_mesh({}, Mesh()),
                lambda: model.early_exit_draft({}),
                lambda: model.adapter_banks(4, 8)):
        with pytest.raises(ValueError, match="TwoAnswers"):
            ask()
    assert model.place_on_mesh({"w": 1}, type("M", (), {
        "axis_names": ("dp",), "devices": np.zeros((4,))})()) == ({"w": 1}, 1)
    with pytest.raises(NotImplementedError):
        PagedModel().paged_step(None, None, None, None, None, None)
