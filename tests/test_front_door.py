"""The two files a newcomer runs from, held to the tree: every command in
`README.md` and `scripts/gate.sh` names files that exist, and every
benchmark cell they name is a cell of `BENCHMARK.json`. No cluster, no jax.
"""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FRONT_DOORS = ["README.md", "scripts/gate.sh"]
PATH_PREFIXES = ("scripts/", "tests/", "benchmarks/", "docs/", "ray_tpu/")


def _code_tokens(name):
    """Whitespace-separated tokens of the file's code: fenced blocks and
    inline spans of a Markdown file, the non-comment lines of a script."""
    with open(os.path.join(REPO, name)) as f:
        text = f.read()
    if name.endswith(".md"):
        code = re.findall(r"```.*?\n(.*?)```", text, flags=re.S)
        code += re.findall(r"`([^`\n]+)`", re.sub(r"```.*?```", "", text,
                                                  flags=re.S))
    else:
        code = [line for line in text.splitlines()
                if not line.lstrip().startswith("#")]
    return [tok.strip("'\"()[],;:") for chunk in code
            for tok in chunk.split()]


def _named_paths(tokens):
    """Repo-relative paths the tokens name: what follows `python` /
    `python3` (a script, or `-m` and a module of this repo), and whatever
    starts with one of PATH_PREFIXES. Placeholders and globs are not
    paths."""
    paths = []
    for i, tok in enumerate(tokens):
        prev = tokens[i - 1] if i else ""
        prev2 = tokens[i - 2] if i > 1 else ""
        if not tok or re.search(r"[*<>…$=]", tok):
            continue
        if prev in ("python", "python3") and not tok.startswith("-"):
            paths.append(tok)
        elif prev == "-m" and prev2 in ("python", "python3"):
            module = tok.replace(".", "/")
            if os.path.isdir(os.path.join(REPO, module.split("/")[0])):
                paths.append(module + "/__main__.py"
                             if os.path.isdir(os.path.join(REPO, module))
                             else module + ".py")
        elif tok.startswith(PATH_PREFIXES):
            paths.append(tok.split("::")[0].rstrip("."))
    return paths


@pytest.mark.parametrize("name", FRONT_DOORS)
def test_every_path_a_front_door_names_exists(name):
    paths = _named_paths(_code_tokens(name))
    assert paths, f"{name} names no path at all: the scan is broken"
    missing = sorted({p for p in paths
                      if not os.path.exists(os.path.join(REPO, p))})
    assert not missing, f"{name} names files that are not in the tree: " \
                        f"{missing}"


@pytest.mark.parametrize("name", FRONT_DOORS)
def test_every_workload_a_front_door_names_is_a_cell(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = {w["name"] for w in json.load(f)["workloads"]}
    tokens = _code_tokens(name)
    named = [tok for prev, tok in zip(tokens, tokens[1:])
             if prev == "--workload" and not re.search(r"[<>…]", tok)]
    assert named, f"{name} runs no benchmark cell"
    unknown = sorted(set(named) - cells)
    assert not unknown, f"{name} names cells BENCHMARK.json does not " \
                        f"have: {unknown}"
