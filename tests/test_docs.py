"""The front page holds what the repository holds: the README names every
cell the benchmark declares and cites no file that is not there."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(name: str) -> str:
    with open(os.path.join(REPO, name), encoding="utf-8") as f:
        return f.read()


CELLS = [w["name"] for w in json.loads(_read("BENCHMARK.json"))["workloads"]]
README = _read("README.md")


@pytest.mark.parametrize("cell", CELLS)
def test_readme_names_the_cell(cell):
    assert f"`{cell}`" in README, (
        f"BENCHMARK.json declares the cell {cell}; README.md's Benchmarks "
        "section has to name it")


def test_every_file_the_readme_cites_exists():
    """A path in backticks that looks like a file of this repo (*.py, *.md,
    *.json, a :line suffix dropped) is there: at the root, or under
    kubernetes_tpu/ as the README's module paths are written. A bare
    lower-case *.json is a file the program writes at run time (a
    checkpoint, a configuration), not one of the repo's documents."""
    cited = set()
    for span in re.findall(r"`([^`\n]+)`", README):
        for word in span.split():
            m = re.match(r"^([\w./-]+\.(?:py|md|json))(?::\d+)?$", word)
            if not m or m.group(1).startswith(("/", "-")):
                continue
            path = m.group(1)
            if "/" in path or not (path.endswith(".json")
                                   and path[0].islower()):
                cited.add(path)
    assert cited, "the pattern found no path in README.md"
    missing = sorted(
        p for p in cited
        if not any(os.path.exists(os.path.join(REPO, root, p))
                   for root in ("", "kubernetes_tpu")))
    assert not missing, f"README.md cites files that do not exist: {missing}"


# suite-tier discipline (tests/test_markers.py): area marker
pytestmark = pytest.mark.core
