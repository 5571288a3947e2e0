"""The tree describes itself: what its documents name exists, nothing
names the pre-chip harness that PR 30 took out, and every query file a
mix config lists is in the tree. No network, about a second."""

import glob
import json
import os
import re

import pytest

from wukong_tpu.utils.paths import QUERIES, REPO

# a repo-relative path: starts at a top-level directory of the checkout,
# or is a root file by its extension
_TOP = ("wukong_tpu/", "tests/", "scripts/", "benchmark/", "queries/",
        ".claude/")
_PATH = re.compile(r"[A-Za-z0-9_.\-/]+")


def _named_paths(text: str) -> set:
    out = set()
    for tok in _PATH.findall(text):
        tok = tok.rstrip(".,:;")
        if tok.startswith(_TOP) or re.fullmatch(
                r"[A-Za-z0-9_\-]+\.(py|md|json|jsonl|sh|toml)", tok):
            out.add(tok)
    return out


def _quick_start(readme: str) -> str:
    """README's quick start and the section that names the benchmark."""
    return "".join(re.findall(
        r"^## (?:Quick start|Benchmark)\n.*?(?=^## )", readme, re.S | re.M))


DOCS = {
    "verify-skill": ".claude/skills/verify/SKILL.md",
    "readme-quick-start": "README.md",
    "ci-check": "scripts/ci_check.sh",
}


@pytest.mark.parametrize("doc", sorted(DOCS))
def test_every_path_a_document_names_exists(doc):
    text = open(os.path.join(REPO, DOCS[doc])).read()
    if doc == "readme-quick-start":
        text = _quick_start(text)
    paths = _named_paths(text)
    # each of the three says where speed is measured, and one that shows
    # the command's --workload names every cell there is to give it
    assert "benchmark/run.py" in paths, doc
    if "--workload" in text:
        cells = [w["name"] for w in json.load(open(os.path.join(
            REPO, "BENCHMARK.json")))["workloads"]]
        assert [c for c in cells if c not in text] == [], doc
    missing = sorted(p for p in paths
                     if not os.path.exists(os.path.join(REPO, p)))
    assert missing == [], f"{doc} names paths that are not in the tree"


# spelled in pieces so that this file does not name what it forbids
_GONE = re.compile("|".join([
    r"bench" + r"\.py\b", r"BENCH" + r"_[A-Za-z0-9_]*\.json",
    r"bench" + r"_report"]))


@pytest.mark.parametrize("top", ["wukong_tpu", "tests", "scripts"])
def test_no_source_names_the_pre_chip_harness(top):
    hits = []
    for path in glob.glob(os.path.join(REPO, top, "**", "*.py"),
                          recursive=True):
        for n, line in enumerate(open(path, errors="replace"), 1):
            if _GONE.search(line):
                hits.append(f"{os.path.relpath(path, REPO)}:{n}")
    assert hits == []


def test_every_mix_config_resolves_through_load_mix_config():
    from wukong_tpu.loader.lubm import VirtualLubmStrings
    from wukong_tpu.runtime.emulator import load_mix_config

    mixes = sorted(glob.glob(os.path.join(QUERIES, "**", "mix_config*"),
                             recursive=True))
    assert mixes, "no mix config under queries/"
    ss = VirtualLubmStrings(1, seed=42)
    for path in mixes:
        nlights, nheavies = (int(x) for x in open(path).readline().split())
        mix = load_mix_config(path, ss)  # opens every file the mix lists
        assert (len(mix.templates), len(mix.heavies)) == (nlights, nheavies)
        assert len(mix.weights) == nlights + nheavies > 0
