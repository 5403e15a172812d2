"""Documentation lint (tier-1): the documents describe the tree that is here.

The drift this catches is real: for five PRs README.md presented a deleted
benchmark script as the benchmark and named the files the driver measures
not once. No session remembers another; each starts from these documents.
`benchmark/`, CHANGES.md, PERF.md and ROADMAP.md keep history and are not
read here.
"""
import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a path under one of the tree's four code directories, or a bare `name.py`
# (not the tail of a longer path or of a dotted name)
_PATH = re.compile(r"(?<![\w/.-])((?:tools|benchmark|tests|paddle_tpu)/"
                   r"[\w./-]*\w|\w+\.py\b)")

# what each case reads, and whether a bare `name.py` in it is held: the
# package's docstrings cite the reference implementation's files by bare
# name (parity notes), so only the documents and the scripts are held to
# theirs, where a bare name is a root-level script or a sibling
DOCUMENTS = {
    "README.md": (["README.md"], True),
    "tools/README.md": (["tools/README.md"], True),
    "verify skill": ([".claude/skills/verify/SKILL.md"], True),
    "package sources": (["paddle_tpu/**/*.py"], False),
    "tools and root scripts": (["tools/*.py", "tools/*.sh", "*.py"], True),
}


def _files(*patterns):
    return sorted(p for pat in patterns
                  for p in glob.glob(os.path.join(REPO, pat), recursive=True))


def _exists(name: str, basenames: set) -> bool:
    if "/" not in name:
        return name in basenames
    # `tools/_timing.measure` names an attribute of tools/_timing.py
    module = name.rsplit(".", 1)[0] + ".py"
    return any(os.path.exists(os.path.join(REPO, p)) for p in (name, module))


@pytest.mark.parametrize("doc", sorted(DOCUMENTS))
def test_every_path_a_document_names_exists(doc):
    """A path that starts with tools/, benchmark/, tests/ or paddle_tpu/
    exists as written; a bare `name.py` is a file of the tree."""
    patterns, bare_names_held = DOCUMENTS[doc]
    files = _files(*patterns)
    assert files, f"{doc}: nothing to read"
    basenames = {os.path.basename(p) for p in
                 _files("*.py", "tools/*.py", "tests/**/*.py",
                        "benchmark/**/*.py", "paddle_tpu/**/*.py")}
    missing = set()
    for path in files:
        for name in _PATH.findall(open(path, encoding="utf-8").read()):
            if "/" not in name and not bare_names_held:
                continue
            if not _exists(name, basenames):
                missing.add(f"{os.path.relpath(path, REPO)}: {name}")
    assert not missing, (
        f"{doc} names files the tree does not hold: {sorted(missing)}")


def test_chip_smoke_trains_the_published_widths():
    """One owner for BERT-base's sizes: chip_smoke's trainer is
    models.transformer.bert_base() and agrees with the `published` block of
    the benchmark's configuration (read only)."""
    import chip_smoke

    with open(os.path.join(REPO, "benchmark/configs/bert_base.json")) as f:
        pub = json.load(f)["published"]
    cfg = chip_smoke.TRAINER_CFG
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.ffn_size,
            cfg.vocab_size, cfg.max_position) == (
        pub["hidden_size"], pub["num_hidden_layers"],
        pub["num_attention_heads"], pub["intermediate_size"],
        pub["vocab_size"], pub["max_position_embeddings"])
    assert cfg.dropout == 0.0 and not cfg.use_tp


def test_readme_names_the_benchmark_and_every_cell():
    readme = open(os.path.join(REPO, "README.md")).read()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    wanted = ["benchmark/run.py", "BENCHMARK.json", "PERF_LEDGER.jsonl"]
    absent = [n for n in wanted + cells if n not in readme]
    assert cells and not absent, f"README.md does not name {absent}"
