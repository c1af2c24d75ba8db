"""The documents name files that exist.

README.md, DESIGN.md, docs/*.md and the verify skill are what a new owner
reads first.  Every ``*.py`` path and every repo-root ``*.json`` record one of
them names must be in the tree; a paragraph may name a file that is gone only
where it says so ("deleted").  A PR that deletes a script or a record meets
the sentences that still send a reader to it here.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "DESIGN.md", *sorted(glob.glob("docs/*.md", root_dir=REPO)), ".claude/skills/verify/SKILL.md"]
SOURCE_DIRS = ["fast_tffm_tpu", "tools", "tests", "benchmark", "csrc"]
PY = re.compile(r"(?<![\w/.*<{-])((?:[\w.-]+/)*[\w-]+\.py)\b")
RECORD = re.compile(r"(?<![\w/.*<{-])([A-Z][A-Za-z0-9_]*\.json)\b")  # a root record: BENCH_r18.json, BASELINE.json
PLACEHOLDER = re.compile(r"NN|<|\*")  # BENCH_rNN.json, probe_*.py: a pattern, not a file
THE_REFERENCES_OWN = {"py/fm_ops.py"}  # renyi533/fast_tffm's file, in DESIGN's table of what replaced what


def _scripts():
    """Every ``*.py`` of the tree as ``/<path from the root>``: a document may
    name one by any tail of its path (`ops/fm.py`, `router.py`)."""
    found = ["/" + fn for fn in os.listdir(REPO) if fn.endswith(".py")]
    for base in SOURCE_DIRS:
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, base)):
            rel = os.path.relpath(dirpath, REPO)
            found += [f"/{rel}/{fn}" for fn in files if fn.endswith(".py")]
    return found


def _missing(text):
    scripts = _scripts() + ["/" + name for name in THE_REFERENCES_OWN]
    gone = set()
    for paragraph in re.split(r"\n\s*\n", text):
        if "deleted" in paragraph:
            continue
        for name in PY.findall(paragraph):
            if not PLACEHOLDER.search(name) and not any(path.endswith("/" + name) for path in scripts):
                gone.add(name)
        for name in RECORD.findall(paragraph):
            if not PLACEHOLDER.search(name) and not os.path.exists(os.path.join(REPO, name)):
                gone.add(name)
    return sorted(gone)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_script_and_record_a_document_names_is_in_the_tree(document):
    with open(os.path.join(REPO, document)) as f:
        assert _missing(f.read()) == []


def test_the_check_sees_a_missing_file_and_spares_a_paragraph_that_says_deleted():
    text = "Run `tools/no_such_probe.py`, which wrote `NO_SUCH_r01.json`.\n\n`gone.py` was deleted in PR 31.\n\nSee `ops/fm.py` and BASELINE.json."
    assert _missing(text) == ["NO_SUCH_r01.json", "tools/no_such_probe.py"]
