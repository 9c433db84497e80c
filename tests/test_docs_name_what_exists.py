"""The documents and the ``Makefile`` name only what the tree holds: every
``make <target>`` is a target, every ``python <path>.py`` a file, every
``python -m <module>`` a module, every path under a directory of the tree a
file. A document that tells its reader to run what is gone fails here.
"""

import glob
import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs/*.md")))

FENCE = re.compile(r"```.*?\n(.*?)```", re.S)
SPAN = re.compile(r"`([^`\n]+)`")
MAKE = re.compile(r"(?:^|[\s;&(])(?:make|\$\(MAKE\))\s+(?:-\w+\s+)*([a-z][\w-]*)")
SCRIPT = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
MODULE = re.compile(r"\bpython3?\s+-m\s+([\w.]+)")
# a path under one of the tree's own directories, as far as it is spelled out
PATH = re.compile(
    r"(?<![\w./-])((?:tests|benchmarks|picotron_tpu|docs|configs|template)"
    r"/[\w./-]*\.(?:py|md|json|jsonl|cc|sh))\b")


def read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


def make_targets():
    return set(re.findall(r"^([a-z][\w-]*):", read("Makefile"), re.M))


def code_of(markdown):
    """The text of a document's fenced blocks and of its code spans."""
    blocks = FENCE.findall(markdown)
    return blocks + SPAN.findall(FENCE.sub("", markdown))


def missing(code, targets):
    """What one stretch of code names that the tree does not hold."""
    out = [f"make {t}" for t in MAKE.findall(code) if t not in targets]
    out += [f"python {p}" for p in SCRIPT.findall(code)
            if not os.path.isfile(os.path.join(ROOT, p))]
    for mod in MODULE.findall(code):
        top = mod.split(".")[0]
        if os.path.isdir(os.path.join(ROOT, top)):  # one of the tree's own
            base = os.path.join(ROOT, *mod.split("."))
            found = os.path.isfile(base + ".py") \
                or os.path.isfile(os.path.join(base, "__main__.py"))
        else:
            found = importlib.util.find_spec(top) is not None
        if not found:
            out.append(f"python -m {mod}")
    out += [p for p in PATH.findall(code)
            if not glob.glob(os.path.join(ROOT, p))]
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_what_the_tree_holds(doc):
    targets = make_targets()
    gone = sorted({m for code in code_of(read(doc))
                   for m in missing(code, targets)})
    assert not gone, f"{doc} names what is not there: {gone}"


def test_the_makefile_runs_only_what_the_tree_holds():
    text = read("Makefile")
    targets = make_targets()
    recipes = "\n".join(ln for ln in text.splitlines() if ln.startswith("\t"))
    assert not missing(recipes, targets)
    phony = re.search(r"^\.PHONY:(.*)$", text, re.M).group(1).split()
    assert set(phony) <= targets, set(phony) - targets
