"""The repo's account of itself, held to the repo: the documents name the
benchmark's cells and configurations as ``BENCHMARK.json`` declares them
(read, never edited), the files they point at exist, and nothing points a
reader at the harness PR 28 took out."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CONFIGS = [c["name"] for c in MANIFEST["configs"]]

# who must name every cell and configuration; of PERF.md its section 4
# ("Cells"), whose other sections also list cells the benchmark lacks yet
NAMING_DOCS = ("README.md", "docs/benchmarks.md", "PERF.md#4")
PATH_DOCS = ("README.md", "docs/benchmarks.md", "docs/index.md",
             "docs/observability.md", ".claude/skills/verify/SKILL.md")

# what a reader must not be sent to any more
GONE = ("bench" + ".py", "check_bench" + "_trend", "step" + "time")


def _read(doc):
    path, _, section = doc.partition("#")
    with open(os.path.join(ROOT, path)) as f:
        text = f.read()
    if section:
        m = re.search(rf"^## {section}\..*?(?=^## )", text, re.M | re.S)
        assert m, f"{path} has no section {section}"
        text = m.group(0)
    return text


def _code_tokens(text):
    """What a document sets in backticks, and the words of its fenced
    code blocks."""
    fenced = re.findall(r"^```.*?^```", text, re.M | re.S)
    for block in fenced:
        text = text.replace(block, "")
        for line in block.splitlines()[1:-1]:
            yield from line.split()
    yield from re.findall(r"`([^`\n]+)`", text)


_NAME = r"[a-z0-9]+(?:-[a-z0-9]+)*"
_CELL_SHAPED = re.compile(rf"^({_NAME})\.({_NAME})$")
_FILE_EXT = ("py", "json", "md", "sh", "jsonl", "gz", "cpp", "so", "txt")


def _cell_shaped(text):
    """Backticked ``<configuration>.<traffic>`` names: two hyphenated
    lower-case words around one dot, neither a file nor a metric nor a
    declared configuration whose own name holds a dot (``ouro-2.6b``)."""
    metrics = {m["name"] for m in
               MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for tok in set(_code_tokens(text)):
        m = _CELL_SHAPED.match(tok)
        if m and "-" in tok and tok not in metrics \
                and tok not in CONFIGS and m.group(2) not in _FILE_EXT:
            yield tok, m.group(1)


@pytest.mark.parametrize("name", CELLS + CONFIGS)
def test_documents_name_what_the_manifest_declares(name):
    for doc in NAMING_DOCS:
        assert f"`{name}`" in _read(doc), f"{doc} does not name {name}"


@pytest.mark.parametrize("doc", NAMING_DOCS)
def test_documents_name_no_cell_the_manifest_lacks(doc):
    unknown = sorted(tok for tok, config in _cell_shaped(_read(doc))
                     if tok not in CELLS or config not in CONFIGS)
    assert unknown == []


_PATH = re.compile(r"^[A-Za-z0-9_.][A-Za-z0-9_./-]*\.(?:py|json|md|sh)$")
# a path may be written from the root or from one of these
_BASES = ("", "apex_tpu", "benchmark", "docs", "tests")


def _repo_files():
    """The checkout's files, without what building and running leave
    behind (dot-directories but ``.claude``, caches, chip outputs)."""
    for d, dirs, files in os.walk(ROOT):
        dirs[:] = [x for x in dirs
                   if (not x.startswith(".") or x == ".claude")
                   and x not in ("__pycache__", "chiprun_out")]
        for f in files:
            yield os.path.join(d, f)


@pytest.mark.parametrize("doc", PATH_DOCS)
def test_paths_a_document_names_exist(doc):
    """Every repo-relative ``*.py`` / ``*.json`` / ``*.md`` / ``*.sh`` a
    document sets in backticks or a code block is a file: with a
    directory, under the root or a top-level package; bare, a name some
    file of the checkout has."""
    basenames = None
    missing = []
    for tok in sorted(set(_code_tokens(_read(doc)))):
        tok = tok.strip("'\",;:()[]")
        if not _PATH.match(tok) or tok.startswith("/"):
            continue
        if "/" not in tok:
            if basenames is None:
                basenames = {os.path.basename(p) for p in _repo_files()}
            if tok not in basenames:
                missing.append(tok)
        elif not any(os.path.isfile(os.path.join(ROOT, base, tok))
                     for base in _BASES):
            missing.append(tok)
    assert missing == []


def test_nothing_points_at_the_harness_that_went():
    """No document under ``docs/``, nor ``README.md``, nor the verify
    notes, nor any ``*.py`` names what PR 28 deleted.  ``CHANGES.md``,
    ``ROADMAP.md``, ``PERF.md`` and ``ISSUE.md`` are history and may."""
    hits = []
    for path in _repo_files():
        rel = os.path.relpath(path, ROOT)
        if not (rel.endswith(".py") or rel == "README.md"
                or (rel.endswith(".md")
                    and rel.split(os.sep)[0] in ("docs", ".claude"))):
            continue
        with open(path, errors="replace") as f:
            text = f.read()
        hits += [f"{rel}: {g}" for g in GONE if g in text]
    assert hits == []


# what PR 47 took out, as a reader would meet it in a sentence that
# offers it: a pattern each, matched without regard to case
REMOVED = {
    "timeline": r"observability\.timeline|timeline\.py|make_profiler"
                r"|analyze_capture|kind: profile",
    "/profilez": r"/profilez",
    "adasum": r"adasum",
    "pallas_syncbn": r"pallas_syncbn|pallas_forced",
    "comm_enabled": r"comm_enabled|supervisor_signals|record_numerics"
                    r"|last_numerics|overlap_schedule_fields",
    "FORCE_PALLAS=prod": r"FORCE_PALLAS\W{1,3}prod",
}


def _offering_docs():
    yield "README.md"
    yield "PAPERS.md"
    for f in sorted(os.listdir(os.path.join(ROOT, "docs"))):
        if f.endswith(".md"):
            yield os.path.join("docs", f)


@pytest.mark.parametrize("name", list(REMOVED))
def test_documents_do_not_offer_what_was_removed(name):
    """``README.md``, ``docs/*.md`` and ``PAPERS.md`` name nothing PR 47
    deleted.  ``CHANGES.md``, ``ROADMAP.md``, ``PERF.md`` and ``ISSUE.md``
    are history and may."""
    pattern = re.compile(REMOVED[name], re.I)
    hits = []
    for doc in _offering_docs():
        for n, line in enumerate(_read(doc).splitlines(), 1):
            if pattern.search(line):
                hits.append(f"{doc}:{n}: {line.strip()[:80]}")
    assert hits == []
