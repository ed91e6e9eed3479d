"""Execute the python code blocks in the docs.

The reference runs its documentation code through a doctest leg
(tests/python/doctest/run.py, SURVEY §4.7) so examples cannot drift
from the API; this is that gate for docs/tutorials and docs/how_to.

Per file, every ```python fence is concatenated in order and executed
in one namespace (later blocks may use earlier blocks' variables, as
prose tutorials naturally do), under the suite's virtual 8-device CPU
mesh and a temp cwd. A fence preceded (within five lines) by an HTML
comment containing ``no-run`` is skipped — for blocks that genuinely
need external data, a real cluster, or a TPU; the marker carries the
reason so the exemption is reviewable in the doc source.

Two more gates on drift: the documents a new user starts from name only
files that exist (``test_doc_paths_exist``), and ``docs/env_vars.md`` and
the code agree on the environment variables, both ways
(``test_env_vars_*``).
"""
import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DOC_DIRS = ["docs/tutorials", "docs/how_to"]


def _collect():
    files = []
    for d in DOC_DIRS:
        for dirpath, _, names in os.walk(os.path.join(ROOT, d)):
            for n in sorted(names):
                if n.endswith(".md"):
                    files.append(os.path.relpath(
                        os.path.join(dirpath, n), ROOT))
    return sorted(files)


def _blocks(text):
    lines = text.split("\n")
    out, i = [], 0
    while i < len(lines):
        if lines[i].strip() == "```python":
            # the marker is an HTML comment whose FIRST line reads
            # `<!-- no-run: reason` — prose mentioning "no-run" or a
            # flag in a nearby block must not un-gate an example
            skip = any("<!--" in lines[j] and "no-run" in lines[j]
                       for j in range(max(0, i - 5), i))
            j = i + 1
            while j < len(lines) and lines[j].strip() != "```":
                j += 1
            if not skip:
                # pad with blank lines so tracebacks point at the real
                # line numbers in the .md file
                out.append("\n" * (i + 1) + "\n".join(lines[i + 1:j]))
            i = j + 1
        else:
            i += 1
    return out


@pytest.mark.parametrize("relpath", _collect())
def test_doc_python_blocks(relpath, tmp_path, monkeypatch):
    text = open(os.path.join(ROOT, relpath)).read()
    blocks = _blocks(text)
    if not blocks:
        pytest.skip("no runnable python blocks")
    monkeypatch.chdir(tmp_path)
    ns = {"__name__": "__doc_example__"}
    for block in blocks:
        exec(compile(block, os.path.join(ROOT, relpath), "exec"), ns)


# -- the documents name files that exist ----------------------------------------
PATH_DOCS = ["README.md", "docs/how_to/perf.md", "docs/how_to/profiling.md",
             "docs/how_to/serving.md", "docs/how_to/index.md",
             "docs/how_to/low_precision_comms.md", "docs/api/index.md",
             "docs/env_vars.md", ".claude/skills/verify/SKILL.md"]
_NOT_TREE = {".git", "build", "__pycache__", "chiprun_out", "benchmark_out",
             ".jax_cache", ".parent", ".change"}
_FENCE = re.compile(r"^```.*?$(.*?)^```\s*$", re.S | re.M)


def _code_spans(text):
    """Fenced blocks and inline code spans of a markdown text."""
    fenced = [m.group(1) for m in _FENCE.finditer(text)]
    return fenced + re.findall(r"`([^`\n]+)`", _FENCE.sub("", text))


def _named_paths(text):
    """Every token of a code span that ends in .py / .json / .md, without
    a trailing ``:line`` or ``::test``; placeholders (``<cell>``,
    ``{name}``, ``*``), absolute paths and URLs are not paths of the
    repository."""
    found = set()
    for span in _code_spans(text):
        for tok in re.split(r"[\s\"'=,;()\[\]]+", span):
            tok = re.sub(r":[0-9][0-9,\-:]*$", "", tok.strip(".:"))
            tok = tok.split("::")[0]
            if not re.search(r"\.(py|json|md)$", tok):
                continue
            if set("<>*{}$%") & set(tok) or tok.startswith(
                    ("/", "~", "http")):
                continue
            found.add(tok)
    return found


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for _, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _NOT_TREE]
        names.update(files)
    return names


@pytest.mark.parametrize("relpath", PATH_DOCS)
def test_doc_paths_exist(relpath):
    """A path with a directory in it exists from the root of the
    repository, from ``mxnet_tpu/`` or from the document's own directory;
    a bare file name exists somewhere in the tree."""
    text = open(os.path.join(ROOT, relpath)).read()
    names = _basenames()
    bases = ("", "mxnet_tpu", os.path.dirname(relpath))
    missing = sorted(
        p for p in _named_paths(text)
        if not (p in names if "/" not in p else any(
            os.path.exists(os.path.join(ROOT, b, p)) for b in bases)))
    assert not missing, "%s names files that do not exist: %s" % (
        relpath, missing)


# -- docs/env_vars.md and the code agree, both ways -------------------------------
_ENV_NAME = r"(?:MXNET|MXCTL)_[A-Z0-9_]+"
ENV_READERS = ["mxnet_tpu", "tools", "benchmark", "chip_smoke.py",
               "tests/conftest.py"]


def _env_rows():
    """The names in the first cell of every table row of docs/env_vars.md."""
    rows = set()
    for line in open(os.path.join(ROOT, "docs", "env_vars.md")):
        if line.startswith("|"):
            rows.update(re.findall(_ENV_NAME, line.split("|")[1]))
    return rows


@functools.lru_cache(maxsize=None)
def _env_read():
    """Every MXNET_* / MXCTL_* name that stands as a string literal in the
    library, the tools, the benchmark, chip_smoke.py or the suite's
    conftest: what a process reads from, or hands to, its environment."""
    read = {}
    files = []
    for entry in ENV_READERS:
        path = os.path.join(ROOT, entry)
        if os.path.isfile(path):
            files.append(path)
            continue
        for dirpath, dirs, names in os.walk(path):
            dirs[:] = [d for d in dirs if d not in _NOT_TREE]
            files.extend(os.path.join(dirpath, n) for n in names
                         if n.endswith(".py"))
    for path in files:
        for name in re.findall(r"[\"'](%s)[\"']" % _ENV_NAME,
                               open(path).read()):
            read.setdefault(name, os.path.relpath(path, ROOT))
    return read


def test_env_vars_rows_are_read():
    read = _env_read()
    stale = sorted(n for n in _env_rows() if n not in read)
    assert not stale, "docs/env_vars.md has rows nothing reads: %s" % stale


def test_env_vars_read_are_rows():
    rows = _env_rows()
    missing = sorted("%s (%s)" % (n, f) for n, f in _env_read().items()
                     if n not in rows)
    assert not missing, "read but not in docs/env_vars.md: %s" % missing
