"""The documents name things that exist.

Every back-quoted repo path, ``python -m <module>`` and ``make
<target>`` in the operator-facing documents must resolve against the
tree, and so must every command the Makefile runs. A file deleted (or a
target removed) without following its references fails here, in the
document that still sends a reader to it.

``chipbench/README.md`` is the benchmark's own and is not checked here.
"""

import functools
import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "docs/ARCHITECTURE.md", "docs/RUNBOOK.md", "COMPONENTS.md"]

_PATH_SUFFIXES = (".py", ".json", ".md", ".sh", "/")
_PATH_TOKEN = re.compile(r"^[\w.][\w./-]*$")
_FENCE = re.compile(r"```.*?```", re.S)
_INLINE = re.compile(r"`([^`\n]+)`")
_PY_MODULE = re.compile(r"\bpython3?\s+-m\s+([A-Za-z_][\w.]*)")
_PY_SCRIPT = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_MAKE = re.compile(r"\bmake\s+(?:-\w+\s+)*([a-z][\w-]*)")


@functools.lru_cache(maxsize=None)
def _ignored_dirs():
    """Directories `.gitignore` lists whole: what building, testing and
    running leave behind (`chiprun_out/`, `build/`, `.jax_cache/`)."""
    with open(os.path.join(ROOT, ".gitignore"), encoding="utf-8") as f:
        return tuple(
            line.strip() for line in f
            if line.strip().endswith("/") and "*" not in line
        )


@functools.lru_cache(maxsize=None)
def _tree():
    """Every file of the checkout, run-time leftovers aside (a stale
    copy of the tree under `build/` must not stand in for a file)."""
    skip = {d.rstrip("/") for d in _ignored_dirs()} | {".git"}
    files = []
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in skip]
        rel = os.path.relpath(base, ROOT)
        files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return tuple(files)


def _code_spans(text):
    """Inline code spans and fenced blocks: where a document names a
    path or a command, as opposed to prose ("make sure ...")."""
    fenced = _FENCE.findall(text)
    inline = _INLINE.findall(_FENCE.sub("", text))
    return inline, fenced + inline


def _is_upstream(token):
    # the reference's own tree (SURVEY.md), named beside ours in the
    # component tables: `openr/decision/`, `openr/if/OpenrCtrl.thrift`
    return token.startswith("openr/")


def _resolves(token, files):
    """A path as the documents write them: from the repo root, or by
    its tail (`ops/spf.py` for `openr_tpu/ops/spf.py`, `decision.py`);
    or what a run writes under a directory `.gitignore` lists."""
    if token.startswith(_ignored_dirs()):
        return True
    if token.endswith("/"):
        return any(
            f.startswith(token) or ("/" + token) in ("/" + f) for f in files
        )
    return any(f == token or f.endswith("/" + token) for f in files)


def _make_rules():
    with open(os.path.join(ROOT, "Makefile"), encoding="utf-8") as f:
        text = f.read()
    return text, set(re.findall(r"^([A-Za-z][\w-]*)\s*:(?!=)", text, re.M))


def _module_exists(name):
    try:
        return importlib.util.find_spec(name) is not None
    except ModuleNotFoundError:
        return False


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    files = _tree()
    inline, code = _code_spans(text)
    _, rules = _make_rules()
    stale = []
    for span in inline:
        # `tests/test_x.py::TestY`, `daemon.py:128-145`, `tools/x.py --flag`
        token = re.split(r"::|:\d|\s", span.strip(), maxsplit=1)[0]
        if (
            token.endswith(_PATH_SUFFIXES)
            and _PATH_TOKEN.match(token)
            and not _is_upstream(token)
            and not _resolves(token, files)
        ):
            stale.append(f"path `{token}`")
    for chunk in code:
        for mod in _PY_MODULE.findall(chunk):
            if not _module_exists(mod):
                stale.append(f"python -m {mod}")
        for script in _PY_SCRIPT.findall(chunk):
            if not _resolves(script, files):
                stale.append(f"python {script}")
        for target in _MAKE.findall(chunk):
            if target not in rules:
                stale.append(f"make {target}")
    assert not stale, f"{doc} names what is not in the tree: {sorted(set(stale))}"


def test_makefile_runs_only_what_exists():
    text, rules = _make_rules()
    files = _tree()
    missing = [
        f"python -m {m}" for m in _PY_MODULE.findall(text)
        if not _module_exists(m)
    ] + [
        f"python {s}" for s in _PY_SCRIPT.findall(text)
        if not _resolves(s, files)
    ]
    phony = re.search(r"^\.PHONY:(.*)$", text, re.M).group(1).split()
    missing += [f".PHONY {t} has no rule" for t in phony if t not in rules]
    prereqs = {
        p
        for line in re.findall(r"^[A-Za-z][\w-]*\s*:(?!=)(.*)$", text, re.M)
        for p in line.split()
    }
    missing += [f"prerequisite {p} has no rule" for p in prereqs - rules]
    assert not missing, sorted(set(missing))
