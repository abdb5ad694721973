"""The options the program takes from its environment, against the
table of them in ``docs/RUNBOOK.md``.

Every ``OPENR_*`` variable that ``openr_tpu/`` reads from the process
environment has a row there (what it sets, its default, who reads it),
and the table names no variable the tree does not read: a PR that adds
a variable writes its row, and one that deletes a variable takes its
row out. None of them selects a device code path; that is chosen from
shape and platform inside the module that owns the formulation.
"""

import ast
import pathlib
import re

import pytest

from openr_tpu.analysis.core import dotted_name

REPO = pathlib.Path(__file__).resolve().parent.parent
RUNBOOK = REPO / "docs" / "RUNBOOK.md"
TABLE_HEADING = "## Environment variables the program reads"


def _env_name(node):
    """The constant name a node reads from the environment, if it is
    ``os.environ.get(NAME, ...)``, ``os.getenv(NAME, ...)`` or
    ``os.environ[NAME]``."""
    if (
        isinstance(node, ast.Call)
        and node.args
        and dotted_name(node.func) in ("os.environ.get", "os.getenv")
    ):
        arg = node.args[0]
    elif isinstance(node, ast.Subscript) and (
        dotted_name(node.value) == "os.environ"
    ):
        arg = node.slice
    else:
        return None
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return None


def variables_read() -> dict:
    """``OPENR_*`` name -> the files of ``openr_tpu/`` that read it."""
    found = {}
    for path in sorted((REPO / "openr_tpu").rglob("*.py")):
        text = path.read_text()
        if "OPENR_" not in text:
            continue
        for node in ast.walk(ast.parse(text)):
            name = _env_name(node)
            if name is not None and name.startswith("OPENR_"):
                found.setdefault(name, set()).add(
                    str(path.relative_to(REPO))
                )
    return found


def table_rows() -> dict:
    """Variable -> its row's other cells, from the RUNBOOK's table."""
    lines = RUNBOOK.read_text().splitlines()
    at = lines.index(TABLE_HEADING)
    rows = {}
    in_table = False
    for line in lines[at + 1:]:
        if line.startswith("|"):
            in_table = True
            cells = [c.strip() for c in line.strip("|").split("|")]
            match = re.fullmatch(r"`(OPENR_[A-Z0-9_]+)`", cells[0])
            if match:
                rows[match.group(1)] = cells[1:]
        elif in_table:
            break
    return rows


READ = variables_read()


@pytest.mark.parametrize("name", sorted(READ))
def test_a_variable_the_program_reads_has_its_row(name):
    rows = table_rows()
    assert name in rows, (
        f"{name} is read by {sorted(READ[name])} and has no row under "
        f"{TABLE_HEADING!r} in docs/RUNBOOK.md"
    )
    sets, default, read_by = rows[name]
    assert sets and default, rows[name]
    for path in READ[name]:
        assert path in read_by, (name, path, read_by)


def test_the_table_names_no_variable_the_program_does_not_read():
    assert READ, "the walk found no variable at all"
    assert sorted(set(table_rows()) - set(READ)) == []
