"""The tree's programs against the record (``tools/program_fingerprint.py``):
every line of ``tests/recorded/program_fingerprints.json`` — what each
benchmark configuration's class lowers to at toy size, and the device
scopes of the compiled program — is reproduced, a line a case.  A PR that
MEANS to change a program records the file again with the tool's command
and names the lines that moved in ``CHANGES.md``."""
import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "tests", "recorded",
                       "program_fingerprints.json")) as _f:
    RECORDED = json.load(_f)


@pytest.fixture(scope="module")
def programs():
    spec = importlib.util.spec_from_file_location(
        "program_fingerprint",
        os.path.join(REPO, "tools", "program_fingerprint.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool.programs(REPO)      # one toy model a configuration


def test_every_program_is_recorded(programs):
    assert sorted(programs) == sorted(RECORDED)


@pytest.mark.parametrize("line", sorted(RECORDED))
def test_program_is_the_recorded_one(programs, line):
    assert programs[line]() == RECORDED[line]
