"""Guard: every test file belongs to a Makefile split (or is intentionally
unsplit), so `make test_core && make test_models && ...` never silently
loses coverage as files are added."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# files covered by `make test` only (new files should be slotted into a
# split; list one here only with a reason)
UNSPLIT: set = {
    "test_makefile_splits.py",  # meta: the guard itself
}


# `python tools/x.py`, `python3 benchmark/run.py`, `python chip_smoke.py`
RUN_A_SCRIPT = re.compile(r"python3? +([\w./-]+\.py)\b")


def _makefile():
    with open(os.path.join(REPO, "Makefile")) as f:
        return f.read()


def _targets(makefile: str) -> set:
    return set(re.findall(r"^([A-Za-z_][\w-]*):", makefile, re.M))


def test_every_test_file_is_in_a_split():
    makefile = _makefile()
    listed = set(re.findall(r"tests/(test_\w+\.py)", makefile))
    on_disk = {
        f for f in os.listdir(os.path.join(REPO, "tests"))
        if f.startswith("test_") and f.endswith(".py")
    }
    missing = on_disk - listed - UNSPLIT
    assert not missing, (
        f"test files not in any Makefile split: {sorted(missing)} — add them "
        "to the matching target in Makefile (or to UNSPLIT with a reason)"
    )


def test_every_script_a_recipe_names_exists():
    """A recipe that runs ``python tools/x.py`` or a root script fails only
    when someone runs it; this fails when the file goes."""
    makefile = _makefile()
    recipes = "\n".join(line for line in makefile.splitlines() if line.startswith("\t"))
    named = set(RUN_A_SCRIPT.findall(recipes))
    assert named, "no recipe names a script: the pattern has gone stale"
    assert {p for p in named if not os.path.exists(os.path.join(REPO, p))} == set()
    # and every target of the `test:` chain and of .PHONY is a target
    targets = _targets(makefile)
    chain = re.search(r"^test:(.*)$", makefile, re.M).group(1).split()
    phony = re.search(r"^\.PHONY:(.*)$", makefile, re.M).group(1).split()
    assert set(chain) | set(phony) <= targets


DOCS = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(REPO, "docs")) if f.endswith(".md")
)


@pytest.mark.parametrize("doc", DOCS)
def test_every_script_and_target_a_doc_names_exists(doc):
    """What a document tells its reader to run is there to run: every
    ``python x.py``, ``tools/x.py`` and ``make x`` inside backticks or a
    fenced block names a file of this checkout or a target of the Makefile."""
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    fenced = re.findall(r"^```.*?$(.*?)^```", text, re.M | re.S)
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"^```.*?^```", "", text, flags=re.M | re.S))
    code = "\n".join(fenced + inline)
    scripts = set(RUN_A_SCRIPT.findall(code))
    scripts |= set(re.findall(r"\b(tools/[\w.-]+\.py)\b", code))
    targets = set(re.findall(r"\bmake +([a-z][\w-]*)", code))
    missing = {p for p in scripts if not os.path.exists(os.path.join(REPO, p))}
    missing |= targets - _targets(_makefile())
    assert missing == set(), f"{doc} names what is not there: {sorted(missing)}"

