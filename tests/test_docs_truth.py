"""The documents name what is in the tree, and the commands they quote
are commands the apps accept.

Two text-only checks, one case a document (or a quoted command):

- every repo path ending in ``.py`` that a document names exists in
  this checkout. Documents: ``README.md``, ``PARITY.md``, the verify
  skill, every ``docs/*.md``, and the docstrings and comments of each
  subpackage (and of the package's top-level modules as one). A name
  matches by suffix, so ``harness/cli.py`` finds
  ``hpc_patterns_tpu/harness/cli.py``. The tree is walked, not asked of
  git: a copy of the checkout may have no ``.git``.
- every ``python -m hpc_patterns_tpu.apps.<app> ...`` command quoted in
  ``README.md`` and ``docs/*.md`` (continuation lines joined) is
  accepted by that app's ``build_parser().parse_args``. A command that
  does not parse is the document's fault: the parser is what runs.
"""

import importlib
import os
import re
import shlex
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "hpc_patterns_tpu"

#: what building, testing and running leave behind (.gitignore) is not
#: the tree: a scratch copy there must not vouch for a name
SKIPPED_DIRS = frozenset({".git", ".cache", ".chipwork", ".archive_check",
                          "chiprun_out", "__pycache__", ".pytest_cache",
                          ".hypothesis"})

#: names that stand for "some file"
PLACEHOLDERS = frozenset({
    "path/to/script.py",    # the verify skill's "python path/to/script.py"
})

#: how the documents name a fixture pair: bad_/clean_<rule>.py
_TWINS = "bad_/clean_"

_PY_NAME = re.compile(r"[A-Za-z0-9_./-]*[A-Za-z0-9_]\.py\b")


def _tree_files():
    out = []
    for base, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIPPED_DIRS]
        rel = Path(base).relative_to(REPO)
        out.extend((rel / f).as_posix() for f in files
                   if f.endswith(".py"))
    return out


_TREE = _tree_files()


def _exists(name: str) -> bool:
    if _TWINS in name:
        return all(_exists(name.replace(_TWINS, twin))
                   for twin in ("bad_", "clean_"))
    name = name.lstrip("./")
    return any(f == name or f.endswith("/" + name) for f in _TREE)


def _subpackages():
    return sorted(p.name for p in PACKAGE.iterdir()
                  if p.is_dir() and (p / "__init__.py").is_file())


def _documents():
    """(case id, the files whose text is the document)."""
    docs = [("README.md", [REPO / "README.md"]),
            ("PARITY.md", [REPO / "PARITY.md"]),
            (".claude/skills/verify/SKILL.md",
             [REPO / ".claude" / "skills" / "verify" / "SKILL.md"])]
    docs += [(f"docs/{p.name}", [p])
             for p in sorted((REPO / "docs").glob("*.md"))]
    docs += [(f"hpc_patterns_tpu/{name}",
              sorted((PACKAGE / name).rglob("*.py")))
             for name in _subpackages()]
    docs.append(("hpc_patterns_tpu/*.py", sorted(PACKAGE.glob("*.py"))))
    return docs


_DOCUMENTS = _documents()


@pytest.mark.parametrize("files", [d[1] for d in _DOCUMENTS],
                         ids=[d[0] for d in _DOCUMENTS])
def test_named_python_files_exist(files):
    missing = []
    for path in files:
        if not path.is_file():  # the skill is optional in a copy
            continue
        for n, line in enumerate(path.read_text().splitlines(), 1):
            for name in _PY_NAME.findall(line):
                if name in PLACEHOLDERS or _exists(name):
                    continue
                missing.append(f"{path.relative_to(REPO)}:{n}: {name}")
    assert not missing, (
        "named, and not in this checkout:\n  " + "\n  ".join(missing))


# ---------------------------------------------------------------------------

_APP_CMD = re.compile(r"python3? -m hpc_patterns_tpu\.apps\.(\w+)")
_SHELL_ENDS = frozenset({"|", ">", ">>", "&&", "||", ";", "&", "2>&1"})


def _quoted_commands():
    """(case id, app, argv) for every app command a document quotes. A
    launcher line is one command (the launcher's parser sees the
    launched command as its remainder) and the launched app command
    inside it is another."""
    out = []
    docs = [REPO / "README.md"] + sorted((REPO / "docs").glob("*.md"))
    for path in docs:
        lines = path.read_text().splitlines()
        i = 0
        while i < len(lines):
            # join continuation lines, remembering where each began
            text, starts = "", []
            while True:
                starts.append((len(text), i + 1))
                piece = lines[i].rstrip()
                i += 1
                if piece.endswith("\\") and i < len(lines):
                    text += piece[:-1] + " "
                else:
                    text += piece
                    break
            for m in _APP_CMD.finditer(text):
                lineno = [n for off, n in starts if off <= m.start()][-1]
                rest = text[m.end():].split("`", 1)[0]
                argv = []
                for tok in shlex.split(rest, comments=True):
                    if tok in _SHELL_ENDS:
                        break
                    argv.append(tok)
                rel = path.relative_to(REPO).as_posix()
                out.append((f"{rel}:{lineno}", m.group(1), argv))
    return out


_COMMANDS = _quoted_commands()


@pytest.mark.parametrize("app,argv", [c[1:] for c in _COMMANDS],
                         ids=[c[0] for c in _COMMANDS])
def test_quoted_app_commands_parse(app, argv):
    module = importlib.import_module(f"hpc_patterns_tpu.apps.{app}")
    try:
        module.build_parser().parse_args(argv)
    except SystemExit as e:  # argparse's refusal
        pytest.fail(f"{app} refuses {argv!r} (exit {e.code}): "
                    "repair the document, not the parser")
