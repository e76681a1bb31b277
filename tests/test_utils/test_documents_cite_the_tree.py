"""The user-facing documents cite the tree: every file, environment variable,
root script and telemetry subcommand that `README.md`, `docs/README.md` and
`howto/*.md` name has to exist. A document that tells its reader to run a
file that was deleted fails here, in the PR that deleted it.

Not covered: the records (`PERF.md`, `ROADMAP.md`, `CHANGES.md`, `VERDICT.md`,
`SURVEY.md`), which speak of history, and `benchmarks/README.md`."""

import contextlib
import glob
import io
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DOCUMENTS = sorted(
    os.path.relpath(path, ROOT)
    for path in [os.path.join(ROOT, "README.md"), os.path.join(ROOT, "docs", "README.md")]
    + glob.glob(os.path.join(ROOT, "howto", "*.md"))
)

# A path is looked for under each of these: the documents write
# `configs/exp/ppo.yaml` and `ppo/ppo.py` for files of the package.
BASES = ("", "sheeprl_tpu", os.path.join("sheeprl_tpu", "configs"), os.path.join("sheeprl_tpu", "algos"))
FILE_SUFFIXES = (".py", ".md", ".yaml", ".yml", ".json", ".jsonl", ".sh", ".toml")
# A token with one of these is a pattern or a placeholder, not a file.
PLACEHOLDERS = ("*", "<", "{", "$", "...")
# Where a variable has to be read for a document to tell a user to set it.
VARIABLE_READERS = ("sheeprl_tpu", "scripts", "tests", "chip_smoke.py")
# `howto/register_external_algorithm.md` tells its reader to write this file.
SCRIPTS_THE_READER_WRITES = {"launcher.py"}


def _source_names():
    names = set(os.listdir(ROOT))
    for top in ("sheeprl_tpu", "scripts", "tests", "benchmarks", "examples"):
        for _dir, _subdirs, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    return names


def _dead_files(text, source_names):
    """Back-quoted tokens that read as a file of the repo and are not one. A
    token with a `/` is a path; a bare `name.py` or `name.sh` is a source file
    somewhere in the tree (other bare names, `telemetry.jsonl`, are what a run
    writes)."""
    for token in re.findall(r"`([^`\n]+)`", text):
        token = re.sub(r":\d+(-\d+)?$", "", token.strip())
        if not token.endswith(FILE_SUFFIXES) or " " in token or any(mark in token for mark in PLACEHOLDERS):
            continue
        if "/" in token:
            if not any(os.path.exists(os.path.join(ROOT, base, token)) for base in BASES):
                yield token
        elif token.endswith((".py", ".sh")) and not token.startswith(".") and token not in source_names:
            yield token


def _read_variables():
    sources = []
    for reader in VARIABLE_READERS:
        path = os.path.join(ROOT, reader)
        files = [path] if os.path.isfile(path) else glob.glob(os.path.join(path, "**", "*.*"), recursive=True)
        for name in files:
            if name.endswith((".py", ".sh", ".yaml")) and os.path.abspath(name) != os.path.abspath(__file__):
                with open(name, encoding="utf-8") as fp:
                    sources.append(fp.read())
    return set(re.findall(r"SHEEPRL_[A-Z0-9_]+", "\n".join(sources)))


def _telemetry_subcommands():
    # The parser is the authority: its help names the subcommands in braces.
    from sheeprl_tpu.telemetry.__main__ import main

    usage = io.StringIO()
    with contextlib.redirect_stdout(usage), pytest.raises(SystemExit):
        main(["--help"])
    return set(re.search(r"\{([a-z_,]+)\}", usage.getvalue()).group(1).split(","))


@pytest.fixture(scope="module")
def source_names():
    return _source_names()


@pytest.fixture(scope="module")
def read_variables():
    return _read_variables()


@pytest.fixture(scope="module")
def telemetry_subcommands():
    return _telemetry_subcommands()


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_cites_the_tree(document, source_names, read_variables, telemetry_subcommands):
    with open(os.path.join(ROOT, document), encoding="utf-8") as fp:
        text = fp.read()
    dead = [f"file `{token}`" for token in _dead_files(text, source_names)]
    dead += [f"variable {name}" for name in set(re.findall(r"SHEEPRL_[A-Z0-9_]+", text)) - read_variables]
    for script in set(re.findall(r"\bpython3? ([A-Za-z0-9_]+\.py)\b", text)) - SCRIPTS_THE_READER_WRITES:
        if not any(os.path.isfile(os.path.join(ROOT, base, script)) for base in ("", "scripts")):
            dead.append(f"command `python {script}`")
    for word in set(re.findall(r"python3? -m sheeprl_tpu\.telemetry\s+([a-z_]+)", text)) - telemetry_subcommands:
        dead.append(f"subcommand `python -m sheeprl_tpu.telemetry {word}`")
    assert not dead, f"{document} cites what the tree does not have: " + "; ".join(sorted(dead))
