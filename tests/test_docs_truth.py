"""The documents name only what exists.

A reader who follows a path, a script name or an `obs` subcommand out of
`README.md`, `docs/`, the examples' README, the verify notes or the CI
script must land on a file or a parser that is there. Scans text only: no
jax, no subprocess.

* every token that is a path under one of the repository's own directories
  exists in the checkout (a glob has to match something);
* every bare ``name.py`` token is the basename of some file of the checkout;
* each `python -m skellysim_tpu.obs` subcommand parses ``--help`` and has a
  section in `docs/observability.md` that shows it; the removed ones are
  refused by the parser and named by no document, and neither is anything
  else of the measuring tools that went with them (`REFUSED`).

The records of the past (`CHANGES.md`, `PERF.md`, `ROADMAP.md`, `ISSUE.md`)
are not documents in this sense and are not scanned.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md", "examples/README.md",
              ".claude/skills/verify/SKILL.md", "ci/run_ci.sh"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))))

#: the repository's own top-level directories: a token that starts with one
#: of these is a claim that the path exists
ROOTS = ("skellysim_tpu", "scripts", "tests", "docs", "examples",
         "benchmarks", "chipbench", "ci")

#: names that belong to another project: each with its reason
ALLOWED = {
    "docs/source/listener.rst": "upstream's listener documentation",
    "skelly_config.py": "upstream's config module, which `config/` mirrors",
    "cache_key.py": "JAX's own `jax/_src/cache_key.py`",
}

SUBCOMMANDS = ("summarize", "flight", "cost", "profile", "timeline")
REMOVED_SUBCOMMANDS = ("roofline", "perf", "campaign")
#: what went with the removed subcommands: no document sends a reader there
REFUSED = ("bench.py", "benchmarks/", "device_peaks", "render-headlines",
           "import bench")

_PATH = re.compile(r"(?<![\w/.\-])((?:%s)/[\w./*{}<>\-]*)" % "|".join(ROOTS))
_PY = re.compile(r"(?<![\w\-])([\w./\-]*\w\.py)\b")


def _read(rel):
    with open(os.path.join(REPO, rel)) as fh:
        return fh.read()


def _placeholder(token):
    return any(mark in token for mark in ("<", "{", "NN"))


def _strip(token):
    """A path as the file system knows it: no ``::test``, ``:line``,
    ``#anchor`` or sentence punctuation after it."""
    token = token.split("::")[0].split("#")[0]
    token = re.sub(r":[\d\-,]*$", "", token)
    return token.rstrip(".,;:)")


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d not in ("chiprun_out", "__pycache__")
                   and (not d.startswith(".") or d == ".claude")]
        names.update(files)
    return names


def _exists(path):
    full = os.path.join(REPO, path)
    if "*" in path:
        return bool(glob.glob(full))
    return os.path.exists(full)


@pytest.mark.parametrize("rel", DOCUMENTS)
def test_names_only_what_exists(rel):
    text = _read(rel)
    basenames = _basenames()
    missing = []
    for m in _PATH.finditer(text):
        token = m.group(1)
        if _placeholder(token):
            continue
        path = _strip(token)
        if path in ALLOWED or path.rstrip("/") in ROOTS:
            continue
        if not _exists(path):
            missing.append(path)
    for m in _PY.finditer(text):
        token = m.group(1)
        if token.startswith("/") or _placeholder(token):
            continue   # an absolute path is the upstream checkout's
        if _strip(token) in ALLOWED or os.path.basename(token) in ALLOWED:
            continue
        if os.path.basename(token) not in basenames:
            missing.append(token)
    assert not missing, (
        f"{rel} names what is not in the checkout: {sorted(set(missing))}")


def _obs_main(argv):
    from skellysim_tpu.obs.cli import main

    with pytest.raises(SystemExit) as exc:
        main(argv)
    return exc.value.code


@pytest.mark.parametrize("cmd", SUBCOMMANDS)
def test_obs_subcommand_is_documented_and_parses(cmd, capsys):
    assert _obs_main([cmd, "--help"]) == 0
    assert f"obs {cmd}" in capsys.readouterr().out
    sections = re.split(r"^## ", _read("docs/observability.md"), flags=re.M)
    shown = re.compile(r"python -m\s+skellysim_tpu\.obs\s+%s\b" % cmd)
    assert any(shown.search(sec) for sec in sections[1:]), (
        f"docs/observability.md has no section that shows `obs {cmd}`")


def test_removed_obs_subcommands_are_refused(capsys):
    for cmd in REMOVED_SUBCOMMANDS:
        assert _obs_main([cmd]) == 2, cmd
    capsys.readouterr()
    named = re.compile(r"obs\s+(%s)\b" % "|".join(REMOVED_SUBCOMMANDS))
    for rel in DOCUMENTS:
        text = _read(rel)
        hit = named.search(text)
        assert hit is None, f"{rel} still names `{hit.group(0)}`"
        for name in REFUSED:
            assert name not in text, f"{rel} still names {name!r}"
