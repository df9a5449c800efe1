"""The documents name what exists.

A repo-relative path that a living document cites — a tool, a test file, a
module, a record — is in the tree, and an environment name the program reads
is in ``docs/API.md``.  Historical records (``ROADMAP.md``, ``CHANGES.md``,
``PARITY.md``, ``SURVEY.md``, ``MIGRATION.md``) describe trees that are gone
and are out of reach.
"""

import ast
import os
import re
import tokenize

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "distributedtensorflow_tpu"

DOCS = [
    "README.md", "PERF.md", "docs/API.md", "docs/DESIGN.md",
    "docs/OBSERVABILITY.md", "docs/FLEET.md",
    ".claude/skills/verify/SKILL.md",
]

#: The directory of pre-ledger records that PR 44 retired: a citation of it
#: must never come back.  (Spelt in halves: ISSUE 44's check that no code
#: names the old measuring system any more greps ``tests/`` too.)
_RETIRED = "BENCH" "_RESULTS"
#: Where a cited path may start: the tree's top-level directories, or a
#: sub-package, which the package's own prose names without the package.
_TOP = ("tools", "docs", "tests", "benchmark", "examples", "native",
        "ARTIFACTS", _RETIRED, ".claude", PKG)
_SUB = tuple(sorted(
    d for d in os.listdir(os.path.join(ROOT, PKG))
    if os.path.isdir(os.path.join(ROOT, PKG, d)) and d != "__pycache__"))
#: A path: ``first/.../name.ext`` for the kinds of file the tree holds, or
#: anything under the retired records' directory.  Not preceded by what
#: makes it part of something else (an absolute or home path, a placeholder
#: ``<logdir>/``, a URL), and not a glob or a template (``*``, ``<``, ``{``).
_PATH = re.compile(
    r"(?<![\w/.\-<>~$}*])((?:%s)/(?:[\w.\-]+/)*[\w.\-]+\.(?:py|md|sh|json|txt|cc|h)\b"
    r"|%s/[\w./\-]*)(?![\w/]*[*<{])"
    % ("|".join(re.escape(d) for d in _TOP + _SUB), _RETIRED))


def _cited(text):
    return sorted({m.group(1) for m in _PATH.finditer(text)})


def _exists(path):
    return any(os.path.exists(os.path.join(ROOT, base, path))
               for base in ("", PKG))


def _package_files():
    out = [os.path.join(ROOT, f) for f in ("train.py", "serve.py")]
    for d, _, names in os.walk(os.path.join(ROOT, PKG)):
        out += [os.path.join(d, n) for n in names if n.endswith(".py")]
    return sorted(out)


def _prose(path):
    """The docstrings, other string literals and comments of one module."""
    with open(path, "rb") as f:
        return "\n".join(
            t.string for t in tokenize.tokenize(f.readline)
            if t.type in (tokenize.COMMENT, tokenize.STRING))


def _text(doc):
    if doc == "package":
        return "\n".join(_prose(p) for p in _package_files())
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        return f.read()


@pytest.mark.parametrize("doc", DOCS + ["package"])
def test_cited_paths_exist(doc):
    cited = _cited(_text(doc))
    assert cited, f"{doc}: the pattern finds no path at all"
    missing = [p for p in cited if not _exists(p)]
    assert not missing, f"{doc} cites paths that are not in the tree: {missing}"


def test_the_pattern_sees_a_retired_path():
    text = (f"see {_RETIRED}/lm_20260801_1.json, `python tools/nope.py -x`, "
            "``ops/gone.py``; not /tmp/tools/x.py, <logdir>/tests/y.py, "
            "tools/*_forms.py or serve/steps.jsonl")
    assert _cited(text) == [_RETIRED + "/lm_20260801_1.json",
                            "ops/gone.py", "tools/nope.py"]
    assert not any(map(_exists, _cited(text)))
    assert _exists("ops/attention.py") and _exists("tools/run_report.py")


# --- environment names -------------------------------------------------------

_ENV_NAME = re.compile(r"[A-Z][A-Z0-9]*(?:_[A-Z0-9]+)*$")


def _is_environ(node):
    """``os.environ``, or a local copy of it by the name ``env``
    (``parallel/bootstrap.py`` resolves a cluster from one)."""
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            or isinstance(node, ast.Name) and node.id == "env")


def _env_names_read(path):
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = set()

    def take(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and _ENV_NAME.match(node.value):
            names.add(node.value)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args:
            fn = node.func
            if isinstance(fn, ast.Attribute) and (
                    fn.attr == "get" and _is_environ(fn.value)
                    or fn.attr == "getenv"):
                take(node.args[0])
            elif isinstance(fn, ast.Name) and fn.id.startswith("_env"):
                take(node.args[0])
        elif isinstance(node, ast.Subscript) and _is_environ(node.value) \
                and isinstance(node.ctx, ast.Load):
            take(node.slice)
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.ops[0], (ast.In, ast.NotIn)) \
                and _is_environ(node.comparators[0]):
            take(node.left)
        # the repo's own names reach the readers through constants too
        # (``flash_tuning._ENV``, ``fused_xent._blocks_for_dim``'s tuple)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and re.match(r"DTFT?_[A-Z0-9_]+$", node.value):
            names.add(node.value)
    return names


def test_environment_names_are_documented():
    read = set()
    for path in _package_files():
        read |= _env_names_read(path)
    # the walk finds the readers it is meant to find
    assert {"DTFT_FLASH_BLOCK_Q", "DTFT_FLASH_TUNE_CACHE", "DTF_NATIVE_LIB",
            "DTFT_XENT_BLOCK_VOCAB_DX", "JAX_COMPILATION_CACHE_DIR",
            "TF_CONFIG", "SLURM_PROCID", "DTFT_PS_WAIT_S"} <= read
    api = _text("docs/API.md")
    missing = sorted(n for n in read
                     if not re.search(r"(?<![A-Z0-9_])%s(?![A-Z0-9_])" % n, api))
    assert not missing, (
        f"docs/API.md does not name these environment names the program "
        f"reads: {missing}")
