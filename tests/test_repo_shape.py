"""The repo's shape: one benchmark (``perfbench/``), documents whose
commands exist, and a knob registry with no orphan.

Reads files only; nothing here imports JAX or runs a program.
"""

import importlib.util
import os
import re

import pytest

from tpudl.analysis.lint import REGISTRY_TARGETS
from tpudl.analysis.registry import KNOBS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SKILL = ".claude/skills/verify/SKILL.md"
#: Where code and runnable instructions live (PERF.md, CHANGES.md and
#: ROADMAP.md tell the history and may name what went).
CODE_ROOTS = (
    "tpudl", "tests", "scripts", "perfbench", "notebooks",
    "chip_smoke.py", "__graft_entry__.py",
)


def _files(roots, suffixes):
    for root in roots:
        path = os.path.join(ROOT, root)
        if os.path.isfile(path):
            yield root
            continue
        for base, dirs, names in os.walk(path):
            dirs[:] = [d for d in dirs if not d.startswith((".", "__"))]
            for name in names:
                if name.endswith(suffixes):
                    yield os.path.relpath(os.path.join(base, name), ROOT)


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def test_nothing_imports_or_invokes_a_second_benchmark():
    """PR 43 removed the old benchmark (a top-level script, a package
    of per-topic modules and a regression gate over their output) with
    its simulated device; nothing may import, run or cite it again."""
    gone = re.compile(
        r"(from|import) +(bench" r"marks|bench)\b|bench" r"marks(/|\.\w)"
        r"|\bbench" r"\.py|bench" r"_regress|BENCH" r"_r0"
    )
    hits = []
    for rel in list(_files(CODE_ROOTS, (".py", ".sh"))) + [SKILL, "README.md"]:
        if not os.path.exists(os.path.join(ROOT, rel)):
            continue
        for n, line in enumerate(_read(rel).splitlines(), 1):
            if gone.search(line):
                hits.append(f"{rel}:{n}: {line.strip()}")
    assert not hits, "\n".join(hits)
    for name in ("bench" ".py", "bench" "marks", "scripts/bench" "_regress.py"):
        assert not os.path.exists(os.path.join(ROOT, name)), name


def _resolves(module):
    path = os.path.join(ROOT, *module.split("."))
    if os.path.isfile(path + ".py") or os.path.isfile(
        os.path.join(path, "__main__.py")
    ):
        return True
    # An installed tool (pytest, ...), not a module of this tree.
    top = module.split(".")[0]
    return (
        not os.path.exists(os.path.join(ROOT, top))
        and importlib.util.find_spec(top) is not None
    )


@pytest.mark.parametrize("doc", ["scripts/ci_check.sh", "README.md", SKILL])
def test_every_command_a_document_gives_resolves(doc):
    """Every ``python <path>`` / ``python -m <module>`` that a reader is
    told to run names a file in the tree (or an installed tool)."""
    if not os.path.exists(os.path.join(ROOT, doc)):
        pytest.skip(f"{doc} is not in this checkout")
    text = _read(doc)
    missing = []
    for module in re.findall(r"python3? +-m +([\w.]+)", text):
        if not _resolves(module):
            missing.append(f"python -m {module}")
    for path in re.findall(r"python3? +((?!-)[\w./-]+\.py)\b", text):
        if not path.startswith("/") and not os.path.isfile(
            os.path.join(ROOT, path)
        ):
            missing.append(f"python {path}")
    assert not missing, f"{doc} tells a reader to run: {sorted(set(missing))}"
    assert re.search(r"python3? ", text), f"{doc} gives no command at all"


def test_every_declared_knob_is_read_under_tpudl():
    """A knob the registry declares and no module reads is an option
    with no behaviour behind it (two went that way in PR 43)."""
    sources = {
        rel: _read(rel)
        for rel in _files(("tpudl",), (".py",))
        if rel != os.path.join("tpudl", "analysis", "registry.py")
    }
    orphans = [
        name for name in KNOBS
        if not any(f'"{name}"' in text for text in sources.values())
    ]
    assert not orphans, orphans


def test_registry_targets_exist():
    missing = [
        t for t in REGISTRY_TARGETS
        if not os.path.exists(os.path.join(ROOT, t))
    ]
    assert not missing, missing
