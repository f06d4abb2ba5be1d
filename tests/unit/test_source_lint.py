"""Repo-wide source lint gate (tier-1): unused imports + undefined names.

The policy lives in ``ruff.toml``; this test enforces its two correctness
rules (F401/F821) via the stdlib implementation in
``nxdi_tpu/analysis/source_lint.py`` so the gate holds in environments
without ruff. A PR that introduces an unused import or an undefined name
fails tier-1 here.
"""

import os

from nxdi_tpu.analysis.source_lint import lint_paths, lint_source

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# -- self-tests: the linter actually catches what it claims to catch --------

def test_detects_unused_import():
    errs = lint_source("x.py", "import os\nimport sys\nprint(sys.path)\n")
    assert [e.code for e in errs] == ["F401"]
    assert "'os'" in errs[0].message and errs[0].line == 1


def test_detects_unused_from_import():
    errs = lint_source("x.py", "from typing import Any, Dict\nx: Dict = {}\n")
    assert [e.code for e in errs] == ["F401"]
    assert "Any" in errs[0].message


def test_detects_undefined_name():
    errs = lint_source("x.py", "def f():\n    return not_defined_anywhere\n")
    assert any(e.code == "F821" and "not_defined_anywhere" in e.message for e in errs)
    # reported at the USE line, so the ruff/pyflakes noqa convention works
    assert errs[0].line == 2
    silenced = "def f():\n    return dynamic_name  # noqa: F821\n"
    assert lint_source("x.py", silenced) == []
    # a def-line noqa must NOT blanket-suppress body errors
    wrong_line = "def f():  # noqa: F821\n    return dynamic_name\n"
    assert any(e.code == "F821" for e in lint_source("x.py", wrong_line))


def test_future_import_and_noqa_and_reexport_are_exempt():
    assert lint_source("x.py", "from __future__ import annotations\n") == []
    assert lint_source("x.py", "import os  # noqa: F401\n") == []
    assert lint_source("x.py", "import os  # noqa\n") == []
    # __init__.py re-export surface
    assert lint_source("pkg/__init__.py", "from pkg.mod import thing\n") == []
    # __all__ marks a binding used
    assert lint_source(
        "x.py", "from m import thing\n__all__ = ['thing']\n"
    ) == []


def test_string_annotation_usage_not_flagged():
    """pyflakes parses string annotations; identifier extraction keeps the
    stdlib linter agreeing (ruff.toml contract)."""
    src = (
        "from typing import Optional\n"
        "from m import Bar\n"
        "def f(x: \"Optional[Bar]\"):\n"
        "    return x\n"
    )
    assert lint_source("x.py", src) == []


def test_detects_bare_print_in_core():
    src = "def f():\n    print('hi')\n"
    errs = lint_source("nxdi_tpu/utils/foo.py", src)
    assert [e.code for e in errs] == ["T201"] and errs[0].line == 2
    # cli/, scripts/, tests/ are exempt — stdout is their interface
    assert lint_source("nxdi_tpu/cli/foo.py", src) == []
    assert lint_source("scripts/foo.py", src) == []
    assert lint_source("tests/unit/foo.py", src) == []
    # noqa silences an intentional print, matching ruff's flake8-print id
    assert lint_source(
        "nxdi_tpu/utils/foo.py", "def f():\n    print('hi')  # noqa: T201\n"
    ) == []


def test_detects_bare_thread_in_core():
    src = (
        "import threading\n"
        "def f():\n"
        "    t = threading.Thread(target=f)\n"
        "    return t\n"
    )
    errs = lint_source("nxdi_tpu/router/foo.py", src)
    assert [e.code for e in errs] == ["NXD001"] and errs[0].line == 3
    assert "daemon and name" in errs[0].message
    # one missing keyword is still a violation, named precisely
    partial = (
        "import threading\n"
        "def f():\n"
        "    return threading.Thread(target=f, daemon=True)\n"
    )
    errs = lint_source("nxdi_tpu/router/foo.py", partial)
    assert [e.code for e in errs] == ["NXD001"] and "name" in errs[0].message
    # both keywords present -> clean; bare `Thread` name counts too
    clean = (
        "from threading import Thread\n"
        "def f():\n"
        "    return Thread(target=f, daemon=True, name='nxdi-x')\n"
    )
    assert lint_source("nxdi_tpu/router/foo.py", clean) == []
    # cli/ and scripts/ are exempt, mirroring T201
    bare = (
        "import threading\n"
        "def f():\n"
        "    return threading.Thread(target=f)\n"
    )
    assert lint_source("nxdi_tpu/cli/foo.py", bare) == []
    assert lint_source("scripts/foo.py", bare) == []
    # noqa silences an intentional one
    silenced = (
        "import threading\n"
        "def f():\n"
        "    return threading.Thread(target=f)  # noqa: NXD001\n"
    )
    assert lint_source("nxdi_tpu/router/foo.py", silenced) == []


def test_closures_globals_and_builtins_not_flagged():
    src = (
        "import os\n"
        "G = 1\n"
        "def outer():\n"
        "    x = os.sep\n"
        "    def inner():\n"
        "        return x + str(G) + len('a') * 0\n"
        "    return inner\n"
    )
    assert lint_source("x.py", src) == []


# -- the gate ---------------------------------------------------------------

def test_repo_is_lint_clean():
    roots = [
        os.path.join(REPO, d)
        for d in ("nxdi_tpu", "tests", "scripts", "bench.py", "chip_smoke.py", "setup.py")
    ]
    errs = lint_paths(roots, repo_root=REPO)
    assert not errs, "source lint violations (see ruff.toml policy):\n" + "\n".join(
        str(e) for e in errs
    )
