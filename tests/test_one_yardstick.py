"""One yardstick (PR 30): the pre-chip bench, its records and its notes are
gone, and nothing left in the tree names them.

``benchmark/`` with the driver's ledger is the one way to measure speed,
``chip_smoke.py`` the one way to show the main path runs, ``PERF.md`` the one
builders' account. A sentence that cites a deleted file reads as a
measurement nobody can look up, so each scope below is searched for the
deleted files' names. The records of the work itself (``CHANGES.md``,
``ROADMAP.md``, ``PERF.md``, ``ISSUE.md``) and the benchmark's own
directories (which only a ``benchmark`` PR may reword) are not searched.
"""

import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

#: the files PR 30 deleted (they remain in git at a7afaa4), as a reader
#: would cite them. ``evoxbench.py`` and ``INTEGRITY_VERDICTS`` are other
#: things: the patterns hold them apart.
DELETED = re.compile(
    r"(?<![A-Za-z_])bench\.py"
    r"|bench_trajectory|BENCH_TRAJECTORY"
    r"|BENCH_r\d|BENCH_\*|BENCH_trace"
    r"|MULTICHIP_r\d"
    r"|(?<![A-Za-z_])VERDICT(?![A-Za-z_])"
    r"|(?<![A-Za-z_])ADVICE(?![A-Za-z_])"
    r"|PERF_NOTES"
)

_SKIP_DIRS = {"__pycache__", "benchmark_checks"}


def _python_files(*roots):
    for root in roots:
        for path in sorted((REPO / root).rglob("*.py")):
            if not _SKIP_DIRS.intersection(path.parts):
                yield path


SCOPES = {
    "package": lambda: list(_python_files("evox_tpu")),
    "tests_examples_scripts": lambda: [
        p
        for p in [
            *_python_files("tests", "examples", "tools"),
            *sorted(REPO.glob("*.py")),
        ]
        if p != pathlib.Path(__file__).resolve()
    ],
    "documents": lambda: [
        REPO / "README.md",
        REPO / "CLAUDE.md",
        REPO / "PARITY.md",
        REPO / "SURVEY.md",
        REPO / "BASELINE.md",
        REPO / "docs" / "GUIDE.md",
        REPO / ".claude" / "skills" / "verify" / "SKILL.md",
    ],
}


@pytest.mark.parametrize("scope", sorted(SCOPES))
def test_nothing_names_a_deleted_file(scope):
    files = [p for p in SCOPES[scope]() if p.exists()]
    assert files, scope
    hits = []
    for path in files:
        for lineno, line in enumerate(
            path.read_text(errors="replace").splitlines(), start=1
        ):
            m = DELETED.search(line)
            if m:
                hits.append(f"{path.relative_to(REPO)}:{lineno}: {m.group(0)}")
    assert not hits, "\n".join(hits)


def test_the_deleted_files_are_gone():
    for name in (
        "bench.py",
        "tools/bench_trajectory.py",
        "BENCH_TRAJECTORY.json",
        "VERDICT.md",
        "ADVICE.md",
        "docs/PERF_NOTES.md",
    ):
        assert not (REPO / name).exists(), name
    assert not list(REPO.glob("BENCH_r*.json"))
    assert not list(REPO.glob("MULTICHIP_r*.json"))
