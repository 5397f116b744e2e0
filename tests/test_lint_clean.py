"""The self-clean CI gate: otpu-lint over the whole package must report
zero non-baselined violations.

The baseline (``lint_suppressions.txt`` at the repo root) may only carry
justified, per-entry-commented exceptions — and only ones that still
fire: unused entries fail the gate, so the file can only shrink.
"""
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
BASELINE = REPO / "lint_suppressions.txt"


def test_package_is_lint_clean():
    """In-process gate: every pass (the PR 6 five + the otpu-verify
    interprocedural three) over every package file."""
    from ompi_tpu import analysis

    sup = analysis.Suppressions.load(str(BASELINE))
    res = analysis.lint([str(REPO / "ompi_tpu")], suppressions=sup)
    assert res.passes == 8
    assert res.files > 100          # the whole package, not a subtree
    assert not res.errors, [f.format() for f in res.errors]
    assert not res.findings, "\n".join(f.format() for f in res.findings)
    assert not sup.unused(), [
        f"{BASELINE}:{e.line_no} suppresses nothing — remove it"
        for e in sup.unused()]
    # the breakdown itself is always well-formed (one row per pass)
    assert len(res.timings) == res.passes
    assert all(t >= 0 for _n, t in res.timings)


def test_baseline_entries_are_justified():
    """Every baseline entry carries a comment: either trailing on the
    line or in the comment block immediately above it."""
    lines = BASELINE.read_text().splitlines()
    for i, raw in enumerate(lines):
        code = raw.split("#", 1)[0].strip()
        if not code:
            continue
        has_trailing = "#" in raw
        has_block_above = i > 0 and lines[i - 1].strip().startswith("#")
        assert has_trailing or has_block_above, (
            f"{BASELINE}:{i + 1}: suppression {code!r} has no "
            "justification comment")


def test_acceptance_command_exits_zero():
    """The exact acceptance-criteria invocation, from the repo root."""
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.otpu_lint", "ompi_tpu/"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout
