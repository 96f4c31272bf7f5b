"""repro-lint framework tests: each rule trips on exactly its known-bad
corpus twin and stays quiet on the known-good one, the suppression
machinery works (and rejects undocumented/stale suppressions), and the
real tree is clean at HEAD."""
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
RUN = REPO / "tools" / "analyze" / "run.py"
CORPUS = REPO / "tests" / "lint_corpus"


def lint(*paths):
    proc = subprocess.run(
        [sys.executable, str(RUN), *[str(p) for p in paths]],
        capture_output=True, text=True, cwd=REPO)
    return proc.returncode, proc.stdout


def rule_hits(out, rule_id):
    return [ln for ln in out.splitlines() if f" {rule_id} " in ln]


CASES = [
    # (rule id, bad target, good target, expected hit count,
    #  expected 1-based lines)
    ("PL001", CORPUS / "pl001" / "kernels" / "bad_kernel.py",
     CORPUS / "pl001" / "kernels" / "good_kernel.py", 3, (11, 14, 17)),
    ("JIT001", CORPUS / "jit001" / "bad",
     CORPUS / "jit001" / "good", 3, (26, 27, 29)),
    ("SEAM001", CORPUS / "seam001" / "bad_policy.py",
     CORPUS / "seam001" / "good_policy.py", 3, (15, 17, 18)),
    ("CFG001", CORPUS / "cfg001" / "bad",
     CORPUS / "cfg001" / "good", 2, (11, 13)),
    ("PHASE001", CORPUS / "phase001" / "bad",
     CORPUS / "phase001" / "good", 2, (14, 24)),
    ("FAULT001", CORPUS / "fault001" / "bad.py",
     CORPUS / "fault001" / "good.py", 3, (13, 17, 21)),
    ("OBS001", CORPUS / "obs001" / "serving" / "bad.py",
     CORPUS / "obs001" / "serving" / "good.py", 3, (12, 16, 21)),
    ("UNIT001", CORPUS / "unit001" / "bad" / "accounting.py",
     CORPUS / "unit001" / "good" / "accounting.py", 3, (14, 18, 22)),
    ("MC001", CORPUS / "mc001" / "bad" / "scheduler.py",
     CORPUS / "mc001" / "good" / "scheduler.py", 6,
     (60, 61, 61, 61, 61, 79)),
]


@pytest.mark.parametrize(
    "rule_id,bad,good,count,lines", CASES,
    ids=[c[0].lower() for c in CASES])
def test_rule_trips_on_bad_quiet_on_good(rule_id, bad, good, count,
                                         lines):
    rc, out = lint(bad)
    assert rc == 1
    hits = rule_hits(out, rule_id)
    assert len(hits) == count, out
    # exactly the targeted rule fires — nothing else in the corpus file
    assert len(out.splitlines()) == count, out
    got_lines = tuple(
        int(re.search(r":(\d+): ", h).group(1)) for h in hits)
    assert got_lines == lines, out

    rc, out = lint(good)
    assert rc == 0
    assert out == "", out


def test_head_is_clean():
    """The acceptance gate: repro-lint over the real tree — source,
    benchmarks, tooling and tests — exits 0 (corpus twins excluded by
    the directory walk)."""
    rc, out = lint(REPO / "src", REPO / "benchmarks",
                   REPO / "tools", REPO / "tests")
    assert rc == 0, out


def test_list_rules_names_all_nine():
    proc = subprocess.run(
        [sys.executable, str(RUN), "--list-rules"],
        capture_output=True, text=True, cwd=REPO)
    listed = {ln.split()[0] for ln in proc.stdout.splitlines()}
    assert {"PL001", "JIT001", "SEAM001", "CFG001", "PHASE001",
            "FAULT001", "OBS001", "UNIT001", "MC001"} <= listed


def test_model_checker_is_deterministic():
    """Two uncached runs over the known-bad twin produce byte-identical
    reports: BFS order, dedup and traces are all deterministic."""
    bad = CORPUS / "mc001" / "bad" / "scheduler.py"
    runs = [lint("--no-cache", bad) for _ in range(2)]
    assert runs[0] == runs[1]
    assert runs[0][0] == 1


def test_model_checker_explores_with_blocks(tmp_path):
    """A phase write under a `with` block (a profiler span around a
    scheduler method's body) is still explored: the known-bad twin with
    `force_finish`'s body under a span trips the same QUEUED -> FINISHED
    violation."""
    src = (CORPUS / "mc001" / "bad" / "scheduler.py").read_text()
    head, body = src.split("    def force_finish(self, r, now):\n")
    f = tmp_path / "scheduler.py"
    f.write_text(head + "    def force_finish(self, r, now):\n"
                 + '        with span("sched.finish"):\n'
                 + "".join("    " + ln if ln.strip() else ln
                           for ln in body.splitlines(True)))
    rc, out = lint("--no-cache", f)
    assert rc == 1
    assert [h for h in rule_hits(out, "MC001")
            if "QUEUED -> FINISHED" in h], out


def test_github_format_and_json():
    bad = CORPUS / "unit001" / "bad" / "accounting.py"
    rc, out = lint("--format=github", bad)
    assert rc == 1
    first = out.splitlines()[0]
    assert first.startswith("::error file=") and ",line=14," in first \
        and "title=UNIT001" in first
    rc, out = lint("--json", bad)
    assert rc == 1
    import json
    hits = json.loads(out)
    assert [h["line"] for h in hits] == [14, 18, 22]
    assert all(h["rule"] == "UNIT001" for h in hits)


def test_result_cache_warm_run_identical(tmp_path):
    """A warm (fully cached) run reports exactly what the cold run did;
    touching the file invalidates its entry."""
    import shutil
    f = tmp_path / "kernels" / "k.py"
    f.parent.mkdir()
    shutil.copy(CORPUS / "pl001" / "kernels" / "bad_kernel.py", f)
    cold = lint(f)
    warm = lint(f)
    assert cold == warm and cold[0] == 1
    # edit the file: the stale entry must not be served
    f.write_text("x = 1\n")
    rc, out = lint(f)
    assert rc == 0 and out == ""


# ------------------------------------------------- suppression machinery --

BAD_WHEN = """\
from jax.experimental import pallas as pl


def kernel(o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        {line}
"""


def _kernel_file(tmp_path, body_line):
    d = tmp_path / "kernels"
    d.mkdir()
    f = d / "k.py"
    f.write_text(BAD_WHEN.format(line=body_line))
    return f


def test_inline_suppression_with_reason_silences(tmp_path):
    f = _kernel_file(
        tmp_path,
        "o_ref[0] = pl.program_id(1)  "
        "# repro-lint: disable=PL001 -- corpus: proving suppression")
    rc, out = lint(f)
    assert rc == 0, out


def test_comment_block_above_suppresses(tmp_path):
    f = _kernel_file(
        tmp_path,
        "# repro-lint: disable=PL001 -- block-comment form\n"
        "        # (second comment line of the same block)\n"
        "        o_ref[0] = pl.program_id(1)")
    rc, out = lint(f)
    assert rc == 0, out


def test_suppression_without_reason_is_rejected(tmp_path):
    f = _kernel_file(
        tmp_path,
        "o_ref[0] = pl.program_id(1)  # repro-lint: disable=PL001")
    rc, out = lint(f)
    assert rc == 1
    assert rule_hits(out, "LINT000"), out
    assert rule_hits(out, "PL001"), out  # and the hit still reports


def test_unused_suppression_is_flagged(tmp_path):
    f = _kernel_file(
        tmp_path,
        "o_ref[0] = i  # repro-lint: disable=PL001 -- nothing here")
    rc, out = lint(f)
    assert rc == 1
    assert rule_hits(out, "LINT001"), out


def test_file_level_suppression(tmp_path):
    f = _kernel_file(
        tmp_path,
        "o_ref[0] = pl.program_id(1)")
    f.write_text("# repro-lint: file-disable=PL001 -- corpus file\n"
                 + f.read_text())
    rc, out = lint(f)
    assert rc == 0, out
