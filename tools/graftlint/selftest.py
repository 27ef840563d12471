"""graftlint self-test: every rule must catch its known-bad corpus.

Each file under corpus/ declares what the linter must find in a header
line:

    # graftlint-corpus-expect: GL101 GL103 GL103

(`none` asserts the file is CLEAN — the false-positive tripwire; a
clean file must ALSO declare which rules' correct spellings it pins
with `# graftlint-corpus-rule: GL101 GL103 ...`). The self-test fails
if any declared code is missing, if a `none` file raises anything, if
any rule family has no corpus coverage at all, or if a corpus file is
ORPHANED — claimed by no registered rule (its expect/rule header names
only retired codes) — so a refactor that silently lobotomizes a rule
family, or a dead fixture that outlives its rule, fails CI the same
way a reintroduced bug would.
"""
import re
import sys
from collections import Counter
from pathlib import Path

from .core import CORPUS_DIR, RULES, lint_file
from . import rules  # noqa: F401

_EXPECT_RE = re.compile(r"#\s*graftlint-corpus-expect:\s*(.+)")
_CLAIM_RE = re.compile(r"#\s*graftlint-corpus-rule:\s*(.+)")

FAMILIES = ("trace-safety", "mxu", "donation", "pallas-bounds",
            "hygiene", "concurrency", "locksets")


def corpus_expectations(path):
    m = _EXPECT_RE.search(Path(path).read_text())
    if not m:
        raise AssertionError(
            f"{path}: corpus file missing a "
            "`# graftlint-corpus-expect:` header")
    toks = m.group(1).split()
    return [] if toks == ["none"] else toks


def corpus_claims(path):
    """The rule codes a corpus file is CLAIMED by: its expected codes,
    plus (clean tripwires) the `# graftlint-corpus-rule:` header."""
    claims = list(corpus_expectations(path))
    m = _CLAIM_RE.search(Path(path).read_text())
    if m:
        claims.extend(m.group(1).split())
    return claims


def run_selftest(out=sys.stdout):
    """Returns a list of failure strings; empty == pass."""
    failures = []
    covered_families = set()
    files = sorted(CORPUS_DIR.glob("*.py"))
    if not files:
        return [f"no corpus files found under {CORPUS_DIR}"]
    for f in files:
        expected = Counter(corpus_expectations(f))
        findings, _ = lint_file(f, in_corpus=True)
        got = Counter(fd.code for fd in findings)
        for code in got:
            if code in RULES:
                covered_families.add(RULES[code].family)
        if not expected:
            if findings:
                failures.append(
                    f"{f.name}: expected CLEAN, got "
                    + ", ".join(fd.render() for fd in findings))
            continue
        for code, n in expected.items():
            if got[code] < n:
                failures.append(
                    f"{f.name}: expected {n}x {code}, rules raised "
                    f"{got[code]} (all findings: "
                    + (", ".join(fd.render() for fd in findings) or "none")
                    + ")")
        extra = set(got) - set(expected)
        if extra:
            failures.append(
                f"{f.name}: unexpected codes {sorted(extra)} — extend the "
                "expect header if intentional")
    for f in files:
        # orphan check: a fixture no registered rule claims is dead
        # weight that reads as coverage — fail it out of the corpus
        claims = corpus_claims(f)
        known = [c for c in claims if c in RULES]
        unknown = [c for c in claims if c not in RULES]
        if unknown:
            failures.append(
                f"{f.name}: claims unregistered rule(s) {sorted(set(unknown))}"
                " — retire the fixture with the rule, or fix the header")
        if not known:
            failures.append(
                f"{f.name}: ORPHANED — claimed by no registered rule "
                "(clean tripwires must name their rules in a "
                "`# graftlint-corpus-rule:` header)")
    for fam in FAMILIES:
        if fam not in covered_families:
            failures.append(
                f"rule family `{fam}` caught nothing in the corpus — "
                "family lobotomized or corpus gap")
    n = len(files)
    if failures:
        print(f"graftlint selftest: FAIL ({len(failures)} problems, "
              f"{n} corpus files)", file=out)
        for msg in failures:
            print("  " + msg, file=out)
    else:
        print(f"graftlint selftest: OK ({n} corpus files, "
              f"{len(RULES)} rules, {len(FAMILIES)} families covered)",
              file=out)
    return failures


if __name__ == "__main__":
    sys.exit(1 if run_selftest() else 0)
