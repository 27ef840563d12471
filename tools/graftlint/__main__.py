"""CLI: python -m tools.graftlint [paths...] [options].

Exit codes: 0 clean (new findings == 0; baselined findings are reported
but non-fatal), 1 new findings or parse errors, 2 usage error.

Fast pre-commit loop: `python -m tools.graftlint --changed` lints only
the files git says changed — the phase-1 parse/index still covers the
whole default tree, so interprocedural context (call-graph colors)
stays project-accurate while phase 2 pays only for the diff.
Machine-readable output: `--jsonl` emits one JSON object per finding
(rule, path, line, col, message, suppressed, baselined).
"""
import argparse
import json
import subprocess
import sys

from .core import DEFAULT_BASELINE, REPO_ROOT, RULES, run, write_baseline
from . import rules  # noqa: F401
from .selftest import run_selftest

# the tree the tier-0 gate lints (and the phase-1 index default)
TREE_PATHS = ("paddle_tpu/", "tests/", "tools/")


def _git_changed_files():
    """Repo-relative .py files git reports as changed (worktree +
    index) or untracked — the --changed scope."""
    out = set()
    for args in (["git", "diff", "--name-only", "HEAD"],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        proc = subprocess.run(args, capture_output=True, text=True,
                              cwd=REPO_ROOT, timeout=60)
        if proc.returncode != 0:
            raise SystemExit(f"--changed needs git: {proc.stderr.strip()}")
        out.update(ln.strip() for ln in proc.stdout.splitlines()
                   if ln.strip().endswith(".py"))
    return sorted(REPO_ROOT / p for p in out if (REPO_ROOT / p).exists())


def _emit_jsonl(res, out=sys.stdout):
    rows = (
        [(f, False, False) for f in res.new]
        + [(f, False, True) for f in res.baselined]
        + [(f, True, False) for f in res.suppressed_findings])
    for f, suppressed, baselined in sorted(
            rows, key=lambda r: (r[0].path, r[0].line, r[0].code)):
        print(json.dumps({
            "rule": f.code, "path": f.path, "line": f.line,
            "col": f.col, "message": f.message,
            "suppressed": suppressed, "baselined": baselined,
        }, sort_keys=True), file=out)
    for err in res.parse_errors:
        # a machine consumer must see WHY the exit code is red even
        # when zero findings parsed out of the tree
        path, _, msg = err.partition(": ")
        print(json.dumps({
            "rule": "PARSE_ERROR", "path": path, "line": 0, "col": 0,
            "message": msg or err, "suppressed": False,
            "baselined": False,
        }, sort_keys=True), file=out)


def _emit_sarif(res, out=sys.stdout):
    """SARIF 2.1.0, minimal: rule id, level, message, physical
    location — enough for CI diff annotation. New findings are
    `error`, baselined `note`, suppressed findings carry the SARIF
    `suppressions` property (so a viewer greys them out instead of
    losing them)."""
    results = []
    rows = ([(f, "error", False) for f in res.new]
            + [(f, "note", True) for f in res.baselined]
            + [(f, "note", False) for f in res.suppressed_findings])
    seen_rules = {}
    for f, level, baselined in sorted(
            rows, key=lambda r: (r[0].path, r[0].line, r[0].code)):
        seen_rules.setdefault(f.code, None)
        entry = {
            "ruleId": f.code,
            "level": level,
            "message": {"text": f.message},
            "locations": [{"physicalLocation": {
                "artifactLocation": {"uri": f.path},
                "region": {"startLine": max(1, f.line),
                           "startColumn": f.col + 1},
            }}],
        }
        if f in res.suppressed_findings and not baselined:
            entry["suppressions"] = [{"kind": "inSource"}]
        elif baselined:
            entry["suppressions"] = [{"kind": "external"}]
        results.append(entry)
    for err in res.parse_errors:
        path, _, msg = err.partition(": ")
        results.append({
            "ruleId": "PARSE_ERROR", "level": "error",
            "message": {"text": msg or err},
            "locations": [{"physicalLocation": {
                "artifactLocation": {"uri": path},
                "region": {"startLine": 1, "startColumn": 1}}}],
        })
    doc = {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "graftlint",
                "informationUri":
                    "tools/graftlint (this repository)",
                "rules": [
                    {"id": code,
                     "shortDescription": {"text": RULES[code].name}}
                    for code in sorted(seen_rules) if code in RULES],
            }},
            "results": results,
        }],
    }
    json.dump(doc, out, indent=2, sort_keys=True)
    print(file=out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m tools.graftlint",
        description="framework-aware static analysis (two-phase: "
                    "project index + context colors, then trace safety, "
                    "Pallas bounds, repo hygiene, "
                    "async/concurrency rules)")
    ap.add_argument("paths", nargs="*",
                    help="files/directories to lint (e.g. paddle_tpu/ "
                         "tests/ tools/)")
    ap.add_argument("--baseline", default=str(DEFAULT_BASELINE),
                    help="baseline allowlist JSON (default: "
                         "tools/graftlint_baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding as new (ignore baseline)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="triage mode: write all current findings to the "
                         "baseline file and exit 0")
    ap.add_argument("--show-baselined", action="store_true",
                    help="also print baselined findings")
    ap.add_argument("--jsonl", action="store_true",
                    help="machine-readable output: one JSON object per "
                         "finding (incl. suppressed + baselined, flagged)")
    ap.add_argument("--sarif", action="store_true",
                    help="SARIF 2.1.0 output (minimal: rule id, level, "
                         "message, physical location) for CI diff "
                         "annotation; same exit-code contract as --jsonl")
    ap.add_argument("--changed", action="store_true",
                    help="lint only git-changed .py files (phase 1 still "
                         "indexes the whole tree for call-graph context)")
    ap.add_argument("--selftest", action="store_true",
                    help="run the known-bad corpus through every rule")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for code, r in sorted(RULES.items()):
            print(f"{code}  {r.name:32s} [{r.family}]")
            print(f"       {r.doc.splitlines()[0] if r.doc else ''}")
        return 0

    if args.selftest:
        return 1 if run_selftest() else 0

    rule_paths = None
    if args.changed:
        if args.write_baseline:
            # a diff-scoped run sees only the changed files' findings:
            # writing that as the baseline would silently DELETE every
            # triaged entry for unchanged files
            ap.error("--write-baseline requires a full-tree run "
                     "(drop --changed)")
        changed = _git_changed_files()
        if not args.paths:
            args.paths = [str(REPO_ROOT / p) for p in TREE_PATHS]
        # the summary's "of N changed" must be honest: only files
        # inside the parse set actually get linted — say so about
        # the rest instead of silently counting them as clean
        roots = [str((REPO_ROOT / p).resolve()) for p in args.paths]
        rule_paths = [p for p in changed
                      if any(str(p).startswith(r.rstrip("/") + "/")
                             or str(p) == r for r in roots)]
        skipped = len(changed) - len(rule_paths)
        if skipped:
            print(f"graftlint: note — {skipped} changed .py file(s) "
                  "outside the linted paths were skipped")
        if not rule_paths:
            print("graftlint: OK — no changed .py files in the "
                  "linted paths")
            return 0
    elif not args.paths:
        ap.error("no paths given (and neither --selftest nor "
                 "--list-rules nor --changed)")

    res = run(args.paths, baseline_path=args.baseline,
              use_baseline=not args.no_baseline, rule_paths=rule_paths)

    if args.write_baseline:
        write_baseline(res.new + res.baselined, path=args.baseline)
        print(f"graftlint: wrote {len(res.new) + len(res.baselined)} "
              f"findings to {args.baseline}")
        return 0

    if args.jsonl:
        _emit_jsonl(res)
        return 1 if (res.new or res.parse_errors) else 0

    if args.sarif:
        _emit_sarif(res)
        return 1 if (res.new or res.parse_errors) else 0

    for f in res.parse_errors:
        print(f"PARSE ERROR {f}")
    if args.show_baselined:
        for f in res.baselined:
            print(f"[baselined] {f.render()}")
    for f in res.new:
        print(f.render())
    status = "FAIL" if (res.new or res.parse_errors) else "OK"
    scope = f" of {len(rule_paths)} changed" if rule_paths is not None \
        else ""
    print(f"graftlint: {status} — {res.files} files{scope}, "
          f"{len(res.new)} new finding(s), {len(res.baselined)} baselined, "
          f"{res.suppressed} suppressed"
          + (f", {len(res.parse_errors)} parse error(s)"
             if res.parse_errors else ""))
    print(f"graftlint: phase1 parse+index {res.phase1_s:.2f}s, "
          f"phase2 rules {res.phase2_s:.2f}s")
    return 1 if (res.new or res.parse_errors) else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # e.g. `... --list-rules | head`
        sys.exit(0)
