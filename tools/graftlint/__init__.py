"""graftlint — framework-aware static analysis for this repo.

v2 made it a two-phase, project-wide analyzer: phase 1 parses every
file once into a shared module index + direct call graph and colors
each function with its execution context (async-handler / serve-loop /
jitted / holds-lock / thread-entry — see project.py); phase 2 runs the
rules against the shared ASTs, with the concurrency family (GL114+)
reading interprocedural context from the index. v3 adds per-object
LOCK IDENTITY (two classes' `self._lock` are two different locks;
aliases and from-imports resolve to the same one) and the lockset
index (locksets.py: effective locksets, lock-order digraph, execution
contexts) powering the GL121-GL123 data-race/deadlock rules.

Run it:            python -m tools.graftlint paddle_tpu/ tests/ tools/
Changed-only:      python -m tools.graftlint --changed  (git-diff scope;
                   phase 1 still indexes the whole tree for call-graph
                   accuracy — the fast pre-commit loop)
Machine output:    python -m tools.graftlint --jsonl <paths>
                   python -m tools.graftlint --sarif <paths>
Self-test corpus:  python -m tools.graftlint --selftest
List rules:        python -m tools.graftlint --list-rules
Suppress a line:   trailing `# graftlint: disable=GL103` (comma list; a
                   comment anywhere on a multi-line statement's span
                   works). Suppressions are CHECKED: one no finding
                   consumes — or naming an unknown rule id — flags
                   GL117 (stale-suppression), so rot is visible.
Suppress a file:   `# graftlint: disable-file=GL103` on its own line
Baseline:          tools/graftlint_baseline.json — triaged pre-existing
                   findings, reported but non-fatal; regenerate with
                   `python -m tools.graftlint --write-baseline <paths>`

Stdlib-only (ast); safe to run before jax or the package import.
"""
from .core import (  # noqa: F401
    Finding, RULES, run, lint_file, load_baseline, write_baseline,
    DEFAULT_BASELINE, CORPUS_DIR, REPO_ROOT,
)
from . import rules  # noqa: F401  (registers all rule families)

__all__ = ["Finding", "RULES", "run", "lint_file", "load_baseline",
           "write_baseline", "DEFAULT_BASELINE", "CORPUS_DIR", "REPO_ROOT"]
