"""graftlint core: finding model, rule registry, suppressions, baseline.

Framework-aware static analysis for this repo (stdlib `ast` only — the
linter must import in a bare CI container, before jax, before anything).
Two of the original rule families encode bugs PR 1 fixed by hand:

* the `from jax import shard_map` import skew that silently wiped 43 of
  47 test files off the collection (trace-safety family),
* the `update_paged_kv_cache` out-of-bounds block-table write (Pallas
  bounds family).

The analyzer runs in TWO PHASES. Phase 1 parses every file exactly once
into a `FileContext` (AST, cached node list, parent links, suppression
sets) and builds one `ProjectIndex` over the whole set (module index,
direct call graph, execution-context colors — see project.py). Phase 2
runs the rules: every rule shares the phase-1 AST via `ctx.walk()` (a
cached node list — no re-parse, no re-walk of the tree per family) and
reads interprocedural context through `ctx.project`.

A rule is a function `fn(ctx) -> iterable[Finding]` registered with the
`@rule(...)` decorator. Findings that carry a `# graftlint:
disable=CODE` comment anywhere on the offending statement's line span
are dropped — and CONSUMED: the post-phase GL117 rule flags any
suppression comment no finding consumed (stale) or naming an unknown
rule id, so suppressions rot visibly. Findings listed in the committed
baseline (tools/graftlint_baseline.json) are reported but don't fail
the run — the baseline is the triage ledger for pre-existing,
understood debt (today: empty).
"""
from __future__ import annotations

import ast
import io
import json
import re
import time
import tokenize
from dataclasses import dataclass, field
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_BASELINE = REPO_ROOT / "tools" / "graftlint_baseline.json"
CORPUS_DIR = Path(__file__).resolve().parent / "corpus"

_SUPPRESS_RE = re.compile(r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\s]+)")
_SUPPRESS_FILE_RE = re.compile(
    r"#\s*graftlint:\s*disable-file=([A-Za-z0-9_,\s]+)")


@dataclass(frozen=True)
class Finding:
    code: str
    path: str          # repo-root-relative posix path
    line: int
    col: int
    message: str
    # additional witness sites ((path, line) pairs) in possibly OTHER
    # files — a lock-order cycle has two acquisition chains; a
    # suppression at any listed site suppresses the whole finding
    extra_sites: tuple = ()

    def render(self):
        return f"{self.path}:{self.line}:{self.col}: {self.code} {self.message}"

    def baseline_key(self):
        return (self.code, self.path, self.line)


@dataclass
class Rule:
    code: str
    name: str
    family: str        # trace-safety | ... | concurrency
    doc: str
    fn: object
    applies: object    # fn(ctx) -> bool
    phase: str = "scan"   # "scan" | "post" (post rules read scan output)
    # "file": findings derive from the scanned file alone. "project":
    # findings (and the suppressions they consume) can span files, so
    # a scoped run (--changed) must not judge their suppressions stale
    scope: str = "file"


RULES: dict[str, Rule] = {}


def _applies_everywhere(ctx):
    return True


def rule(code, name, family, applies=_applies_everywhere, phase="scan",
         scope="file"):
    """Register a rule. `applies(ctx)` scopes it (e.g. Pallas rules only
    look at kernel files); corpus files always pass the scope check so the
    self-test corpus exercises every family regardless of layout.
    `phase="post"` rules run after every scan rule on the file and may
    read `ctx.used_suppressions` (GL117's staleness oracle).
    `scope="project"` declares that findings (and the suppressions they
    consume, via `Finding.extra_sites`) can span files."""

    def deco(fn):
        RULES[code] = Rule(code=code, name=name, family=family,
                           doc=(fn.__doc__ or "").strip(), fn=fn,
                           applies=applies, phase=phase, scope=scope)
        return fn

    return deco


def in_paddle_tpu(ctx):
    return ctx.path.startswith("paddle_tpu/") or ctx.in_corpus


def in_pallas(ctx):
    return "pallas" in ctx.path or ctx.in_corpus


class FileContext:
    """Everything a rule needs about one file, parsed once (phase 1).

    `walk()` hands every rule the SAME cached node list — the tree is
    walked once at parse time, not once per rule family — and
    `project` (attached by the runner) is the phase-1 ProjectIndex for
    interprocedural context."""

    def __init__(self, path, source, in_corpus=False):
        self.path = str(path)          # repo-relative posix
        self.source = source
        self.lines = source.splitlines()
        self.in_corpus = in_corpus
        self.tree = ast.parse(source, filename=self.path)
        self.project = None            # ProjectIndex, set by the runner
        self.scan_scoped = False       # True when phase 2 is a subset
        self.used_suppressions = set()  # (line, code) consumed by findings
        self._parents = {}
        self._all_nodes = []
        for node in ast.walk(self.tree):
            self._all_nodes.append(node)
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node
        # per-line and file-level suppressions, from REAL comment tokens
        # only — a `# graftlint: disable=...` spelled inside a docstring
        # (this package's own docs do it) is prose, not a suppression,
        # and must not feed GL117's staleness ledger
        self.line_suppress = {}
        self.file_suppress = set()
        for i, text in sorted(self._comments().items()):
            m = _SUPPRESS_FILE_RE.search(text)
            if m:
                self.file_suppress.update(
                    c.strip() for c in m.group(1).split(",") if c.strip())
                continue
            m = _SUPPRESS_RE.search(text)
            if m:
                self.line_suppress[i] = {
                    c.strip() for c in m.group(1).split(",") if c.strip()}
        # names numpy is bound to in this module (`import numpy as np`)
        self.numpy_aliases = set()
        for node in self._all_nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "numpy" or a.name.startswith("numpy."):
                        self.numpy_aliases.add(
                            a.asname or a.name.split(".")[0])

    def _comments(self):
        """{line: text} for every COMMENT token in the file (the file
        already parsed, so tokenize failing is a fallback path, not the
        common one)."""
        out = {}
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(self.source).readline):
                if tok.type == tokenize.COMMENT:
                    out[tok.start[0]] = tok.string
        except (tokenize.TokenError, IndentationError, SyntaxError):
            return dict(enumerate(self.lines, 1))
        return out

    def walk(self):
        """The file's nodes, walked ONCE at parse time — every rule
        iterates this cached list instead of re-walking the tree."""
        return self._all_nodes

    def parent(self, node):
        return self._parents.get(node)

    def enclosing_functions(self, node):
        """Innermost-first chain of FunctionDef/AsyncFunctionDef above node."""
        out = []
        cur = self.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(cur)
            cur = self.parent(cur)
        return out

    def finding(self, code, node, message, extra_sites=()):
        return Finding(code=code, path=self.path, line=node.lineno,
                       col=node.col_offset, message=message,
                       extra_sites=tuple(extra_sites))

    def suppression_hits(self, finding, node=None):
        """The (line, code) suppression entries this finding consumes;
        empty == not suppressed. Line 0 stands for a file-level
        `disable-file=` entry. The runner records every hit into
        `used_suppressions` so GL117 can flag the UNUSED remainder."""
        hits = []
        for code in (finding.code, "all"):
            if code in self.file_suppress:
                hits.append((0, code))
        lo = finding.line
        hi = getattr(node, "end_lineno", None) or finding.line
        # a suppression comment anywhere on the offending statement's
        # physical line span counts (multi-line calls put the comment at
        # the end)
        for ln in range(lo, hi + 1):
            present = self.line_suppress.get(ln, set())
            for code in (finding.code, "all"):
                if code in present:
                    hits.append((ln, code))
        return hits

    def is_suppressed(self, finding, node=None):
        return bool(self.suppression_hits(finding, node))


@dataclass
class RunResult:
    new: list = field(default_factory=list)
    baselined: list = field(default_factory=list)
    suppressed_findings: list = field(default_factory=list)
    files: int = 0
    parse_errors: list = field(default_factory=list)
    # per-phase wall time: phase 1 = parse + index, phase 2 = rules
    phase1_s: float = 0.0
    phase2_s: float = 0.0

    @property
    def suppressed(self):
        return len(self.suppressed_findings)

    @property
    def ok(self):
        return not self.new and not self.parse_errors


def iter_py_files(paths):
    """Expand CLI paths to .py files; the self-test corpus and caches are
    never linted as part of a tree run (corpus files are intentionally
    bad — `--selftest` checks them against EXPECTED findings instead)."""
    seen = set()
    for p in paths:
        p = Path(p)
        candidates = [p] if p.is_file() else sorted(p.rglob("*.py"))
        for f in candidates:
            f = f.resolve()
            if f.suffix != ".py" or f in seen:
                continue
            if "__pycache__" in f.parts:
                continue
            try:
                f.relative_to(CORPUS_DIR)
                continue
            except ValueError:
                pass
            seen.add(f)
            yield f


def relpath(f):
    f = Path(f).resolve()
    try:
        return f.relative_to(REPO_ROOT).as_posix()
    except ValueError:
        return f.as_posix()


def _consume_suppression(ctx, index, f, node):
    """True when `f` is suppressed — by a comment on its own statement
    span, or (project-scope findings) at any of its `extra_sites`, which
    may live in ANOTHER file. Consumption is recorded in the ledger of
    the file holding the comment, so GL117 judges every comment against
    the whole run, not one file's slice."""
    hits = ctx.suppression_hits(f, node)
    if hits:
        ctx.used_suppressions.update(hits)
        return True
    for site in f.extra_sites:
        p, ln = site
        octx = ctx if p == ctx.path else (
            index.files.get(p) if index is not None else None)
        if octx is None:
            continue
        present = octx.line_suppress.get(ln, set())
        for code in (f.code, "all"):
            if code in present:
                octx.used_suppressions.add((ln, code))
                return True
        for code in (f.code, "all"):
            if code in octx.file_suppress:
                octx.used_suppressions.add((0, code))
                return True
    return False


def _run_rules(ctx, index, phase):
    """One rule phase over one already-parsed file. Returns
    (findings, suppressed)."""
    findings, suppressed = [], []
    for r in RULES.values():
        if r.phase != phase or not r.applies(ctx):
            continue
        for item in r.fn(ctx):
            f, node = item if isinstance(item, tuple) else (item, None)
            if _consume_suppression(ctx, index, f, node):
                suppressed.append(f)
            else:
                findings.append(f)
    return findings, suppressed


def _lint_ctx(ctx, index=None):
    """Phase 2 for one already-parsed file: scan rules first (recording
    which suppressions their findings consume), then post rules (GL117
    reads the consumption ledger). Returns (findings, suppressed)."""
    f1, s1 = _run_rules(ctx, index, "scan")
    f2, s2 = _run_rules(ctx, index, "post")
    return f1 + f2, s1 + s2


def lint_file(path, in_corpus=False):
    """All raw findings for one file (suppressions applied, no
    baseline). Builds a single-file ProjectIndex, so intra-file
    interprocedural context (the corpus and the introduced-snippet
    gate) still resolves; returns (findings, n_suppressed)."""
    from .project import ProjectIndex
    source = Path(path).read_text()
    ctx = FileContext(relpath(path), source, in_corpus=in_corpus)
    ctx.project = ProjectIndex([ctx])
    findings, suppressed = _lint_ctx(ctx, ctx.project)
    return findings, len(suppressed)


def load_baseline(path=DEFAULT_BASELINE):
    path = Path(path)
    if not path.exists():
        return set()
    data = json.loads(path.read_text())
    return {(e["code"], e["path"], e["line"]) for e in data.get("findings", [])}


def write_baseline(findings, path=DEFAULT_BASELINE, notes=None):
    entries = [
        {"code": f.code, "path": f.path, "line": f.line, "message": f.message}
        for f in sorted(findings, key=lambda f: (f.path, f.line, f.code))
    ]
    payload = {
        "_comment": notes or (
            "Triaged pre-existing graftlint findings. Entries here are "
            "reported but do not fail the run. Regenerate with "
            "`python -m tools.graftlint --write-baseline <paths>`; never "
            "add new code here instead of fixing it."),
        "version": 1,
        "findings": entries,
    }
    Path(path).write_text(json.dumps(payload, indent=2) + "\n")


def run(paths, baseline_path=DEFAULT_BASELINE, use_baseline=True,
        rule_paths=None):
    """Two-phase tree run. Phase 1 parses every file under `paths` once
    and builds the shared ProjectIndex; phase 2 runs the rules — over
    every parsed file, or (``rule_paths``, the --changed fast path) a
    subset, with cross-file colors still computed from the FULL parse
    set so interprocedural context doesn't shrink with the diff."""
    from . import rules  # noqa: F401 — registers all rule modules
    from .project import ProjectIndex
    baseline = load_baseline(baseline_path) if use_baseline else set()
    res = RunResult()

    t0 = time.perf_counter()
    ctxs = []
    for f in iter_py_files(paths):
        res.files += 1
        try:
            ctxs.append(FileContext(relpath(f), Path(f).read_text()))
        except SyntaxError as e:
            res.parse_errors.append(f"{relpath(f)}: {e}")
    index = ProjectIndex(ctxs)
    res.phase1_s = time.perf_counter() - t0

    only = None
    if rule_paths is not None:
        only = {relpath(p) for p in rule_paths}
    t1 = time.perf_counter()
    scanned = []
    for ctx in ctxs:
        if only is not None and ctx.path not in only:
            continue
        ctx.project = index
        ctx.scan_scoped = only is not None
        scanned.append(ctx)
    # ALL scan rules run before ANY post rule: a project-scope finding
    # scanned out of file A may consume a suppression comment in file
    # B, and B's GL117 pass must see that consumption (running post
    # per-file interleaved would judge B's ledger before A wrote to it)
    results = {}
    for ctx in scanned:
        results[ctx.path] = _run_rules(ctx, index, "scan")
    for ctx in scanned:
        findings, suppressed = results[ctx.path]
        f2, s2 = _run_rules(ctx, index, "post")
        res.suppressed_findings.extend(suppressed + s2)
        for fd in findings + f2:
            (res.baselined if fd.baseline_key() in baseline
             else res.new).append(fd)
    res.phase2_s = time.perf_counter() - t1

    res.new.sort(key=lambda f: (f.path, f.line, f.code))
    res.baselined.sort(key=lambda f: (f.path, f.line, f.code))
    return res
