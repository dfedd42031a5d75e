"""petalint engine: one AST pass, pluggable rules, suppressions, baseline.

The engine is rule-agnostic. A :class:`Rule` declares which ``ast`` node
types it wants (:attr:`Rule.interests`) and which repo-relative paths it
applies to (:meth:`Rule.applies_to`); the engine parses each file once,
builds a parent map, and dispatches every node to every interested rule in
a single walk. Rules yield :class:`Finding`\\ s.

Three layers decide whether a finding fails the build:

1. **Inline suppressions** — ``# petalint: disable=<rule>[,<rule>...]`` (or
   ``disable=all``) on the flagged line, or alone on the line directly above
   it. ``# petalint: disable-file=<rule>`` in the first
   :data:`FILE_DIRECTIVE_WINDOW` lines suppresses a rule for the whole file.
   Suppressions are for sites where the flagged construct is *intended*;
   convention is to justify them in the same comment.
2. **Baseline** — a committed JSON file of known findings
   (``{rule, path, line, snippet}``). A current finding exactly matching an
   entry is reported as baselined, not failing. An entry matching *no*
   current finding is itself an error ("stale baseline"): the moment the
   flagged line moves or is fixed, the entry must be deleted — the baseline
   can only shrink, never mask new code.
3. Everything else fails the run (exit code 1).
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import io
import json
import os
import re
import sys
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Type

#: Directories never scanned.
SKIP_DIRS = frozenset({'__pycache__', '.git', '.claude', 'node_modules'})

#: How many leading lines may carry a ``disable-file`` directive.
FILE_DIRECTIVE_WINDOW = 25

_DIRECTIVE_RE = re.compile(
    r'#\s*petalint:\s*(disable|disable-file)\s*=\s*([A-Za-z0-9_,\- ]+)')


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    rule: str
    path: str          # repo-relative, '/'-separated
    line: int          # 1-based
    col: int
    message: str
    snippet: str       # the source line, stripped — the baseline match key

    def baseline_entry(self) -> dict:
        return {'rule': self.rule, 'path': self.path, 'line': self.line,
                'snippet': self.snippet}

    def match_key(self) -> Tuple[str, str, int, str]:
        return (self.rule, self.path, self.line, self.snippet)

    def format(self) -> str:
        return '{}:{}:{}: [{}] {}'.format(self.path, self.line, self.col,
                                          self.rule, self.message)


class ModuleContext:
    """Per-file state shared by every rule during one walk."""

    def __init__(self, relpath: str, source: str, tree: ast.Module):
        self.path = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self._parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent

    # -- navigation ------------------------------------------------------------

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        """Ancestors from the immediate parent up to the module node."""
        cur = self._parents.get(node)
        while cur is not None:
            yield cur
            cur = self._parents.get(cur)

    def enclosing(self, node: ast.AST, types) -> Optional[ast.AST]:
        for anc in self.ancestors(node):
            if isinstance(anc, types):
                return anc
        return None

    def enclosing_function(self, node: ast.AST):
        return self.enclosing(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda))

    def enclosing_class(self, node: ast.AST) -> Optional[ast.ClassDef]:
        return self.enclosing(node, ast.ClassDef)

    def at_import_time(self, node: ast.AST) -> bool:
        """True when ``node`` executes at module import (module or class
        body — not inside any function/lambda *body*). Default-argument
        values and decorator expressions of a module-level ``def`` DO run
        at import, so only descent through a function's body defers."""
        child = node
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if child in anc.body:
                    return False
            elif isinstance(anc, ast.Lambda):
                if child is anc.body:
                    return False
            child = anc
        return True

    def line_of(self, node: ast.AST) -> str:
        lineno = getattr(node, 'lineno', 0)
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ''

    def finding(self, rule: str, node: ast.AST, message: str) -> Finding:
        return Finding(rule=rule, path=self.path,
                       line=getattr(node, 'lineno', 0),
                       col=getattr(node, 'col_offset', 0),
                       message=message, snippet=self.line_of(node))


def dotted_name(node: ast.AST) -> Optional[str]:
    """``'os.path.join'`` for a Name/Attribute chain, else ``None``."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return '.'.join(reversed(parts))
    return None


def call_name(call: ast.Call) -> Optional[str]:
    return dotted_name(call.func)


def walk_excluding_defs(node: ast.AST) -> Iterable[ast.AST]:
    """Walk a subtree without descending into nested function/class
    definitions (their bodies execute elsewhere/later)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        cur = stack.pop()
        yield cur
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(cur))


class Rule:
    """Base class for petalint rules. Subclasses set :attr:`name` (the
    suppression/baseline id), :attr:`interests` (ast node classes routed to
    :meth:`visit`) and override :meth:`applies_to` for path scoping."""

    name: str = ''
    #: One-line description for ``--list-rules`` and the docs catalog.
    description: str = ''
    interests: Tuple[Type[ast.AST], ...] = ()

    def applies_to(self, relpath: str) -> bool:
        return True

    def begin_module(self, ctx: ModuleContext) -> None:
        """Per-file setup (rules are reused across files)."""

    def visit(self, node: ast.AST, ctx: ModuleContext) -> Iterable[Finding]:
        return ()

    def finish(self, ctx: ModuleContext) -> Iterable[Finding]:
        """Called after the walk; for checks needing whole-file state."""
        return ()


class Suppressions:
    """Inline ``# petalint: disable=`` directives of one file.

    Directives are read from actual COMMENT tokens, not raw lines — the
    directive text occurring inside a string literal or docstring (e.g. a
    rule's own documentation) is data, not a suppression."""

    def __init__(self, lines: Sequence[str]):
        self.by_line: Dict[int, set] = {}
        self.file_wide: set = set()
        self._standalone: Dict[int, set] = {}
        for i, comment in self._iter_comments(lines):
            m = _DIRECTIVE_RE.search(comment)
            if not m:
                continue
            kind = m.group(1)
            rules = {r.strip() for r in m.group(2).split(',') if r.strip()}
            if kind == 'disable-file':
                if i <= FILE_DIRECTIVE_WINDOW:
                    self.file_wide |= rules
                continue
            self.by_line.setdefault(i, set()).update(rules)
            if lines[i - 1].strip().startswith('#'):
                # comment-only line: the directive covers the NEXT line too
                self._standalone.setdefault(i + 1, set()).update(rules)

    @staticmethod
    def _iter_comments(lines: Sequence[str]):
        """``(lineno, comment_text)`` for every comment token."""
        source = '\n'.join(lines) + '\n'
        try:
            return [(tok.start[0], tok.string)
                    for tok in tokenize.generate_tokens(
                        io.StringIO(source).readline)
                    if tok.type == tokenize.COMMENT]
        except (tokenize.TokenError, IndentationError, SyntaxError):
            # untokenizable source (engine still reports parse-error
            # findings for it): fall back to raw lines
            return [(i, line) for i, line in enumerate(lines, start=1)]

    def suppressed(self, finding: Finding) -> bool:
        for rules in (self.file_wide,
                      self.by_line.get(finding.line, ()),
                      self._standalone.get(finding.line, ())):
            if 'all' in rules or finding.rule in rules:
                return True
        return False


class Baseline:
    """The committed known-findings file. Entries are exact
    ``(rule, path, line, snippet)`` matches; anything that drifted is a
    stale entry — an error, so the baseline can only shrink."""

    def __init__(self, entries: List[dict], path: Optional[str] = None):
        self.path = path
        self.entries = entries

    @classmethod
    def load(cls, path: str) -> 'Baseline':
        with open(path) as f:
            blob = json.load(f)
        if not isinstance(blob, dict) or 'findings' not in blob:
            raise ValueError('{}: not a petalint baseline (expected a JSON '
                             "object with a 'findings' list)".format(path))
        return cls(list(blob['findings']), path=path)

    @classmethod
    def empty(cls) -> 'Baseline':
        return cls([])

    def split(self, findings: List[Finding]):
        """``(new, baselined, stale_entries)``."""
        keys = {(e.get('rule'), e.get('path'), e.get('line'),
                 e.get('snippet')): e for e in self.entries}
        new, baselined = [], []
        matched = set()
        for f in findings:
            key = f.match_key()
            if key in keys:
                baselined.append(f)
                matched.add(key)
            else:
                new.append(f)
        stale = [e for k, e in keys.items() if k not in matched]
        return new, baselined, stale

    @staticmethod
    def dump(findings: List[Finding], path: str) -> None:
        from petastorm_tpu.utils import atomic_write
        blob = {'version': 1,
                'findings': [f.baseline_entry() for f in findings]}
        atomic_write(path, lambda f: json.dump(blob, f, indent=2,
                                               sort_keys=True))


class Analyzer:
    """Runs a rule set over files: parse once, one walk, dispatch by node
    type."""

    def __init__(self, rules: Sequence[Rule]):
        self.rules = list(rules)
        names = [r.name for r in self.rules]
        if len(set(names)) != len(names):
            raise ValueError('duplicate rule names: {}'.format(sorted(names)))

    def analyze_file(self, path: str, relpath: str) -> List[Finding]:
        with open(path, encoding='utf-8') as f:
            source = f.read()
        return self.analyze_source(source, relpath)

    def analyze_source(self, source: str, relpath: str) -> List[Finding]:
        relpath = relpath.replace(os.sep, '/')
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            return [Finding(rule='parse-error', path=relpath,
                            line=e.lineno or 0, col=e.offset or 0,
                            message='file does not parse: {}'.format(e.msg),
                            snippet=(e.text or '').strip())]
        ctx = ModuleContext(relpath, source, tree)
        active = [r for r in self.rules if r.applies_to(relpath)]
        if not active:
            return []
        for rule in active:
            rule.begin_module(ctx)
        dispatch: Dict[type, List[Rule]] = {}
        findings: List[Finding] = []
        for node in ast.walk(tree):
            rules = dispatch.get(type(node))
            if rules is None:
                rules = [r for r in active
                         if isinstance(node, r.interests or ())]
                dispatch[type(node)] = rules
            for rule in rules:
                findings.extend(rule.visit(node, ctx))
        for rule in active:
            findings.extend(rule.finish(ctx))
        suppressions = Suppressions(ctx.lines)
        return [f for f in findings if not suppressions.suppressed(f)]


def iter_python_files(paths: Sequence[str], root: str):
    """Yield ``(abspath, relpath)`` for every ``.py`` under ``paths``."""
    for path in paths:
        full = path if os.path.isabs(path) else os.path.join(root, path)
        if os.path.isfile(full):
            yield full, os.path.relpath(full, root)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = sorted(d for d in dirnames if d not in SKIP_DIRS)
            for name in sorted(filenames):
                if name.endswith('.py'):
                    f = os.path.join(dirpath, name)
                    yield f, os.path.relpath(f, root)


def analyze_paths(paths: Sequence[str], root: str,
                  rules: Optional[Sequence[Rule]] = None) -> List[Finding]:
    if rules is None:
        from ci.analysis.rules import DEFAULT_RULES
        rules = [cls() for cls in DEFAULT_RULES]
    analyzer = Analyzer(rules)
    findings: List[Finding] = []
    for full, rel in iter_python_files(paths, root):
        findings.extend(analyzer.analyze_file(full, rel))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


#: What ``python -m ci.analysis`` scans when no paths are given: first-party
#: runtime code. Tests exercise the rules through fixtures
#: (``tests/test_petalint.py``) and carry their own idioms (anonymous probe
#: threads, deliberate wedges), so they are opt-in via explicit paths.
DEFAULT_PATHS = ('petastorm_tpu', 'ci')

DEFAULT_BASELINE = os.path.join('ci', 'analysis', 'baseline.json')


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog='python -m ci.analysis',
        description='petalint: AST invariant checker (rule catalog in '
                    'docs/static_analysis.md)')
    parser.add_argument('paths', nargs='*', default=None,
                        help='files/directories to scan (default: {})'
                        .format(' '.join(DEFAULT_PATHS)))
    parser.add_argument('--root', default=os.getcwd(),
                        help='base directory for relative paths / rule '
                             'scoping (default: cwd)')
    parser.add_argument('--baseline', default=None,
                        help='baseline JSON (default: {} when present under '
                             '--root)'.format(DEFAULT_BASELINE))
    parser.add_argument('--write-baseline', action='store_true',
                        help='write the current findings as the new baseline '
                             'and exit 0 (review the diff!)')
    parser.add_argument('--list-rules', action='store_true')
    args = parser.parse_args(argv)

    from ci.analysis.rules import DEFAULT_RULES
    rules = [cls() for cls in DEFAULT_RULES]
    if args.list_rules:
        for rule in rules:
            print('{:20s} {}'.format(rule.name, rule.description))
        return 0

    root = os.path.abspath(args.root)
    paths = args.paths or [p for p in DEFAULT_PATHS
                           if os.path.exists(os.path.join(root, p))]
    findings = analyze_paths(paths, root, rules)

    baseline_path = args.baseline
    if baseline_path is None:
        candidate = os.path.join(root, DEFAULT_BASELINE)
        baseline_path = candidate if os.path.exists(candidate) else None
    if args.write_baseline:
        out = baseline_path or os.path.join(root, DEFAULT_BASELINE)
        Baseline.dump(findings, out)
        print('petalint: wrote {} finding(s) to {}'.format(len(findings),
                                                           out))
        return 0
    baseline = (Baseline.load(baseline_path) if baseline_path
                else Baseline.empty())
    new, baselined, stale = baseline.split(findings)

    for f in new:
        print(f.format())
    for f in baselined:
        print('{}  (baselined)'.format(f.format()))
    for entry in stale:
        print('{}:{}: [baseline] stale entry for rule {!r}: the referenced '
              'line no longer matches — delete it from {} (the baseline can '
              'only shrink)'.format(entry.get('path'), entry.get('line'),
                                    entry.get('rule'), baseline.path))
    failed = bool(new or stale)
    print('petalint: {} new, {} baselined, {} stale baseline entr{} -- {}'
          .format(len(new), len(baselined), len(stale),
                  'y' if len(stale) == 1 else 'ies',
                  'FAIL' if failed else 'OK'))
    if new:
        print("petalint: see docs/static_analysis.md ('petalint failed my "
              "PR') for the rule catalog and suppression syntax")
    return 1 if failed else 0
