"""Shared infrastructure for the invariant checker suite.

Everything a checker needs that is not rule logic lives here: the
``Finding`` model (rule, severity, stable baseline key, fix hint), the
parsed-source cache (``RepoContext`` parses each file once; all five
checkers share the ASTs), per-line suppression comments, the committed
baseline (pre-existing findings are pinned with a written justification;
any NEW finding fails ``--strict``), and the human/JSON renderers.

Stdlib-only on purpose: the suite must run on a machine that cannot
import jax (CI collectors, a laptop triaging a diff).

Suppression syntax, on the flagged line or the line directly above::

    # analysis: ok <rule> <reason>

The reason is REQUIRED — a bare suppression is itself an error finding
(rule ``suppression``), so silencing a rule always leaves a written
trace next to the code it excuses.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
import sys

SEVERITIES = ("error", "warning")

# One place for every rule name so run.py, report.py and the tests agree.
RULES = (
    "donation-after-use",
    "recompile-hazard",
    "lock-discipline",
    "lock-order",
    "config-key",
    "telemetry",
    "format-drift",
    "atomic-publish",
    "exception-hygiene",
    "blocking-under-lock",
    "collective-divergence",
    "resource-lifecycle",
    "suppression",
    "parse",
)


@dataclasses.dataclass
class Finding:
    """One violation.  ``context`` is the stable anchor the baseline keys
    on (function/attr/key names — survives line-number drift, unlike
    ``line``, which is for humans and clickable editors).  ``ordinal``
    disambiguates same-context repeats (a SECOND uncached jit in the
    same function must read as NEW, not ride the first one's pin) —
    assigned by :func:`disambiguate` after a run."""

    rule: str
    path: str  # repo-relative, '/'-separated
    line: int
    message: str
    severity: str = "error"
    context: str = ""
    fix_hint: str = ""
    ordinal: int = 1

    @property
    def key(self) -> str:
        base = f"{self.rule}::{self.path}::{self.context or self.message}"
        return base if self.ordinal <= 1 else f"{base}#{self.ordinal}"

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["key"] = self.key
        return d

    def render(self) -> str:
        sev = "" if self.severity == "error" else f" [{self.severity}]"
        hint = f"\n      fix: {self.fix_hint}" if self.fix_hint else ""
        return f"{self.path}:{self.line}:{sev} {self.message}{hint}"


_SUPPRESS_RE = re.compile(
    r"#\s*analysis:\s*ok\s+([a-z][a-z0-9-]*)\b[ \t]*(.*)$"
)


# Process-wide parse cache keyed by (abspath, mtime_ns, size): the
# 11-rule suite builds several RepoContexts per process (the full run,
# a --changed-only pass, every test helper), and parsing + tokenizing
# ~100 files dominates its runtime.  Trees are read-only to every
# checker, so sharing them across contexts is safe; a touched file gets
# a new (mtime, size) key and re-parses.
_PARSE_CACHE: dict[tuple[str, int, int], dict] = {}


def _cache_key(abspath: str) -> tuple[str, int, int] | None:
    try:
        st = os.stat(abspath)
    except OSError:
        return None
    return (abspath, st.st_mtime_ns, st.st_size)


class SourceFile:
    """One parsed file: text, lines, AST (lazy), suppression map."""

    def __init__(self, abspath: str, rel: str):
        self.abspath = abspath
        self.rel = rel
        key = _cache_key(abspath)
        cached = _PARSE_CACHE.get(key) if key is not None else None
        if cached is not None:
            self.text = cached["text"]
            self.lines = cached["lines"]
            self._tree = cached["tree"]
            self._parse_error = cached["error"]
            self._parsed = cached["parsed"]
            self.suppressions = cached["suppressions"]
            return
        with open(abspath, encoding="utf-8") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self._tree: ast.AST | None = None
        self._parse_error: SyntaxError | None = None
        self._parsed = False
        # line -> list[(rule, reason)]; reason may be "" (an error).
        # Tokenized, not line-regexed: the marker inside a STRING literal
        # ("# analysis: ok recompile-hazard ...") must not mute anything.
        self.suppressions: dict[int, list[tuple[str, str]]] = {}
        import io
        import tokenize

        try:
            tokens = list(tokenize.generate_tokens(io.StringIO(self.text).readline))
        except (tokenize.TokenError, SyntaxError, IndentationError):
            tokens = []  # unparseable file: rule=parse reports it anyway
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if m:
                self.suppressions.setdefault(tok.start[0], []).append(
                    (m.group(1), m.group(2).strip())
                )
        if key is not None:
            self._cache_entry = key  # filled into _PARSE_CACHE post-parse

    @property
    def tree(self) -> ast.AST | None:
        if not self._parsed:
            self._parsed = True
            try:
                self._tree = ast.parse(self.text, filename=self.rel)
            except SyntaxError as e:
                self._parse_error = e
            key = getattr(self, "_cache_entry", None)
            if key is not None:
                _PARSE_CACHE[key] = {
                    "text": self.text,
                    "lines": self.lines,
                    "tree": self._tree,
                    "error": self._parse_error,
                    "parsed": True,
                    "suppressions": self.suppressions,
                }
        return self._tree

    @property
    def parse_error(self) -> SyntaxError | None:
        self.tree  # trigger the lazy parse
        return self._parse_error

    def suppressed(self, rule: str, line: int) -> bool:
        """A finding at ``line`` is suppressed by a REASONED ok-comment
        for its rule on the same line or the line directly above."""
        for ln in (line, line - 1):
            for r, reason in self.suppressions.get(ln, ()):
                if r == rule and reason:
                    return True
        return False


class RepoContext:
    """The shared input every checker runs against: the repo root and the
    parsed files.  Construction never raises on bad source — syntax
    errors surface as rule=``parse`` findings so one broken file cannot
    hide the rest of the report."""

    def __init__(self, root: str, rels: list[str]):
        self.root = os.path.abspath(root)
        self.files: list[SourceFile] = []
        self.parse_findings: list[Finding] = []
        for rel in sorted(rels):
            sf = SourceFile(os.path.join(self.root, rel), rel.replace(os.sep, "/"))
            self.files.append(sf)
            if sf.parse_error is not None:
                e = sf.parse_error
                self.parse_findings.append(
                    Finding(
                        rule="parse",
                        path=sf.rel,
                        line=e.lineno or 0,
                        message=f"syntax error: {e.msg}",
                        context=f"syntax:{e.lineno}",
                    )
                )

    def file(self, rel: str) -> SourceFile | None:
        rel = rel.replace(os.sep, "/")
        for sf in self.files:
            if sf.rel == rel:
                return sf
        return None

    def package_files(self, prefix: str = "fast_tffm_tpu/") -> list[SourceFile]:
        return [f for f in self.files if f.rel.startswith(prefix)]


DEFAULT_EXCLUDE_DIRS = {
    "__pycache__", ".git", "csrc", "docs", "configs", "tests"
}


def discover(root: str) -> list[str]:
    """Default target set: the package, tools (including this suite),
    and the top-level drivers.  tests/ is excluded on purpose — its
    fixtures (including test_analysis's own) violate rules by design.
    (PR 14 note: ``data`` used to be excluded here for the root-level
    dataset directory — but the walk never visits the root, and the
    entry silently pruned the ``fast_tffm_tpu/data`` PACKAGE out of the
    whole suite: the wire/binary/stream format modules were unanalyzed
    for a full PR cycle.  The format registries live exactly there, so
    the blind spot is gone.)"""
    rels: list[str] = []
    for base in ("fast_tffm_tpu", "tools"):
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, base)):
            dirnames[:] = [d for d in dirnames if d not in DEFAULT_EXCLUDE_DIRS]
            for fn in filenames:
                if fn.endswith(".py"):
                    rels.append(
                        os.path.relpath(os.path.join(dirpath, fn), root)
                    )
    for fn in ("chip_smoke.py", "fast_tffm.py"):
        if os.path.isfile(os.path.join(root, fn)):
            rels.append(fn)
    return rels


def disambiguate(findings: list[Finding]) -> list[Finding]:
    """Assign ordinals so same-base-key findings get distinct keys in
    source order ('...#2', '...#3').  Removing an occurrence shifts the
    survivors DOWN (never up), so a stale pin goes stale — it can never
    absorb a genuinely new occurrence."""
    counts: dict[str, int] = {}
    for f in sorted(findings, key=lambda f: (f.rule, f.path, f.line)):
        f.ordinal = 1  # key reads the base form during the count
        n = counts.get(f.key, 0) + 1
        counts[f.key] = n
        f.ordinal = n
    return findings


# -- suppression application ----------------------------------------------


def apply_suppressions(
    findings: list[Finding], ctx: RepoContext
) -> list[Finding]:
    """Drop findings covered by a reasoned ok-comment; add one
    rule=``suppression`` error per REASON-LESS ok-comment anywhere in the
    tree (a silent mute is worse than the finding it hides)."""
    out = []
    for f in findings:
        sf = ctx.file(f.path)
        if sf is not None and sf.suppressed(f.rule, f.line):
            continue
        out.append(f)
    for sf in ctx.files:
        for ln, entries in sorted(sf.suppressions.items()):
            for rule, reason in entries:
                if not reason:
                    out.append(
                        Finding(
                            rule="suppression",
                            path=sf.rel,
                            line=ln,
                            message=(
                                f"suppression for {rule!r} has no reason — "
                                "'# analysis: ok <rule> <reason>' requires one"
                            ),
                            context=f"bare:{rule}:{ln}",
                            fix_hint="append the reason the rule is okay to break here",
                        )
                    )
                elif rule not in RULES:
                    out.append(
                        Finding(
                            rule="suppression",
                            path=sf.rel,
                            line=ln,
                            message=f"suppression names unknown rule {rule!r}",
                            context=f"unknown:{rule}:{ln}",
                            fix_hint="rules: " + ", ".join(r for r in RULES),
                        )
                    )
    return out


# -- baseline --------------------------------------------------------------


def load_baseline(path: str) -> dict:
    """{"version": 1, "pinned": [{key, justification, ...}]} → key map."""
    if not os.path.isfile(path):
        return {}
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "pinned" not in data:
        raise ValueError(f"{path}: not a baseline file (no 'pinned' list)")
    out = {}
    for entry in data["pinned"]:
        out[entry["key"]] = entry
    return out


def write_baseline(
    path: str, findings: list[Finding], justifications=None, keep_entries=()
) -> None:
    """Pin the given findings.  ``justifications`` maps key (or rule, as
    a fallback) → text; unpinned-without-text entries get an empty
    justification, which --strict then refuses — writing a baseline is
    not the same as justifying it.  ``keep_entries`` carries existing
    pins to preserve verbatim (a partial --rules regeneration must not
    erase other checkers' debt)."""
    justifications = justifications or {}
    seen = set()
    pinned = []
    for entry in keep_entries:
        if entry["key"] not in seen:
            seen.add(entry["key"])
            pinned.append(entry)
    for f in sorted(findings, key=lambda f: (f.rule, f.path, f.line)):
        if f.key in seen:
            continue
        seen.add(f.key)
        pinned.append(
            {
                "key": f.key,
                "rule": f.rule,
                "path": f.path,
                "severity": f.severity,
                "message": f.message,
                "justification": justifications.get(
                    f.key, justifications.get(f.rule, "")
                ),
            }
        )
    pinned.sort(key=lambda e: e["key"])
    with open(path, "w") as f:
        json.dump({"version": 1, "pinned": pinned}, f, indent=1, sort_keys=True)
        f.write("\n")


def partition(findings: list[Finding], baseline: dict):
    """(new, pinned, stale_keys): findings not in the baseline, findings
    the baseline covers, and baseline keys with no live finding (paid-off
    debt — prune them)."""
    new, pinned = [], []
    live_keys = set()
    for f in findings:
        live_keys.add(f.key)
        (pinned if f.key in baseline else new).append(f)
    stale = sorted(set(baseline) - live_keys)
    return new, pinned, stale


def unjustified(baseline: dict) -> list[str]:
    return sorted(
        k for k, e in baseline.items() if not (e.get("justification") or "").strip()
    )


# -- AST helpers shared by the checkers ------------------------------------


def attr_chain(node: ast.AST) -> str | None:
    """'self._mark', 'jax.jit', 'slot.lock' — or None when the expression
    is not a plain dotted name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> str | None:
    return attr_chain(call.func)


def jax_aliases(tree: ast.AST) -> dict[str, str]:
    """Import-aware names: {'jit': 'jax.jit', 'partial':
    'functools.partial', ...} for this module."""
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def resolves_to(name: str, target: str, aliases: dict[str, str]) -> bool:
    """Does dotted ``name`` (as written) denote ``target`` (canonical,
    e.g. 'jax.jit') under this module's imports?"""
    if name == target:
        return True
    head, _, rest = name.partition(".")
    full = aliases.get(head)
    if full is None:
        return False
    return (full + ("." + rest if rest else "")) == target


def parent_map(tree: ast.AST) -> dict[ast.AST, ast.AST]:
    out = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            out[child] = node
    return out


def enclosing_function(node: ast.AST, parents: dict) -> str:
    """Dotted qualname-ish anchor: 'Router._on_down' / '<module>'."""
    names = []
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            names.append(cur.name)
        cur = parents.get(cur)
    return ".".join(reversed(names)) or "<module>"


# -- interprocedural call graph (PR 14) -------------------------------------
#
# One module, one graph: every def (module-level 'helper', methods as
# 'Class.method') plus the calls each makes, with call-site spellings
# resolved back to local defs where possible ('helper' → helper;
# 'self.m' → '<enclosing class>.m').  Deliberately ONE module deep and
# ONE hop at a time: the checkers that ride it (donation wrappers,
# factory-returned jit callables) follow a single call boundary, which
# is where the historical bugs lived — a whole-repo fixpoint would buy
# noise, not signal.


def function_defs(tree: ast.AST) -> dict[str, ast.FunctionDef]:
    """Qualname → def node: module-level defs under their bare name,
    methods as 'Class.method'.  Nested (closure) defs are skipped — they
    are not callable from outside their scope."""
    out: dict[str, ast.FunctionDef] = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out[node.name] = node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out[f"{node.name}.{sub.name}"] = sub
    return out


class CallGraph:
    """``defs``: qualname → def node.  ``calls``: caller qualname →
    [(callee spelling as written, Call node)].  ``resolve`` maps a
    spelling at a call site inside ``caller`` to a local def qualname
    (or None for externals)."""

    def __init__(self, defs, calls):
        self.defs = defs
        self.calls = calls

    def resolve(self, caller: str, spelling: str) -> str | None:
        if spelling in self.defs:
            return spelling
        head, _, rest = spelling.partition(".")
        if head == "self" and rest and "." in caller:
            qual = f"{caller.split('.')[0]}.{rest.split('.')[0]}"
            if qual in self.defs:
                return qual
        return None

    def callees(self, caller: str):
        """Resolved (qualname, Call) pairs for one caller."""
        for spelling, call in self.calls.get(caller, ()):
            qual = self.resolve(caller, spelling)
            if qual is not None:
                yield qual, call


def _walk_own_scope(fn: ast.AST):
    """Nodes of ``fn``'s body excluding nested def/class bodies (those
    are their own scopes; a closure's calls are not the enclosing def's)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def module_call_graph(tree: ast.AST) -> CallGraph:
    defs = function_defs(tree)
    calls: dict[str, list] = {q: [] for q in defs}
    for qual, fn in defs.items():
        for node in _walk_own_scope(fn):
            if isinstance(node, ast.Call):
                name = call_name(node)
                if name is not None:
                    calls[qual].append((name, node))
    return CallGraph(defs, calls)


# -- intraprocedural CFG + forward dataflow (PR 15) --------------------------
#
# The flow-sensitive core the concurrency checkers ride: basic blocks
# over if/for/while/try/with, one node per statement occurrence, plus a
# generic forward "facts held here" fixpoint.  Deliberately small:
# no expression-level flow, no interprocedural edges (module_call_graph
# above supplies the one-hop composition), exception edges approximated
# as "any statement inside a try can jump to its handlers".  That is
# exactly enough to answer the questions the checkers ask — which locks
# are held AT this statement, can this function leave without reaching
# a cleanup — without modelling Python it doesn't need.


class CFGNode:
    """One statement occurrence.  ``with_items`` is the lexical stack of
    ``with`` context expressions entered around this statement (innermost
    last) — with-scoped facts (lock held) are precise lexically, so they
    ride the node instead of the dataflow.  ``kind`` ∈ stmt | entry |
    exit."""

    __slots__ = ("stmt", "kind", "succ", "pred", "with_items", "index")

    def __init__(self, stmt=None, kind="stmt", with_items=()):
        self.stmt = stmt
        self.kind = kind
        self.succ: list[CFGNode] = []
        self.pred: list[CFGNode] = []
        self.with_items = tuple(with_items)
        self.index = -1

    def link(self, other: "CFGNode") -> None:
        if other not in self.succ:
            self.succ.append(other)
            other.pred.append(self)

    def own_exprs(self) -> tuple:
        """The AST subtrees that execute AT this node.  A compound
        statement's node is its HEADER (test/iter/subject/context
        expressions) — the body statements have their own nodes, so
        transfer functions and call scans must not walk the subtree
        twice."""
        s = self.stmt
        if s is None:
            return ()
        if isinstance(s, (ast.If, ast.While)):
            return (s.test,)
        if isinstance(s, (ast.For, ast.AsyncFor)):
            return (s.iter,)
        if isinstance(s, (ast.With, ast.AsyncWith)):
            return tuple(item.context_expr for item in s.items)
        if isinstance(s, ast.Match):
            return (s.subject,)
        if isinstance(
            s,
            (ast.Try, ast.ExceptHandler, ast.FunctionDef,
             ast.AsyncFunctionDef, ast.ClassDef),
        ):
            return ()
        return (s,)

    def __repr__(self):  # pragma: no cover - debugging aid
        what = self.kind if self.kind != "stmt" else type(self.stmt).__name__
        return f"<CFGNode {self.index} {what}>"


class CFG:
    """entry → statement nodes → exit.  ``nodes`` excludes entry/exit;
    ``by_stmt`` maps a statement AST node to its CFGNode (headers of
    compound statements get the node; their bodies get their own)."""

    def __init__(self):
        self.entry = CFGNode(kind="entry")
        self.exit = CFGNode(kind="exit")
        self.nodes: list[CFGNode] = []
        self.by_stmt: dict[ast.AST, CFGNode] = {}

    def _new(self, stmt, with_items) -> CFGNode:
        node = CFGNode(stmt, with_items=with_items)
        node.index = len(self.nodes)
        self.nodes.append(node)
        self.by_stmt[stmt] = node
        return node


class _CFGBuilder:
    """Recursive-descent CFG construction.  The frontier is the set of
    nodes whose control continues at the NEXT statement; terminators
    (return/raise/break/continue) empty it."""

    def __init__(self):
        self.cfg = CFG()
        self._breaks: list[list[CFGNode]] = []
        self._loop_heads: list[CFGNode] = []
        self._handlers: list[list[CFGNode]] = []  # enclosing try handler heads
        self._with: list[ast.expr] = []
        # Returns (and unhandled raises) inside a try-with-finally run the
        # finalbody on the way out: they park here and become extra preds
        # of the finally instead of edges straight to exit.
        self._final_pending: list[list[CFGNode]] = []

    def build(self, fn) -> CFG:
        frontier = self._seq(fn.body, [self.cfg.entry])
        for node in frontier:
            node.link(self.cfg.exit)
        return self.cfg

    def _seq(self, body, preds) -> list[CFGNode]:
        # An empty frontier (code after a terminator, a finally whose try
        # always exits) still gets nodes — predecessor-less, so dataflow
        # treats them as unreached — because by_stmt must cover every
        # statement the lexical checks ask about.
        frontier = list(preds)
        for stmt in body:
            frontier = self._stmt(stmt, frontier)
        return frontier

    def _node(self, stmt, preds) -> CFGNode:
        node = self.cfg._new(stmt, tuple(self._with))
        for p in preds:
            p.link(node)
        # Conservative exception edge: any statement inside a try may
        # transfer to its (innermost) handlers — or, in a finally-only
        # try, straight into the finalbody (the exception runs it on the
        # way out, so the finally must meet every body statement's OUT,
        # including pre-acquire ones).
        if self._handlers:
            for h in self._handlers[-1]:
                node.link(h)
        elif self._final_pending:
            self._final_pending[-1].append(node)
        return node

    def _stmt(self, stmt, preds) -> list[CFGNode]:
        if isinstance(stmt, ast.If):
            test = self._node(stmt, preds)
            then_f = self._seq(stmt.body, [test])
            else_f = self._seq(stmt.orelse, [test]) if stmt.orelse else [test]
            return then_f + else_f
        if isinstance(stmt, (ast.While, ast.For, ast.AsyncFor)):
            head = self._node(stmt, preds)
            self._breaks.append([])
            self._loop_heads.append(head)
            body_f = self._seq(stmt.body, [head])
            for node in body_f:
                node.link(head)  # back edge
            self._loop_heads.pop()
            breaks = self._breaks.pop()
            else_f = self._seq(stmt.orelse, [head]) if stmt.orelse else [head]
            return else_f + breaks
        if isinstance(stmt, ast.Try):
            handler_heads = [
                self.cfg._new(h, tuple(self._with)) for h in stmt.handlers
            ]
            if stmt.finalbody:
                self._final_pending.append([])
            # Only a try WITH handlers claims the exception edges — an
            # empty list on the stack would swallow raises in a
            # finally-only try instead of routing them to the finalbody.
            if handler_heads:
                self._handlers.append(handler_heads)
            body_f = self._seq(stmt.body, preds)
            if handler_heads:
                self._handlers.pop()
            for p in preds:  # an exception can fire before any body stmt ran
                for h in handler_heads:
                    p.link(h)
            out = []
            for head, h in zip(handler_heads, stmt.handlers):
                out += self._seq(h.body, [head])
            out += self._seq(stmt.orelse, body_f) if stmt.orelse else body_f
            if stmt.finalbody:
                pending = self._final_pending.pop()
                # Return/raise paths meet the normal fall-through at the
                # finally's entry (conservative: after the finally they
                # continue with the frontier rather than forking back to
                # exit — extra predecessors only shrink must-facts).
                out = self._seq(stmt.finalbody, out + pending)
            return out
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            head = self._node(stmt, preds)
            self._with.extend(item.context_expr for item in stmt.items)
            body_f = self._seq(stmt.body, [head])
            del self._with[len(self._with) - len(stmt.items):]
            return body_f
        if isinstance(stmt, ast.Match):
            subject = self._node(stmt, preds)
            out = [subject]  # no case may match
            for case in stmt.cases:
                out += self._seq(case.body, [subject])
            return out
        # simple statements (incl. nested def/class, one opaque node each)
        node = self._node(stmt, preds)
        if isinstance(stmt, ast.Return):
            if self._final_pending:
                self._final_pending[-1].append(node)
            else:
                node.link(self.cfg.exit)
            return []
        if isinstance(stmt, ast.Raise):
            if self._handlers:
                for h in self._handlers[-1]:
                    node.link(h)
            elif self._final_pending:
                self._final_pending[-1].append(node)
            else:
                node.link(self.cfg.exit)
            return []
        if isinstance(stmt, ast.Break):
            if self._breaks:
                self._breaks[-1].append(node)
            return []
        if isinstance(stmt, ast.Continue):
            if self._loop_heads:
                node.link(self._loop_heads[-1])
            return []
        return [node]


def build_cfg(fn) -> CFG:
    """CFG for one FunctionDef/AsyncFunctionDef (nested defs are opaque
    single nodes — they execute later, on their own CFG)."""
    return _CFGBuilder().build(fn)


def forward_must(cfg: CFG, gen_kill) -> dict[CFGNode, frozenset]:
    """Forward MUST dataflow to fixpoint: fact sets meet by intersection
    at joins (a fact holds at a statement only if it holds on EVERY path
    reaching it — the right polarity for "lock held here", where a maybe
    is not an is).  ``gen_kill(node) -> (gen, kill)``.  Returns the IN
    fact per node (facts established BEFORE the statement runs); TOP
    (unvisited) is represented internally as None.  Convergence is
    guaranteed: facts only leave a set at a kill, and intersection is
    monotone on the finite fact universe."""
    IN: dict[CFGNode, frozenset | None] = {cfg.entry: frozenset()}
    OUT: dict[CFGNode, frozenset | None] = {cfg.entry: frozenset()}
    work = list(cfg.entry.succ)
    while work:
        node = work.pop()
        acc = None
        for p in node.pred:
            po = OUT.get(p)
            if po is None:
                continue  # TOP: identity for intersection
            acc = po if acc is None else (acc & po)
        if acc is None:
            continue  # no computed predecessor yet
        gen, kill = gen_kill(node)
        out = (acc - frozenset(kill)) | frozenset(gen)
        if IN.get(node) != acc or OUT.get(node) != out:
            IN[node] = acc
            OUT[node] = out
            work.extend(node.succ)
    return {n: (IN.get(n) if IN.get(n) is not None else frozenset())
            for n in cfg.nodes}


def reaches_without(cfg: CFG, start: CFGNode, stop_pred) -> bool:
    """May-escape query: is ``cfg.exit`` reachable from ``start`` without
    passing through a node satisfying ``stop_pred``?  The lifecycle
    checker's core question — can control leave the function while the
    resource acquired at ``start`` has seen no cleanup."""
    seen = set()
    work = list(start.succ)
    while work:
        node = work.pop()
        if node is cfg.exit:
            return True
        if id(node) in seen:
            continue
        seen.add(id(node))
        if node.kind == "stmt" and stop_pred(node):
            continue
        work.extend(node.succ)
    return False


# -- output ----------------------------------------------------------------


def render_text(
    findings: list[Finding], new: list[Finding], stale: list[str],
    baseline: dict, strict: bool,
) -> str:
    L = []
    by_rule: dict[str, list[Finding]] = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    for rule in sorted(by_rule):
        fs = by_rule[rule]
        L.append(f"[{rule}] {len(fs)} finding(s):")
        for f in sorted(fs, key=lambda f: (f.path, f.line)):
            mark = "NEW " if f in new else ""
            L.append(f"  {mark}{f.render()}")
    errs = sum(1 for f in findings if f.severity == "error")
    L.append(
        f"analysis: {len(findings)} finding(s) ({errs} error(s)), "
        f"{len(new)} new vs baseline, {len(baseline)} pinned, {len(stale)} stale"
    )
    if stale:
        L.append(
            "stale baseline entries (debt paid off — prune them from the "
            "baseline file):"
        )
        L += [f"  {k}" for k in stale]
    bad = unjustified(baseline)
    if bad and strict:
        L.append("baseline entries missing a justification:")
        L += [f"  {k}" for k in bad]
    return "\n".join(L)


def to_json(findings, new, stale, baseline, root) -> dict:
    by_rule: dict[str, int] = {}
    by_sev: dict[str, int] = {}
    debt_by_rule: dict[str, int] = {}
    new_keys = {f.key for f in new}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        by_sev[f.severity] = by_sev.get(f.severity, 0) + 1
        if f.key not in new_keys:
            debt_by_rule[f.rule] = debt_by_rule.get(f.rule, 0) + 1
    return {
        "version": 1,
        "root": root,
        "counts": {"by_rule": by_rule, "by_severity": by_sev},
        "baseline": {
            "pinned": len(baseline),
            "stale": len(stale),
            "unjustified": len(unjustified(baseline)),
            "debt": len(findings) - len(new),
            "debt_by_rule": debt_by_rule,
        },
        # The lockfile gate's input: ANY live format-drift finding —
        # pinned or not — is persisted-format drift (pinning drift in the
        # baseline must not hide it from the report gate).
        "lock_drift": by_rule.get("format-drift", 0),
        "new": [f.to_dict() for f in new],
        "findings": [f.to_dict() for f in findings],
    }


def _tools_on_path() -> None:
    tools = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if tools not in sys.path:
        sys.path.insert(0, tools)


_tools_on_path()
