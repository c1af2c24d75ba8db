"""atomic-publish: every published file lands via tmp + ``os.replace``.

The repo's crash-consistency story (DESIGN "Checkpointing", invariants
1-7) rests on ONE idiom: write the complete payload to a sibling tmp
name, then ``os.replace`` it onto the published path — so a reader (the
serving watcher, a resuming trainer, the report tool) only ever sees a
complete file or the previous one, never a torn write.  Three historical
bugs (``kill_publish``, ``kill_writeback``, store-ahead-of-chain) were
all orderings around this idiom; this checker pins it statically:

  * **direct-write** — ``open(p, "w"/"wb")`` straight onto a published
    name.  "Published" is judged three ways: the expression is a known
    published-artifact spelling (``out_path`` / ``args.out`` /
    ``model_file`` — the tools' JSON artifact writers and the
    checkpoint path); its constant fragments end in ``.npz``/``.json``;
    or the same module ``os.replace``s onto that exact attribute chain
    somewhere (``self._path``).  Exempt: append modes (JSONL logs are
    append-only, not published snapshots), paths whose spelling contains
    ``tmp``, and opens whose scope later replaces that path AWAY (it IS
    the tmp).
  * **rename-no-tmp** — ``os.replace(src, dst)`` where ``src`` is a
    local built in this scope but never written here (and never handed
    to a writer call): the rename publishes bytes nobody provably wrote.
    Move-asides (``dst`` spelled ``*.corrupt``/``*.bak``/``*.tmp``) are
    quarantines, not publishes, and stay quiet.
  * **write-after-rename** — a write-open of the SAME path expression
    after the ``os.replace`` that published it, in the same scope: the
    post-rename write tears the just-published file in place.
  * **unlink-order** — a full-save scope that both unlinks the delta
    chain (``os.remove`` over ``delta_paths(...)``) and publishes must
    unlink BEFORE the rename (crash between the two leaves old-base +
    old-chain, never new-base + stale-chain — DESIGN invariant 4).

Spelling-based (``ast.unparse``) matching is deliberate: it is stable,
explainable, and matches how the publish sites are actually written;
aliased paths land in the baseline or a reasoned suppression.
"""

from __future__ import annotations

import ast

from analysis.core import (
    Finding,
    RepoContext,
    attr_chain,
    call_name,
    enclosing_function,
    parent_map,
)

RULE = "atomic-publish"

# Exact path-expression spellings that are published artifacts wherever
# they appear (committed probe/bench JSONs, the checkpoint path).
PUBLISHED_EXPRS = {"out_path", "args.out", "model_file"}

# Constant suffixes that mark a published name when they terminate the
# path expression's literal text.
PUBLISHED_SUFFIXES = (".npz", ".json")

# A rename TO one of these is a quarantine/move-aside, not a publish.
QUARANTINE_FRAGMENTS = (".corrupt", ".bak", ".tmp", ".quarantine")

WRITE_MODES = ("w", "wb", "w+", "wb+", "xb", "x")


def _spell(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - unparse is total on parsed trees
        return "<expr>"


def _open_write(call: ast.Call):
    """(path_node, mode) when ``call`` is an ``open``/``io.open`` for
    writing; None otherwise (default mode is read)."""
    name = call_name(call)
    if name not in ("open", "io.open") or not call.args:
        return None
    mode = None
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        mode = call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    if not isinstance(mode, str):
        return None
    if "a" in mode or not any(mode.startswith(m) for m in WRITE_MODES):
        return None
    return call.args[0], mode


def _replace_call(call: ast.Call):
    """(src, dst) for os.replace/os.rename with two args."""
    name = call_name(call)
    if name in ("os.replace", "os.rename") and len(call.args) >= 2:
        return call.args[0], call.args[1]
    return None


def _const_text(node: ast.AST) -> str:
    """Concatenated literal fragments of a path expression — enough to
    judge tmp-ness and published suffixes on f-strings and ``+`` chains."""
    parts = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            parts.append(sub.value)
    return "".join(parts)


def _is_tmp_spelling(node: ast.AST) -> bool:
    return "tmp" in _spell(node).lower()


def _scopes(tree: ast.AST):
    """Every function body plus the module body as statement lists."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node, node.body
    yield tree, [
        s
        for s in tree.body
        if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))
    ]


def _walk_scope_stmts(body):
    """All statements in source order, NOT descending into nested defs
    (they are their own scopes)."""
    for stmt in body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        yield stmt
        for field in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field, None)
            if sub:
                yield from _walk_scope_stmts(sub)
        for h in getattr(stmt, "handlers", ()) or ():
            yield from _walk_scope_stmts(h.body)


def _stmt_expr_nodes(stmt):
    """AST nodes belonging to THIS statement only — its expression
    children, not its nested statement blocks (those are yielded as their
    own entries by ``_walk_scope_stmts``, and double-walking a ``with``
    would count its body's calls twice)."""
    for field, value in ast.iter_fields(stmt):
        if field in ("body", "orelse", "finalbody", "handlers"):
            continue
        nodes = value if isinstance(value, list) else [value]
        for n in nodes:
            if isinstance(n, ast.AST):
                yield from ast.walk(n)


class PublishChecker:
    name = "publish"
    rules = (RULE,)
    description = "published files land via the tmp + os.replace idiom"

    def run(self, ctx: RepoContext) -> list[Finding]:
        findings: list[Finding] = []
        for sf in ctx.files:
            tree = sf.tree
            if tree is None:
                continue
            parents = parent_map(tree)
            # module-wide: attribute chains that are ever a replace DST —
            # a direct write onto one of these anywhere in the module is
            # a bypass of the module's own publish discipline.
            module_attr_dsts = set()
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    rep = _replace_call(node)
                    if rep is not None and isinstance(rep[1], ast.Attribute):
                        chain = attr_chain(rep[1])
                        if chain:
                            module_attr_dsts.add(chain)
            for scope_node, body in _scopes(tree):
                findings.extend(
                    self._check_scope(
                        sf, scope_node, body, parents, module_attr_dsts
                    )
                )
        return findings

    def _check_scope(self, sf, scope_node, body, parents, module_attr_dsts):
        stmts = list(_walk_scope_stmts(body))
        opens = []  # (index, path_node, spell)
        replaces = []  # (index, src_node, dst_node, lineno)
        assigns = {}  # name -> index of first assignment
        arg_uses = {}  # name -> indices where passed to a non-replace call
        unlink_idx = []  # indices of chain-unlink statements
        for i, stmt in enumerate(stmts):
            for node in _stmt_expr_nodes(stmt):
                if not isinstance(node, ast.Call):
                    continue
                ow = _open_write(node)
                if ow is not None:
                    opens.append((i, ow[0], _spell(ow[0])))
                rep = _replace_call(node)
                if rep is not None:
                    replaces.append((i, rep[0], rep[1], node.lineno))
                else:
                    cname = call_name(node) or ""
                    if cname != "os.remove":
                        # any Name reaching a call (directly, in a list,
                        # in an f-string: subprocess argv, writer helpers)
                        # counts as handing the path to a producer
                        for arg in list(node.args) + [
                            kw.value for kw in node.keywords
                        ]:
                            for sub in ast.walk(arg):
                                if isinstance(sub, ast.Name):
                                    arg_uses.setdefault(sub.id, []).append(i)
                if (call_name(node) or "").endswith("delta_paths"):
                    unlink_idx.append(i)
            if isinstance(stmt, ast.Assign):
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        assigns.setdefault(tgt.id, i)

        findings = []
        anchor = enclosing_function(scope_node, parents) if stmts else ""
        replaced_away = {_spell(src) for _, src, _, _ in replaces}

        # -- direct-write ------------------------------------------------
        for i, path_node, spell in opens:
            if _is_tmp_spelling(path_node) or spell in replaced_away:
                continue
            text = _const_text(path_node)
            published = (
                spell in PUBLISHED_EXPRS
                or text.endswith(PUBLISHED_SUFFIXES)
                or (isinstance(path_node, ast.Attribute) and spell in module_attr_dsts)
            )
            if not published:
                continue
            line = getattr(path_node, "lineno", 0)
            findings.append(
                Finding(
                    rule=RULE,
                    path=sf.rel,
                    line=line,
                    message=(
                        f"direct write to published path {spell!r} — a crash "
                        "mid-write leaves a torn file where readers expect "
                        "complete-or-previous"
                    ),
                    context=f"{anchor}:direct:{spell}",
                    fix_hint=(
                        "write to a sibling tmp name and os.replace it onto "
                        f"{spell} (the checkpoint.py _save_npz idiom)"
                    ),
                )
            )

        # -- rename-no-tmp / write-after-rename --------------------------
        open_spells_at = [(i, spell) for i, _n, spell in opens]
        for ri, src, dst, line in replaces:
            dst_text = _const_text(dst) + _spell(dst)
            if any(frag in dst_text for frag in QUARANTINE_FRAGMENTS):
                continue  # move-aside, not a publish
            src_spell = _spell(src)
            written_before = any(
                i <= ri and spell == src_spell for i, spell in open_spells_at
            )
            if not written_before and isinstance(src, ast.Name):
                handed_off = any(
                    i <= ri for i in arg_uses.get(src.id, ())
                )
                if src.id in assigns and not handed_off:
                    findings.append(
                        Finding(
                            rule=RULE,
                            path=sf.rel,
                            line=line,
                            message=(
                                f"os.replace publishes {src_spell!r} but this "
                                "scope never writes it (no open/writer call) "
                                "— the rename ships bytes nobody provably "
                                "produced"
                            ),
                            context=f"{anchor}:no-tmp-write:{src_spell}",
                            fix_hint=(
                                "write the tmp in the same scope (or pass it "
                                "to the writer helper) before renaming"
                            ),
                        )
                    )
            dst_spell = _spell(dst)
            for oi, spell in open_spells_at:
                if oi > ri and spell == dst_spell:
                    findings.append(
                        Finding(
                            rule=RULE,
                            path=sf.rel,
                            line=stmts[oi].lineno,
                            message=(
                                f"write to {dst_spell!r} AFTER the os.replace "
                                f"that published it (line {line}) — tears the "
                                "published file in place"
                            ),
                            context=f"{anchor}:write-after-rename:{dst_spell}",
                            fix_hint=(
                                "fold the extra payload into the tmp before "
                                "the rename, or publish a second artifact"
                            ),
                        )
                    )

        # -- unlink-order ------------------------------------------------
        if unlink_idx and replaces:
            removes = [
                i
                for i, stmt in enumerate(stmts)
                for node in _stmt_expr_nodes(stmt)
                if isinstance(node, ast.Call)
                and (call_name(node) or "") in ("os.remove", "os.unlink")
            ]
            publish_ri = [
                ri
                for ri, _s, dst, _l in replaces
                if not any(
                    frag in (_const_text(dst) + _spell(dst))
                    for frag in QUARANTINE_FRAGMENTS
                )
            ]
            if removes and publish_ri and min(removes) > min(publish_ri):
                line = stmts[min(removes)].lineno
                findings.append(
                    Finding(
                        rule=RULE,
                        path=sf.rel,
                        line=line,
                        message=(
                            "delta-chain unlink AFTER the publish rename — a "
                            "crash between the two leaves the NEW base with "
                            "the OLD chain (stale rows replay on restore); "
                            "unlink first, then rename"
                        ),
                        context=f"{anchor}:unlink-after-publish",
                        fix_hint=(
                            "order: write tmp -> unlink old deltas -> "
                            "os.replace (checkpoint.py _save_npz)"
                        ),
                    )
                )
        return findings
