"""The project rules: CLAUDE.md's design contracts as AST checks.

Each rule encodes ONE prose invariant (catalog with rationale and
suppression policy: docs/static-analysis.md). Rules are intentionally
narrow — they match the specific idioms this codebase uses (``cfg.<flag>``
reads, ``self.<dict>["key"]`` stores, ``with …stats_lock`` blocks,
``_faults.site("…")`` registrations) rather than trying to be a general
linter; a pattern the rule can't see is a pattern the codebase shouldn't
use for that invariant in the first place.

False-positive policy: a deliberate exception gets an inline
``# kakveda: allow[rule-id]`` pragma WITH a comment explaining why —
never widen a rule's blind spot to hide one site.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from kakveda_tpu.analysis import discovery as _discovery
from kakveda_tpu.analysis import knobs as _knobs
from kakveda_tpu.analysis.framework import (
    FileContext,
    Finding,
    Rule,
    TreeContext,
    register,
)

# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def _parent_map(node: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for parent in ast.walk(node):
        for child in ast.iter_child_nodes(parent):
            parents[child] = parent
    return parents


def _enclosing_function(
    node: ast.AST, parents: Dict[ast.AST, ast.AST]
) -> Optional[ast.AST]:
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur
        cur = parents.get(cur)
    return None


def _self_attr(node: ast.AST) -> Optional[str]:
    """``self.X`` -> ``X`` (else None)."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _call_chain_base_attr(call: ast.Call) -> Optional[str]:
    """For ``self.G.labels(...).set(...)`` / ``self.G.set(...)`` /
    ``self.C.labels(...).inc()`` return ``G``/``C`` — the self attribute at
    the base of a method-call chain (else None)."""
    cur: ast.AST = call.func
    while True:
        if isinstance(cur, ast.Attribute):
            cur = cur.value
        elif isinstance(cur, ast.Call):
            cur = cur.func
        else:
            return None  # chain bottoms out at a bare name/subscript
        attr = _self_attr(cur)
        if attr is not None:
            return attr


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


# ---------------------------------------------------------------------------
# single-writer
# ---------------------------------------------------------------------------

_SINGLE_WRITER = {
    "kakveda_tpu/models/serving.py": ("_set_gate_state",),
    "kakveda_tpu/core/admission.py": ("_set_brownout_state", "_set_tenant_state"),
    "kakveda_tpu/fleet/autoscaler.py": ("_set_scale_state",),
}
_ANY_KEY = object()


@register
class SingleWriterTransitions(Rule):
    id = "single-writer"
    invariant = (
        "the fields moved by _set_gate_state/_set_brownout_state/"
        "_set_scale_state (state key, gauge vector, transition counter) "
        "are assigned nowhere else in their class except __init__"
    )
    scope = tuple(_SINGLE_WRITER)

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        out: List[Finding] = []
        for cls in ast.walk(fc.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            methods = {
                m.name: m
                for m in cls.body
                if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
            }
            for helper_name in _SINGLE_WRITER[fc.rel]:
                helper = methods.get(helper_name)
                if helper is None:
                    continue
                attrs, subs, metrics = self._protected(helper)
                for name, m in methods.items():
                    if name in (helper_name, "__init__"):
                        continue
                    out.extend(
                        self._violations(fc, m, helper_name, attrs, subs, metrics)
                    )
        return out

    @staticmethod
    def _protected(helper) -> Tuple[Set[str], Dict[str, set], Set[str]]:
        """Derive the protected write-set from the helper's own body."""
        attrs: Set[str] = set()          # self.X = …
        subs: Dict[str, set] = {}        # self.X[key] = … (key set or ANY)
        metrics: Set[str] = set()        # self.G.labels(...).set()/.inc()
        for n in ast.walk(helper):
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in targets:
                    a = _self_attr(t)
                    if a is not None:
                        attrs.add(a)
                    elif isinstance(t, ast.Subscript):
                        base = _self_attr(t.value)
                        if base is not None:
                            key = _const_str(t.slice)
                            subs.setdefault(base, set()).add(
                                key if key is not None else _ANY_KEY
                            )
            elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                if n.func.attr in ("set", "inc", "dec"):
                    base = _call_chain_base_attr(n)
                    if base is not None:
                        metrics.add(base)
        return attrs, subs, metrics

    def _violations(
        self, fc, method, helper_name, attrs, subs, metrics
    ) -> List[Finding]:
        out: List[Finding] = []
        # Local aliases of protected dict attrs (x = self.spec_stats).
        aliases: Dict[str, str] = {}
        for n in ast.walk(method):
            if isinstance(n, ast.Assign) and len(n.targets) == 1:
                t = n.targets[0]
                base = _self_attr(n.value)
                if (
                    isinstance(t, ast.Name)
                    and base is not None
                    and (base in subs or base in attrs)
                ):
                    aliases[t.id] = base

        def sub_base(node: ast.Subscript) -> Optional[str]:
            b = _self_attr(node.value)
            if b is not None:
                return b
            if isinstance(node.value, ast.Name):
                return aliases.get(node.value.id)
            return None

        for n in ast.walk(method):
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in targets:
                    a = _self_attr(t)
                    if a is not None and (a in attrs or a in subs):
                        out.append(Finding(
                            self.id, fc.rel, t.lineno,
                            f"`self.{a}` is moved by {helper_name}() only; "
                            f"direct assignment in {method.name}() bypasses "
                            "the single-writer transition helper",
                        ))
                    elif isinstance(t, ast.Subscript):
                        base = sub_base(t)
                        if base in subs:
                            key = _const_str(t.slice)
                            protected = subs[base]
                            if _ANY_KEY in protected or key in protected:
                                out.append(Finding(
                                    self.id, fc.rel, t.lineno,
                                    f"`self.{base}[{key!r}]` is moved by "
                                    f"{helper_name}() only; direct store in "
                                    f"{method.name}() bypasses the "
                                    "single-writer transition helper",
                                ))
            elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                if n.func.attr in ("set", "inc", "dec"):
                    base = _call_chain_base_attr(n)
                    if base in metrics:
                        out.append(Finding(
                            self.id, fc.rel, n.lineno,
                            f"metric `self.{base}` is moved by "
                            f"{helper_name}() only; direct "
                            f".{n.func.attr}() in {method.name}() bypasses "
                            "the single-writer transition helper",
                        ))
        return out


# ---------------------------------------------------------------------------
# stats-lock
# ---------------------------------------------------------------------------

_STATS_ATTRS = frozenset({"spec_stats", "prefix_stats", "_stats"})
_READ_GUARDED = ("spec_stats", "prefix_stats")
_MUTATORS = frozenset({
    "update", "setdefault", "pop", "popitem", "clear",
    "append", "extend", "insert", "remove",
})
_SERVING_REL = "kakveda_tpu/models/serving.py"


def _attr_anywhere(node: ast.AST, attr: str) -> bool:
    return any(
        isinstance(n, ast.Attribute) and n.attr == attr for n in ast.walk(node)
    )


@register
class StatsLockDiscipline(Rule):
    id = "stats-lock"
    invariant = (
        "mutations of the batcher/engine stats dicts (spec_stats, "
        "prefix_stats, _stats) happen lexically inside `with …stats_lock`; "
        "outside models/serving.py the spec/prefix stats are read only "
        "through stats()/stats_snapshot()"
    )
    scope = None  # needs the whole tree for the external-read half

    def check_tree(self, ctx: TreeContext) -> List[Finding]:
        out: List[Finding] = []
        for fc in ctx.files:
            if fc.tree is None:
                continue
            if fc.rel == _SERVING_REL or fc.rel.startswith("tests/"):
                continue
            if fc.rel.startswith("kakveda_tpu/analysis/"):
                continue  # the linter names the dicts without touching them
            for n in ast.walk(fc.tree):
                if isinstance(n, ast.Attribute) and n.attr in _READ_GUARDED:
                    out.append(Finding(
                        self.id, fc.rel, n.lineno,
                        f"direct `{n.attr}` access outside the serving "
                        "module — the loop thread mutates the live dicts; "
                        "read through ServingEngine.stats() / "
                        "ContinuousBatcher.stats_snapshot()",
                    ))
        fc = ctx.by_rel.get(_SERVING_REL)
        if fc is not None and fc.tree is not None:
            out.extend(self._check_serving(fc))
        return out

    def _check_serving(self, fc: FileContext) -> List[Finding]:
        out: List[Finding] = []
        parents = _parent_map(fc.tree)

        def in_locked_with(node: ast.AST) -> bool:
            cur = parents.get(node)
            while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                if isinstance(cur, ast.With):
                    for item in cur.items:
                        if _attr_anywhere(item.context_expr, "stats_lock"):
                            return True
                cur = parents.get(cur)
            return False

        for func in ast.walk(fc.tree):
            if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if func.name == "__init__":
                continue  # construction publishes the dicts before any reader
            # Aliases: s = self.spec_stats; kt = s["k_trace"] — anything
            # reached from a stats dict counts as the stats dict.
            aliases: Set[str] = set()
            changed = True
            while changed:
                changed = False
                for n in ast.walk(func):
                    if isinstance(n, ast.Assign) and len(n.targets) == 1:
                        t = n.targets[0]
                        if isinstance(t, ast.Name) and t.id not in aliases:
                            if self._is_stats_expr(n.value, aliases):
                                aliases.add(t.id)
                                changed = True

            for n in ast.walk(func):
                target = None
                if isinstance(n, (ast.Assign, ast.AugAssign)):
                    targets = n.targets if isinstance(n, ast.Assign) else [n.target]
                    for t in targets:
                        if isinstance(t, ast.Subscript) and self._is_stats_expr(
                            t.value, aliases
                        ):
                            target = t
                elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                    if n.func.attr in _MUTATORS and self._is_stats_expr(
                        n.func.value, aliases
                    ):
                        target = n
                elif isinstance(n, ast.Delete):
                    for t in n.targets:
                        if isinstance(t, ast.Subscript) and self._is_stats_expr(
                            t.value, aliases
                        ):
                            target = t
                if target is not None and not in_locked_with(target):
                    out.append(Finding(
                        self.id, fc.rel, target.lineno,
                        f"stats mutation in {func.name}() outside a "
                        "`with …stats_lock` block — the loop thread and "
                        "readers race on these dicts",
                    ))
        return out

    @staticmethod
    def _is_stats_expr(node: ast.AST, aliases: Set[str]) -> bool:
        if isinstance(node, ast.Attribute) and node.attr in _STATS_ATTRS:
            return True
        if isinstance(node, ast.Name) and node.id in aliases:
            return True
        if isinstance(node, ast.Subscript):
            return StatsLockDiscipline._is_stats_expr(node.value, aliases)
        return False


# ---------------------------------------------------------------------------
# host-sync — RELOCATED to kakveda_tpu/analysis/device.py: the jit-body
# checks now share the device family's JitIndex discovery (same rule id,
# same messages). The device-plane rules (retrace-hazard,
# donation-after-use, constant-capture, dynamic-slice-by-trace) live there.
# ---------------------------------------------------------------------------

_NP_NAMES = frozenset({"np", "onp", "numpy"})


# ---------------------------------------------------------------------------
# typed-errors
# ---------------------------------------------------------------------------

_TYPED_ERRORS = frozenset({
    "OverloadError", "DeviceUnavailableError", "EngineDeadError",
    "EngineRetryableError", "DeadlineExceededError",
})
_BROAD = frozenset({"Exception", "BaseException"})
# Calls whose raise surface includes the typed errors above.
_TYPED_SOURCES = frozenset({
    "submit", "generate_ids", "register_prefix", "try_admit", "admit",
    "shed", "slot", "check",
})
_PROPAGATORS = frozenset({"set_exception", "_fail", "fail", "note_failure"})


@register
class TypedErrorDiscipline(Rule):
    id = "typed-errors"
    invariant = (
        "no broad `except Exception` that swallows "
        "OverloadError/DeviceUnavailableError/EngineDeadError around "
        "admission/engine calls on service paths — shed work must surface "
        "as 429, never take the solo-decode fallback"
    )
    scope = (
        "kakveda_tpu/service/",
        "kakveda_tpu/cli/",
        "kakveda_tpu/core/admission.py",
        "kakveda_tpu/models/serving.py",
        "kakveda_tpu/models/generate.py",
        "kakveda_tpu/models/runtime.py",
    )

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        out: List[Finding] = []
        for n in ast.walk(fc.tree):
            if not isinstance(n, ast.Try):
                continue
            typed_handled = False
            for h in n.handlers:
                names = self._handler_names(h)
                if names & _TYPED_ERRORS:
                    typed_handled = True
                    continue
                broad = h.type is None or (names & _BROAD)
                if not broad or typed_handled:
                    continue
                if not self._body_calls_typed_source(n.body):
                    continue
                if self._handler_propagates(h):
                    continue
                out.append(Finding(
                    self.id, fc.rel, h.lineno,
                    "broad except around a typed-error source "
                    "(admission/engine call in this try) swallows "
                    "OverloadError/DeviceUnavailableError/EngineDeadError; "
                    "catch the typed errors first, re-raise, or propagate "
                    "the original exception",
                ))
        return out

    @staticmethod
    def _handler_names(h: ast.ExceptHandler) -> Set[str]:
        names: Set[str] = set()
        if h.type is None:
            return names
        nodes = h.type.elts if isinstance(h.type, ast.Tuple) else [h.type]
        for t in nodes:
            if isinstance(t, ast.Name):
                names.add(t.id)
            elif isinstance(t, ast.Attribute):
                names.add(t.attr)
        return names

    @staticmethod
    def _body_calls_typed_source(body) -> bool:
        for stmt in body:
            for n in ast.walk(stmt):
                if isinstance(n, ast.Call):
                    f = n.func
                    name = (
                        f.attr if isinstance(f, ast.Attribute)
                        else f.id if isinstance(f, ast.Name) else None
                    )
                    if name in _TYPED_SOURCES:
                        return True
        return False

    @staticmethod
    def _handler_propagates(h: ast.ExceptHandler) -> bool:
        for n in ast.walk(h):
            if isinstance(n, ast.Raise):
                if n.exc is None:
                    return True  # bare re-raise keeps the type
                if isinstance(n.exc, ast.Call):
                    f = n.exc.func
                    name = f.id if isinstance(f, ast.Name) else (
                        f.attr if isinstance(f, ast.Attribute) else None
                    )
                    if name in _TYPED_ERRORS:
                        return True
            elif isinstance(n, ast.Call) and h.name is not None:
                f = n.func
                name = (
                    f.attr if isinstance(f, ast.Attribute)
                    else f.id if isinstance(f, ast.Name) else None
                )
                if name in _PROPAGATORS and any(
                    isinstance(a, ast.Name) and a.id == h.name for a in n.args
                ):
                    return True
        return False


# ---------------------------------------------------------------------------
# fault-site-once
# ---------------------------------------------------------------------------


@register
class FaultSiteOnce(Rule):
    id = "fault-site-once"
    invariant = (
        "faults.site(\"…\") resolves ONCE at construction (module import "
        "or __init__) — the hot path calls .fire() on the kept reference, "
        "never re-resolves"
    )
    scope = ("kakveda_tpu/", "bench.py", "scripts/", "__graft_entry__.py")

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        if fc.rel == "kakveda_tpu/core/faults.py":
            return []  # the registry itself
        out: List[Finding] = []
        parents = None
        for n in ast.walk(fc.tree):
            if not (
                isinstance(n, ast.Call)
                and isinstance(n.func, (ast.Name, ast.Attribute))
                and (
                    n.func.id == "site"
                    if isinstance(n.func, ast.Name)
                    else n.func.attr == "site"
                )
                and n.args
            ):
                continue
            name = _const_str(n.args[0])
            if name is None or "." not in name:
                continue
            if parents is None:
                parents = _parent_map(fc.tree)
            func = _enclosing_function(n, parents)
            if func is None or func.name == "__init__":
                continue  # construction / import time: the contract
            out.append(Finding(
                self.id, fc.rel, n.lineno,
                f"fault site {name!r} resolved inside {func.name}() — "
                "resolve once at construction and keep the reference "
                "(unarmed fire() is a bare attribute check; site() takes "
                "a lock)",
            ))
        return out


# ---------------------------------------------------------------------------
# fault-site-catalog + knob-docs (check_knobs, as rules)
# ---------------------------------------------------------------------------


def _evidence(ctx: TreeContext, files: List[str], needle: str) -> Tuple[str, int]:
    """(file, line) of the first reference to ``needle`` among ``files``."""
    for rel in files:
        fc = ctx.by_rel.get(str(rel).replace("\\", "/"))
        if fc is not None:
            return fc.rel, fc.find_line(needle)
    return files[0] if files else "?", 1


@register
class FaultSiteCatalog(Rule):
    id = "fault-site-catalog"
    invariant = (
        "every fault site registered in code appears in the "
        "docs/robustness.md catalog — the only surface operators can "
        "discover KAKVEDA_FAULTS arms from"
    )
    scope = None

    def check_tree(self, ctx: TreeContext) -> List[Finding]:
        out: List[Finding] = []
        for site, files in _knobs.undocumented_fault_sites(ctx.root).items():
            rel, line = _evidence(ctx, files, site)
            out.append(Finding(
                self.id, rel, line,
                f"fault site {site!r} is registered here but missing from "
                "the docs/robustness.md catalog",
            ))
        return out


@register
class KnobDocsParity(Rule):
    id = "knob-docs"
    invariant = (
        "every KAKVEDA_* knob the code reads is documented, and every "
        "documented knob is still read (no dead-knob drift)"
    )
    scope = None

    def check_tree(self, ctx: TreeContext) -> List[Finding]:
        out: List[Finding] = []
        for knob, files in _knobs.undocumented_knobs(ctx.root).items():
            rel, line = _evidence(ctx, files, knob)
            out.append(Finding(
                self.id, rel, line,
                f"env knob {knob} is read here but documented nowhere "
                "(CLAUDE.md / docs/) — an undocumented knob is an outage "
                "waiting for an operator",
            ))
        for knob in _knobs.dead_knobs(ctx.root):
            rel, line = "docs", 1
            for md in _discovery.md_files(ctx.root):
                try:
                    text = md.read_text(errors="replace")
                except OSError:
                    continue
                if knob in text:
                    rel = md.relative_to(ctx.root).as_posix()
                    line = next(
                        (i for i, ln in enumerate(text.splitlines(), 1) if knob in ln),
                        1,
                    )
                    break
            out.append(Finding(
                self.id, rel, line,
                f"env knob {knob} is documented but no code reads it — "
                "dead-knob drift sends operators tuning a no-op",
            ))
        return out


# ---------------------------------------------------------------------------
# atomic-log-rewrite
# ---------------------------------------------------------------------------

# The replayed stores: every byte of these files is state (restart = replay),
# so an in-place "w"-mode rewrite that crashes mid-write IS data loss. The
# only legal rewrite is write-tmp -> fsync -> os.replace (the compaction
# idiom); expressions routed through .with_suffix() derive such a tmp/bak
# sibling and pass.
_REPLAYED_LOG_ATTRS = frozenset({
    "failures_path", "patterns_path", "applied_path", "tombstones_path",
})
_REPLAYED_LOG_NAMES = (
    "failures.jsonl", "patterns.jsonl", "applied_events.jsonl",
    "tombstones.jsonl",
)
_TRUNCATING_WRITERS = frozenset({"write_text", "write_bytes"})


def _replayed_log_ref(node: ast.AST) -> Optional[str]:
    """The replayed log this path expression refers to (else None).
    ``.with_suffix``-derived expressions name a tmp/bak sibling, not the
    log itself — they return None by design."""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr == "with_suffix":
            return None
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in _REPLAYED_LOG_ATTRS:
            return n.attr
        s = _const_str(n)
        if s is not None:
            for name in _REPLAYED_LOG_NAMES:
                if s == name or s.endswith("/" + name):
                    return name
    return None


def _truncating_write_target(call: ast.Call) -> Optional[ast.AST]:
    """The path expression a call truncates, if it is a truncating write:
    ``X.write_text(...)`` / ``X.write_bytes(...)`` / ``X.open("w"…)`` /
    ``open(X, "w"…)`` — else None. Append ("a") and read modes pass."""
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr in _TRUNCATING_WRITERS:
        return f.value
    if isinstance(f, ast.Attribute) and f.attr == "open":
        mode = _const_str(call.args[0]) if call.args else None
        if mode is None:
            for kw in call.keywords:
                if kw.arg == "mode":
                    mode = _const_str(kw.value)
        if mode is not None and mode.startswith("w"):
            return f.value
    if isinstance(f, ast.Name) and f.id == "open" and len(call.args) >= 2:
        mode = _const_str(call.args[1])
        if mode is not None and mode.startswith("w"):
            return call.args[0]
    return None


@register
class AtomicLogRewrite(Rule):
    id = "atomic-log-rewrite"
    invariant = (
        "replayed logs (failures/patterns/applied_events/tombstones "
        ".jsonl) are never opened 'w' in place — rewrites go write-tmp + "
        "fsync + os.replace (crash at any byte leaves old or new log "
        "fully live); torn-FINAL-line truncation is the only in-place "
        "surgery and it goes through _truncate_pending"
    )
    scope = ("kakveda_tpu/", "bench.py", "scripts/", "__graft_entry__.py")

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        out: List[Finding] = []
        # Local helpers that "w"-rewrite one of their own parameters: a
        # call passing a replayed-log path into one is the same hazard one
        # hop away (the routes_admin _purge_jsonl shape).
        rewriting_helpers: Dict[str, Set[int]] = {}
        for n in ast.walk(fc.tree):
            if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            params = {a.arg for a in n.args.args if a.arg != "self"}
            if not params:
                continue
            hit = {
                i for i, a in enumerate(n.args.args)
                for c in ast.walk(n)
                if isinstance(c, ast.Call)
                and (t := _truncating_write_target(c)) is not None
                and isinstance(t, ast.Name) and t.id == a.arg
            }
            if hit:
                rewriting_helpers[n.name] = hit
        for n in ast.walk(fc.tree):
            if not isinstance(n, ast.Call):
                continue
            target = _truncating_write_target(n)
            if target is not None:
                ref = _replayed_log_ref(target)
                if ref is not None:
                    out.append(Finding(
                        self.id, fc.rel, n.lineno,
                        f"in-place 'w'-mode rewrite of replayed log "
                        f"{ref!r} — a crash mid-write loses committed "
                        "state; write a .tmp sibling, fsync, then "
                        "os.replace (or append)",
                    ))
                continue
            if isinstance(n.func, ast.Name) and n.func.id in rewriting_helpers:
                for i, arg in enumerate(n.args):
                    if i not in rewriting_helpers[n.func.id]:
                        continue
                    ref = _replayed_log_ref(arg)
                    if ref is not None:
                        out.append(Finding(
                            self.id, fc.rel, n.lineno,
                            f"replayed log {ref!r} passed into "
                            f"{n.func.id}(), which rewrites its argument "
                            "in place with mode 'w' — a crash mid-write "
                            "loses committed state; rewrite via .tmp + "
                            "os.replace",
                        ))
        return out
