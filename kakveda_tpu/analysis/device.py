"""Device-plane hygiene rules: retrace, donation, capture and slice checks.

Every dispatch has a fixed host cost, a device→host sync stalls the
pipeline behind it, and one silent retrace costs far more than the kernel
it wraps — so the device-plane
discipline CLAUDE.md states as prose (pow2 bucketing before every jit
dispatch, donation-safe buffer handoff, no host constants closed over by
traced bodies, static shapes in jit/scan bodies) is machine-enforced
here, on the PR-6 AST framework. Four new rules plus the relocated
``host-sync`` rule share ONE jit-discovery index per file
(:class:`JitIndex`): decorated ``@jax.jit`` functions, ``jax.jit(fn)``
wrappers (including ``self._impl`` methods and inline lambdas) and
``lax.scan`` bodies, with their ``donate_argnums`` / ``static_argnums`` /
``static_argnames`` metadata.

The runtime half is :mod:`kakveda_tpu.core.ledger` (``KAKVEDA_LEDGER=1``):
the compile-and-transfer ledger counts what these rules predict — a tree
that lints clean must show O(log N) distinct lowerings per entry point
and zero post-warmup compiles on the serve path, and the bench rows
assert it. Static and runtime halves cross-check exactly like the
concurrency sanitizer pair (analysis/concurrency.py + core/sanitize.py).

False-positive policy is the framework's: a deliberate exception gets an
inline ``# kakveda: allow[rule-id]`` pragma with a comment saying why.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from kakveda_tpu.analysis.framework import (
    FileContext,
    Finding,
    Rule,
    TreeContext,
    register,
)
from kakveda_tpu.analysis.rules import _parent_map, _self_attr

# The device plane: compiled programs and the modules that dispatch them.
_DEVICE_SCOPE = ("kakveda_tpu/models/", "kakveda_tpu/ops/", "kakveda_tpu/index/")

# THE blessed bucket seam (ops/knn.pow2_bucket) and its thin wrappers —
# rounding a data-dependent size through any of these kills the taint.
_BLESSED_BUCKETS = frozenset({
    "pow2_bucket", "batch_bucket", "_bucket_len", "bucket_for", "_bucket",
    "_corpus_pad", "_prefill_width",
})

_NP_NAMES = frozenset({"np", "onp", "numpy"})
_JNP_NAMES = frozenset({"jnp"})
# Shape-taking constructors: a tainted name in the shape argument makes the
# result a retrace-hazard array. *_like ctors mirror an existing array's
# shape and are exempt by construction.
_SHAPE_CTORS = frozenset({"zeros", "ones", "empty", "full", "arange"})


# ---------------------------------------------------------------------------
# shared jit discovery
# ---------------------------------------------------------------------------


def _is_jit_ref(node: ast.AST) -> bool:
    return (isinstance(node, ast.Name) and node.id == "jit") or (
        isinstance(node, ast.Attribute) and node.attr == "jit"
    )


def _is_jit_decorator(dec: ast.AST) -> bool:
    if _is_jit_ref(dec):
        return True
    if isinstance(dec, ast.Call):
        if _is_jit_ref(dec.func):
            return True
        # @partial(jax.jit, static_argnames=…)
        if (
            isinstance(dec.func, ast.Name) and dec.func.id == "partial"
        ) or (
            isinstance(dec.func, ast.Attribute) and dec.func.attr == "partial"
        ):
            return any(_is_jit_ref(a) for a in dec.args)
    return False


def _int_tuple(node: Optional[ast.AST]) -> Tuple[int, ...]:
    """``donate_argnums=(0, 1)`` / ``=2`` → (0, 1) / (2,)."""
    if node is None:
        return ()
    elts = node.elts if isinstance(node, (ast.Tuple, ast.List)) else [node]
    out = []
    for e in elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, int):
            out.append(e.value)
    return tuple(out)


def _str_tuple(node: Optional[ast.AST]) -> Tuple[str, ...]:
    if node is None:
        return ()
    elts = node.elts if isinstance(node, (ast.Tuple, ast.List)) else [node]
    return tuple(
        e.value for e in elts
        if isinstance(e, ast.Constant) and isinstance(e.value, str)
    )


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _fn_params(node: ast.AST) -> List[str]:
    args = node.args
    return [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]


class JitBody:
    """One traced body: a function/lambda whose code runs under trace."""

    __slots__ = ("label", "node", "static_names")

    def __init__(self, label: str, node: ast.AST, static_names: Set[str]):
        self.label = label
        self.node = node
        self.static_names = static_names


class JitEntry:
    """One *callable* jit entry point: the name host code calls."""

    __slots__ = ("name", "donate", "line")

    def __init__(self, name: str, donate: Tuple[int, ...], line: int):
        self.name = name
        self.donate = donate
        self.line = line


class JitIndex:
    """Per-file index of traced bodies and callable jit entry points.

    Shared by every device rule (and the relocated host-sync rule) so the
    family blesses/flags ONE consistent notion of "inside jit" and "a call
    into jit": ``@jax.jit``/``@partial(jax.jit, …)`` decorations,
    ``x = jax.jit(fn)`` / ``self._x = jax.jit(self._impl)`` wrappers
    (entry = the assignment target; lambdas traced inline), and
    ``jax.lax.scan(body, …)`` bodies.
    """

    def __init__(self, tree: ast.AST, parents: Dict[ast.AST, ast.AST]):
        self.bodies: List[JitBody] = []
        self.entries: Dict[str, JitEntry] = {}
        self._body_nodes: Set[int] = set()

        func_defs: Dict[str, ast.AST] = {}
        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                func_defs.setdefault(n.name, n)

        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in n.decorator_list:
                    if _is_jit_decorator(dec):
                        donate, statics = self._jit_opts(dec, n)
                        self._add_body(n.name, n, statics)
                        self._add_entry(n.name, donate, n.lineno)
                        break
            elif isinstance(n, ast.Call) and _is_jit_ref(n.func) and n.args:
                donate_nums = _int_tuple(_kw(n, "donate_argnums"))
                target = self._assign_target(n, parents)
                a = n.args[0]
                body: Optional[ast.AST] = None
                label = target or "<jit>"
                if isinstance(a, ast.Lambda):
                    body = a
                elif isinstance(a, ast.Name):
                    body = func_defs.get(a.id)
                    label = a.id
                elif isinstance(a, ast.Attribute):
                    body = func_defs.get(a.attr)
                    label = a.attr
                if body is not None:
                    statics = self._static_names(n, body)
                    self._add_body(label, body, statics)
                if target is not None:
                    self._add_entry(target, donate_nums, n.lineno)
            elif (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "scan"
                and n.args
                and isinstance(n.args[0], ast.Name)
            ):
                body = func_defs.get(n.args[0].id)
                if body is not None:
                    self._add_body(n.args[0].id, body, set())

    def _add_body(self, label: str, node: ast.AST, statics: Set[str]) -> None:
        if id(node) in self._body_nodes:
            return
        self._body_nodes.add(id(node))
        self.bodies.append(JitBody(label, node, statics))

    def _add_entry(self, name: str, donate: Tuple[int, ...], line: int) -> None:
        self.entries.setdefault(name, JitEntry(name, donate, line))

    def is_body(self, node: ast.AST) -> bool:
        return id(node) in self._body_nodes

    @staticmethod
    def _assign_target(
        call: ast.Call, parents: Dict[ast.AST, ast.AST]
    ) -> Optional[str]:
        """``x = jax.jit(f)`` / ``self._x = jax.jit(…)`` → the entry name."""
        parent = parents.get(call)
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
            t = parent.targets[0]
            if isinstance(t, ast.Name):
                return t.id
            attr = _self_attr(t)
            if attr is not None:
                return attr
        return None

    @staticmethod
    def _static_names(call: ast.Call, body: ast.AST) -> Set[str]:
        names = set(_str_tuple(_kw(call, "static_argnames")))
        params = _fn_params(body)
        for i in _int_tuple(_kw(call, "static_argnums")):
            if 0 <= i < len(params):
                names.add(params[i])
        return names

    @classmethod
    def _jit_opts(
        cls, dec: ast.AST, fn: ast.AST
    ) -> Tuple[Tuple[int, ...], Set[str]]:
        if isinstance(dec, ast.Call):
            return (
                _int_tuple(_kw(dec, "donate_argnums")),
                cls._static_names(dec, fn),
            )
        return (), set()


def _jit_index(fc: FileContext) -> Tuple[JitIndex, Dict[ast.AST, ast.AST]]:
    """Build (and memoize on the FileContext) the file's jit index."""
    cached = getattr(fc, "_device_jit_index", None)
    if cached is not None:
        return cached
    parents = _parent_map(fc.tree)
    idx = JitIndex(fc.tree, parents)
    fc._device_jit_index = (idx, parents)  # type: ignore[attr-defined]
    return idx, parents


def _name_loads(node: ast.AST) -> Iterator[ast.Name]:
    """Name loads in ``node``, excluding names used only as the base of an
    attribute access (``cfg.max_seq_len`` reads a static config field, not
    the per-request value ``cfg`` itself)."""
    attr_bases = {
        id(n.value) for n in ast.walk(node) if isinstance(n, ast.Attribute)
    }
    for n in ast.walk(node):
        if (
            isinstance(n, ast.Name)
            and isinstance(n.ctx, ast.Load)
            and id(n) not in attr_bases
        ):
            yield n


def _contains_blessed_call(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            f = n.func
            name = (
                f.id if isinstance(f, ast.Name)
                else f.attr if isinstance(f, ast.Attribute) else None
            )
            if name in _BLESSED_BUCKETS:
                return True
    return False


def _contains_taint_source(node: ast.AST) -> bool:
    """``len(…)`` calls or ``.shape`` reads anywhere in the expression."""
    for n in ast.walk(node):
        if (
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Name)
            and n.func.id == "len"
        ):
            return True
        if isinstance(n, ast.Attribute) and n.attr == "shape":
            return True
    return False


def _shape_ctor(call: ast.Call, modules: frozenset) -> Optional[ast.AST]:
    """``np.zeros(shape, …)``-style constructor → its shape expression."""
    f = call.func
    if (
        isinstance(f, ast.Attribute)
        and f.attr in _SHAPE_CTORS
        and isinstance(f.value, ast.Name)
        and f.value.id in modules
    ):
        shape = _kw(call, "shape")
        if shape is None and call.args:
            shape = call.args[0]
        return shape
    return None


def _assign_name_targets(stmt: ast.AST) -> List[ast.Name]:
    targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
    out: List[ast.Name] = []
    for t in targets:
        elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
        out.extend(e for e in elts if isinstance(e, ast.Name))
    return out


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------


@register
class RetraceHazard(Rule):
    id = "retrace-hazard"
    invariant = (
        "an array whose shape derives from a data-dependent Python value "
        "(len(), .shape[i]) must round through the blessed bucket seam "
        "(ops/knn.pow2_bucket or its wrappers) before being passed to a "
        "jit entry point — exact-fit shapes retrace per distinct size"
    )
    scope = _DEVICE_SCOPE

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        idx, _parents = _jit_index(fc)
        if not idx.entries:
            return []
        out: List[Finding] = []
        for func in ast.walk(fc.tree):
            if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if idx.is_body(func):
                continue  # inside a trace, shapes are static per trace
            out.extend(self._check_function(fc, idx, func))
        return out

    def _check_function(self, fc, idx: JitIndex, func) -> List[Finding]:
        out: List[Finding] = []
        tainted: Set[str] = set()   # data-dependent Python sizes
        hazard: Dict[str, str] = {}  # array name -> the size name that sized it

        events: List[Tuple[int, int, str, ast.AST]] = []
        for n in ast.walk(func):
            if n is not func and isinstance(
                n, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                continue  # nested defs analyzed on their own walk
            if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                if getattr(n, "value", None) is not None:
                    events.append((n.lineno, n.col_offset, "assign", n))
            elif isinstance(n, ast.Call):
                name = self._call_name(n)
                if name in idx.entries:
                    events.append((n.lineno, n.col_offset, "call", n))
        events.sort(key=lambda e: (e[0], e[1]))

        for _ln, _col, kind, node in events:
            if kind == "assign":
                self._apply_assign(node, tainted, hazard)
                continue
            entry = self._call_name(node)
            for arg in list(node.args) + [k.value for k in node.keywords]:
                flagged = self._hazard_in(arg, tainted, hazard)
                if flagged is not None:
                    array, size = flagged
                    out.append(Finding(
                        self.id, fc.rel, node.lineno,
                        f"array `{array}` (sized by data-dependent "
                        f"`{size}`) is passed to jit entry `{entry}` in "
                        f"{func.name}() — every distinct size is a fresh "
                        "trace+compile; round the size through the blessed "
                        "bucket seam (ops/knn.pow2_bucket or its wrappers)",
                    ))
        return out

    @staticmethod
    def _call_name(call: ast.Call) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Name):
            return f.id
        if isinstance(f, ast.Attribute):
            return f.attr
        return None

    def _apply_assign(self, stmt, tainted: Set[str], hazard: Dict[str, str]):
        value = stmt.value
        targets = _assign_name_targets(stmt)
        if not targets:
            return
        if _contains_blessed_call(value):
            # Rounded through the seam: the result is bucket-clean.
            for t in targets:
                tainted.discard(t.id)
                hazard.pop(t.id, None)
            return
        # Hazard-array creation: shape-taking ctor with a tainted dim.
        sized_by = self._ctor_tainted_dim(value, tainted)
        if sized_by is not None:
            for t in targets:
                hazard[t.id] = sized_by
                tainted.discard(t.id)
            return
        # Hazard propagation through plain rebinds (idx, val = pad_i, pad_v).
        src_names = [n.id for n in _name_loads(value)]
        carried = [n for n in src_names if n in hazard]
        if carried and isinstance(value, (ast.Name, ast.Tuple, ast.List)):
            srcs = (
                value.elts if isinstance(value, (ast.Tuple, ast.List))
                else [value]
            )
            for t, s in zip(targets, srcs):
                if isinstance(s, ast.Name) and s.id in hazard:
                    hazard[t.id] = hazard[s.id]
                    tainted.discard(t.id)
            return
        # Size-taint creation/propagation.
        if _contains_taint_source(value) or any(n in tainted for n in src_names):
            for t in targets:
                tainted.add(t.id)
                hazard.pop(t.id, None)
            return
        for t in targets:  # clean reassignment kills prior state
            tainted.discard(t.id)
            hazard.pop(t.id, None)

    def _ctor_tainted_dim(self, value, tainted: Set[str]) -> Optional[str]:
        for n in ast.walk(value):
            if isinstance(n, ast.Call):
                shape = _shape_ctor(n, _NP_NAMES | _JNP_NAMES)
                if shape is not None:
                    for name in _name_loads(shape):
                        if name.id in tainted:
                            return name.id
        return None

    def _hazard_in(
        self, arg: ast.AST, tainted: Set[str], hazard: Dict[str, str]
    ) -> Optional[Tuple[str, str]]:
        for name in _name_loads(arg):
            if name.id in hazard:
                return name.id, hazard[name.id]
        # Inline ctor in the call args: self._jit(np.zeros((b, d))).
        sized_by = self._ctor_tainted_dim(arg, tainted)
        if sized_by is not None:
            return "<inline array>", sized_by
        return None


# ---------------------------------------------------------------------------
# donation-after-use
# ---------------------------------------------------------------------------


@register
class DonationAfterUse(Rule):
    id = "donation-after-use"
    invariant = (
        "an array passed at a donate_argnums position is dead after the "
        "call — its buffer was handed to the output; the sanctioned shape "
        "rebinds the result over the donated name in the same statement "
        "(self.cache, … = _step_jit(…, self.cache, …))"
    )
    scope = _DEVICE_SCOPE

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        idx, parents = _jit_index(fc)
        donating = {n: e for n, e in idx.entries.items() if e.donate}
        if not donating:
            return []
        out: List[Finding] = []
        for call in ast.walk(fc.tree):
            if not isinstance(call, ast.Call):
                continue
            name = RetraceHazard._call_name(call)
            entry = donating.get(name)
            if entry is None:
                continue
            func = self._enclosing(call, parents)
            if func is None:
                continue
            stmt = self._enclosing_stmt(call, parents)
            for pos in entry.donate:
                if pos >= len(call.args):
                    continue
                key = self._var_key(call.args[pos])
                if key is None:
                    continue
                if stmt is not None and self._stmt_rebinds(stmt, key):
                    continue  # the sanctioned same-statement rebind
                read = self._first_read_after(func, stmt or call, key)
                if read is not None:
                    out.append(Finding(
                        self.id, fc.rel, read,
                        f"`{self._human(key)}` is donated to `{name}` "
                        f"(donate_argnums position {pos}) at line "
                        f"{call.lineno} but read afterwards — the donated "
                        "buffer is dead after the call; rebind the result "
                        "over it in the same statement before any use",
                    ))
        return out

    @staticmethod
    def _enclosing(node, parents):
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                return cur
            cur = parents.get(cur)
        return None

    @staticmethod
    def _enclosing_stmt(node, parents):
        cur = node
        while cur is not None:
            parent = parents.get(cur)
            if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Module, ast.ClassDef)):
                return cur if isinstance(cur, ast.stmt) else None
            cur = parent
        return None

    @staticmethod
    def _var_key(node: ast.AST) -> Optional[Tuple[str, str]]:
        if isinstance(node, ast.Name):
            return ("name", node.id)
        attr = _self_attr(node)
        if attr is not None:
            return ("self", attr)
        return None

    @staticmethod
    def _human(key: Tuple[str, str]) -> str:
        return key[1] if key[0] == "name" else f"self.{key[1]}"

    @classmethod
    def _matches(cls, node: ast.AST, key: Tuple[str, str]) -> bool:
        return cls._var_key(node) == key

    @classmethod
    def _stmt_rebinds(cls, stmt: ast.AST, key: Tuple[str, str]) -> bool:
        if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (
                stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
            )
            for t in targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                if any(cls._matches(e, key) for e in elts):
                    return True
        return False

    @classmethod
    def _first_read_after(cls, func, stmt, key) -> Optional[int]:
        """Line of the first Load of ``key`` lexically after ``stmt`` in
        ``func``, unless a Store/del kills it first. Lexical order is the
        approximation that matches this tree's straight-line dispatch code."""
        after = getattr(stmt, "end_lineno", stmt.lineno)
        events: List[Tuple[int, int, str]] = []
        for n in ast.walk(func):
            if cls._var_key(n) != key:
                continue
            if n.lineno <= after:
                continue
            if isinstance(n.ctx, ast.Load):
                events.append((n.lineno, n.col_offset, "load"))
            elif isinstance(n.ctx, (ast.Store, ast.Del)):
                events.append((n.lineno, n.col_offset, "store"))
        for ln, _col, kind in sorted(events):
            if kind == "store":
                return None
            return ln
        return None


# ---------------------------------------------------------------------------
# constant-capture
# ---------------------------------------------------------------------------


@register
class ConstantCapture(Rule):
    id = "constant-capture"
    invariant = (
        "jit bodies must not close over module/instance numpy arrays — a "
        "closed-over host array is re-hashed (and on remote backends "
        "re-uploaded) on every trace; pass it as an argument or upload it "
        "once at construction"
    )
    scope = _DEVICE_SCOPE

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        idx, _parents = _jit_index(fc)
        if not idx.bodies:
            return []
        np_globals, np_attrs = self._numpy_names(fc.tree)
        if not np_globals and not np_attrs:
            return []
        out: List[Finding] = []
        for body in idx.bodies:
            params = set(_fn_params(body.node))
            locals_: Set[str] = {
                t.id
                for n in ast.walk(body.node)
                if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign))
                for t in _assign_name_targets(n)
            }
            for n in ast.walk(body.node):
                ref = None
                if (
                    isinstance(n, ast.Name)
                    and isinstance(n.ctx, ast.Load)
                    and n.id in np_globals
                    and n.id not in params
                    and n.id not in locals_
                ):
                    ref = n.id
                else:
                    attr = _self_attr(n)
                    if (
                        attr is not None
                        and attr in np_attrs
                        and isinstance(n.ctx, ast.Load)
                        and "self" not in params
                    ):
                        ref = f"self.{attr}"
                if ref is not None:
                    out.append(Finding(
                        self.id, fc.rel, n.lineno,
                        f"jit body `{body.label}` closes over host numpy "
                        f"array `{ref}` — re-hashed per trace and "
                        "re-uploaded per compile on remote backends; pass "
                        "it as an argument or pre-upload it once",
                    ))
        return out

    @staticmethod
    def _numpy_names(tree: ast.AST) -> Tuple[Set[str], Set[str]]:
        """(module-level names, self attributes) known to hold numpy
        arrays: assigned from an np.* call or carrying the tree's ``_np``
        host-mirror suffix."""

        def is_np_value(v: ast.AST) -> bool:
            return (
                isinstance(v, ast.Call)
                and isinstance(v.func, ast.Attribute)
                and isinstance(v.func.value, ast.Name)
                and v.func.value.id in _NP_NAMES
            )

        np_globals: Set[str] = set()
        for stmt in getattr(tree, "body", []):
            if isinstance(stmt, ast.Assign) and is_np_value(stmt.value):
                np_globals.update(
                    t.id for t in stmt.targets if isinstance(t, ast.Name)
                )
        np_attrs: Set[str] = set()
        for n in ast.walk(tree):
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    attr = _self_attr(t)
                    if attr is not None and (
                        attr.endswith("_np") or is_np_value(n.value)
                    ):
                        np_attrs.add(attr)
        return np_globals, np_attrs


# ---------------------------------------------------------------------------
# dynamic-slice-by-trace
# ---------------------------------------------------------------------------


@register
class DynamicSliceByTrace(Rule):
    id = "dynamic-slice-by-trace"
    invariant = (
        "no x[n:] / lax.dynamic_slice sized by a traced value inside "
        "jit/scan bodies — output shapes must be static under trace "
        "(the prefix-slab contract); traced starts are fine, traced "
        "SIZES are the bug"
    )
    scope = ("kakveda_tpu/models/", "kakveda_tpu/ops/")

    _DSLICE = frozenset({"dynamic_slice", "dynamic_slice_in_dim"})

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        idx, _parents = _jit_index(fc)
        out: List[Finding] = []
        for body in idx.bodies:
            traced = {
                p for p in _fn_params(body.node)
                if p not in body.static_names and p != "self"
            }
            traced |= self._derived(body.node, traced)
            for n in ast.walk(body.node):
                if isinstance(n, ast.Subscript):
                    for sl in self._slices(n.slice):
                        name = self._traced_in(
                            [sl.lower, sl.upper, sl.step], traced
                        )
                        if name is not None:
                            out.append(Finding(
                                self.id, fc.rel, n.lineno,
                                f"slice bound `{name}` inside jit body "
                                f"`{body.label}` is traced/per-request — "
                                "the result shape changes per value; use a "
                                "static width + masking (or lax.dynamic_"
                                "slice with a STATIC size)",
                            ))
                elif isinstance(n, ast.Call):
                    fname = RetraceHazard._call_name(n)
                    if fname in self._DSLICE:
                        size_args = self._size_args(n, fname)
                        name = self._traced_in(size_args, traced)
                        if name is not None:
                            out.append(Finding(
                                self.id, fc.rel, n.lineno,
                                f"`{fname}` size `{name}` inside jit body "
                                f"`{body.label}` is traced/per-request — "
                                "dynamic_slice sizes must be static; only "
                                "the start indices may be traced",
                            ))
        return out

    @staticmethod
    def _slices(node: ast.AST) -> List[ast.Slice]:
        if isinstance(node, ast.Slice):
            return [node]
        if isinstance(node, ast.Tuple):
            return [e for e in node.elts if isinstance(e, ast.Slice)]
        return []

    @staticmethod
    def _size_args(call: ast.Call, fname: str) -> List[Optional[ast.AST]]:
        if fname == "dynamic_slice":  # (operand, starts, slice_sizes)
            out = [call.args[2] if len(call.args) > 2 else None]
            out.append(_kw(call, "slice_sizes"))
            return out
        # dynamic_slice_in_dim(operand, start, size, axis)
        return [call.args[2] if len(call.args) > 2 else None,
                _kw(call, "slice_size"), _kw(call, "size")]

    @staticmethod
    def _derived(body: ast.AST, traced: Set[str]) -> Set[str]:
        """Locals assigned from expressions over traced names."""
        derived: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for n in ast.walk(body):
                if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    value = getattr(n, "value", None)
                    if value is None:
                        continue
                    if any(
                        nm.id in traced or nm.id in derived
                        for nm in _name_loads(value)
                    ):
                        for t in _assign_name_targets(n):
                            if t.id not in derived and t.id not in traced:
                                derived.add(t.id)
                                changed = True
        return derived

    @staticmethod
    def _traced_in(
        nodes: Sequence[Optional[ast.AST]], traced: Set[str]
    ) -> Optional[str]:
        for node in nodes:
            if node is None:
                continue
            for name in _name_loads(node):
                if name.id in traced:
                    return name.id
        return None


# ---------------------------------------------------------------------------
# host-sync (relocated from analysis/rules.py — same id, same messages)
# ---------------------------------------------------------------------------


@register
class HostSyncHazards(Rule):
    id = "host-sync"
    invariant = (
        "no host synchronization (.item()/.tolist()/np.asarray/float(arg)) "
        "inside jit-compiled bodies in models/ and ops/, and no "
        "jnp.asarray(self.<mirror>_np) upload without .copy() — the CPU "
        "backend aliases numpy buffers zero-copy"
    )
    scope = ("kakveda_tpu/models/", "kakveda_tpu/ops/")

    def visit_file(self, fc: FileContext, ctx: TreeContext) -> List[Finding]:
        idx, _parents = _jit_index(fc)
        out: List[Finding] = []
        for body in idx.bodies:
            func = body.node
            params = set(_fn_params(func))
            for n in ast.walk(func):
                if not isinstance(n, ast.Call):
                    continue
                msg = None
                if isinstance(n.func, ast.Attribute):
                    if n.func.attr in ("item", "tolist"):
                        msg = f".{n.func.attr}() forces a device→host sync"
                    elif (
                        n.func.attr in ("asarray", "array")
                        and isinstance(n.func.value, ast.Name)
                        and n.func.value.id in _NP_NAMES
                    ):
                        msg = (
                            f"{n.func.value.id}.{n.func.attr}() on a traced "
                            "value forces a device→host sync"
                        )
                    elif (
                        n.func.attr == "device_get"
                        and isinstance(n.func.value, ast.Name)
                        and n.func.value.id == "jax"
                    ):
                        msg = "jax.device_get() forces a device→host sync"
                elif (
                    isinstance(n.func, ast.Name)
                    and n.func.id in ("float", "int", "bool")
                    and len(n.args) == 1
                    and isinstance(n.args[0], ast.Name)
                    and n.args[0].id in params
                ):
                    msg = (
                        f"{n.func.id}() on traced argument "
                        f"`{n.args[0].id}` forces a device→host sync"
                    )
                if msg is not None:
                    out.append(Finding(
                        self.id, fc.rel, n.lineno,
                        f"inside jit-compiled `{body.label}`: {msg} "
                        "(a device→host sync stalls every dispatch queued "
                        "behind it)",
                    ))

        # Mutable-mirror aliasing: jnp.asarray(self.<x>_np) without .copy().
        for n in ast.walk(fc.tree):
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "asarray"
                and isinstance(n.func.value, ast.Name)
                and n.func.value.id == "jnp"
                and n.args
                and isinstance(n.args[0], ast.Attribute)
                and n.args[0].attr.endswith("_np")
            ):
                out.append(Finding(
                    self.id, fc.rel, n.lineno,
                    f"jnp.asarray(…{n.args[0].attr}) without .copy(): on the "
                    "CPU backend the upload aliases the mutating numpy "
                    "mirror zero-copy (flaky garbage logits)",
                ))
        return out
