"""AST lint pass: repo-specific serving-stack hazards (rules L001-L006).

Pure stdlib (``ast``) — importable and runnable without jax, so the CI
job can fail fast before any lowering work starts.

Rules
-----
L001  host sync on a traced value inside jit-traced code: ``int()``/
      ``float()``/``bool()``, ``.item()``/``.tolist()``,
      ``np.asarray``/``np.array`` or ``jax.device_get`` applied to a
      value derived from a traced function's array arguments. Each of
      these either fails under jit or silently blocks the dispatch
      pipeline once per trace.
L002  Python control flow (``if``/``while``/``assert``) testing a
      traced value — a ConcretizationTypeError at trace time, or a
      per-call host block under ``jax.disable_jit``.
L003  use of the private jit ``_cache_size`` API anywhere but the one
      helper in ``serve/core.py`` (``_wrapper_compiles``): a jax release
      that changes or drops the API then breaks in one place.
L004  a ``time.time()``/``perf_counter()`` timed region that dispatches
      device work but never blocks on it (``jax.block_until_ready``,
      ``device_get``, ``np.asarray`` ...): async dispatch means such a
      timer measures *enqueue*, not completion.
L005  unpaired resource lifecycle in the serving clients: an acquire
      (``PagePool.alloc``/``retain``, hub ``pin``, prefix-cache
      ``adopt_prefix``) with no matching release anywhere in the same
      function while later statements can raise — the exception path
      leaks a reference. (The allocator's own modules — ``kvcache.py``
      — maintain these invariants internally and are covered by the
      property tests in ``tests/test_paged_kv.py``, so the pairing
      rule applies to the *client* modules only.)
L006  a prefill/suffix dispatch (``_prefill_fn``/``_suffix_fn``) whose
      shape argument (length bucket, or chunk index) is not derived
      from the bucket ladders — ``bucket_for``/``pad_shape`` results,
      ``chunk_len``/``max_len``, or ``len_buckets``/``batch_buckets``
      elements. A raw length (``toks.shape[1]``, ``len(prompt)``)
      keys a fresh executable per distinct value: the silent
      recompile-per-length regression the bucketed-jit contract (and
      the H004 executable-count bound) exists to prevent. Derivation
      is tracked by *name* across the whole file (the analysis is
      intra-file, not intra-procedural: ``Sb`` blessed by one
      ``bucket_for`` assignment stays blessed when passed as a
      parameter named ``Sb``), which matches the repo idiom of
      threading bucket values under stable names.

Taint model (L001/L002): inside a traced function, positional
parameters are traced arrays; keyword-only parameters are static
configuration (the repo-wide kernel idiom: ``def _kernel(refs..., *,
window, n_blocks)``), and closure variables are host values. Taint
propagates through expressions and assignments; ``.shape``/``.dtype``/
``.ndim`` and ``len()`` escape it. Traced functions are those
decorated with ``jax.jit``-family wrappers or passed (possibly through
``functools.partial``) to ``jit``/``vmap``/``pmap``/``pallas_call``/
``lax`` control-flow combinators in the same file. The analysis is
intra-procedural: a helper called *from* a traced function is only
checked if it is itself traced somewhere.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from . import REPO_ROOT, Violation

# names whose call-argument functions get traced
_TRACING_CALLS = {"jit", "vmap", "pmap", "pallas_call", "scan", "cond",
                  "while_loop", "fori_loop", "switch", "checkpoint",
                  "grad", "value_and_grad", "custom_vjp", "remat"}
# attribute reads that yield host metadata, not a traced value
_UNTAINT_ATTRS = {"shape", "dtype", "ndim", "size", "sharding", "aval",
                  "at"}
_UNTAINT_CALLS = {"len", "isinstance", "type", "getattr", "hasattr",
                  "range", "enumerate", "zip"}
# calls that always yield traced values even with no traced args
_ALWAYS_TRACED_CALLS = {"program_id", "num_programs"}

_HOST_CAST_CALLS = {"int", "float", "bool", "complex"}
_HOST_SYNC_METHODS = {"item", "tolist"}
_NP_ROOTS = {"np", "numpy", "onp"}

# L004: method-name hints for "this call dispatches device work" when
# the callee is repo code rather than a jnp/jax primitive
_DEVICE_HINTS = {"step", "tick", "admit", "admit_wave", "harvest",
                 "prefill", "decode", "generate", "warmup", "drain",
                 "run_step", "service", "dispatch", "install",
                 "pallas_call", "apply"}
_SYNC_CALLS = {"block_until_ready", "device_get", "effects_barrier"}
# jax-rooted calls that only *build* wrappers / traces — no dispatch
_NON_DISPATCH = {"jit", "vmap", "pmap", "grad", "value_and_grad",
                 "partial", "ShapeDtypeStruct", "eval_shape",
                 "named_scope", "lower", "compile"}
_TIME_FNS = {"time", "perf_counter", "monotonic", "process_time"}

# L005 pairing table and client scope
_ACQUIRE_RELEASE = {"alloc": {"release"},
                    "retain": {"release"},
                    "pin": {"unpin"},
                    "adopt_prefix": {"release"}}
_LIFECYCLE_FILES = ("src/repro/serve/core.py",
                    "src/repro/serve/scheduler.py",
                    "src/repro/serve/hub.py",
                    "src/repro/serve/engine.py",
                    "src/repro/serve/router.py")
_SAFE_CALLS = {"append", "pop", "extend", "add", "update", "get",
               "items", "keys", "values", "setdefault", "sort",
               "join", "copy", "len", "int", "str", "list", "dict",
               "tuple", "set", "zip", "range", "enumerate", "sorted",
               "min", "max", "sum", "abs", "isinstance", "format"}

_CACHE_SIZE_HOME = "src/repro/serve/core.py"


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(call: ast.Call) -> Optional[str]:
    return _dotted(call.func)


def _last_attr(name: Optional[str]) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


class _Scope:
    """Maps local names to function nodes (defs and lambda bindings)."""

    def __init__(self) -> None:
        self.by_name: Dict[str, ast.AST] = {}

    def collect(self, body: Iterable[ast.stmt]) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.by_name[stmt.name] = stmt
            elif isinstance(stmt, ast.Assign) and isinstance(
                    stmt.value, ast.Lambda):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        self.by_name[t.id] = stmt.value


class _Parents(ast.NodeVisitor):
    def __init__(self, tree: ast.AST) -> None:
        self.parent: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node

    def qualname(self, node: ast.AST) -> str:
        names: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                names.append(cur.name)
            elif isinstance(cur, ast.Lambda):
                names.append("<lambda>")
            cur = self.parent.get(cur)
        return ".".join(reversed(names)) or "<module>"

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self.parent.get(node)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            cur = self.parent.get(cur)
        return cur

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        cur = self.parent.get(node)
        while cur is not None:
            yield cur
            cur = self.parent.get(cur)


# ---------------------------------------------------------------------------
# traced-function discovery
# ---------------------------------------------------------------------------


def _is_tracing_name(name: Optional[str]) -> bool:
    return _last_attr(name) in _TRACING_CALLS


def _resolve_fn_arg(arg: ast.AST, scope: _Scope) -> Optional[ast.AST]:
    """The function node an argument of jit/vmap/... refers to."""
    if isinstance(arg, ast.Lambda):
        return arg
    if isinstance(arg, ast.Name):
        return scope.by_name.get(arg.id)
    if isinstance(arg, ast.Call) and _last_attr(
            _call_name(arg)) == "partial" and arg.args:
        return _resolve_fn_arg(arg.args[0], scope)
    return None


def find_traced_functions(tree: ast.AST) -> Set[ast.AST]:
    """Function/lambda nodes whose bodies run under a jax trace."""
    scope = _Scope()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef,
                             ast.AsyncFunctionDef, ast.ClassDef)):
            scope.collect(node.body)
    traced: Set[ast.AST] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                name = _dotted(dec) if not isinstance(dec, ast.Call) \
                    else _call_name(dec)
                if _is_tracing_name(name):
                    traced.add(node)
                elif isinstance(dec, ast.Call) and _last_attr(
                        _call_name(dec)) == "partial" and dec.args \
                        and _is_tracing_name(_dotted(dec.args[0])):
                    traced.add(node)
        elif isinstance(node, ast.Call) and _is_tracing_name(
                _call_name(node)):
            for arg in list(node.args) + [kw.value for kw in
                                          node.keywords]:
                fn = _resolve_fn_arg(arg, scope)
                if fn is not None:
                    traced.add(fn)
    return traced


# ---------------------------------------------------------------------------
# taint analysis inside one traced function (L001/L002)
# ---------------------------------------------------------------------------


class _Taint:
    def __init__(self, fn: ast.AST) -> None:
        self.tainted: Set[str] = set()
        args = fn.args
        for a in list(args.posonlyargs) + list(args.args):
            if a.arg not in ("self", "cls"):
                self.tainted.add(a.arg)
        if args.vararg:
            self.tainted.add(args.vararg.arg)
        # keyword-only params are static config by repo convention;
        # closure variables are host values: neither seeds taint

    def expr(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _UNTAINT_ATTRS:
                return False
            return self.expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value)
        if isinstance(node, ast.Call):
            name = _call_name(node)
            last = _last_attr(name)
            if last in _ALWAYS_TRACED_CALLS:
                return True
            if last in _UNTAINT_CALLS:
                return False
            # a method on a traced value yields a traced value
            if isinstance(node.func, ast.Attribute) and self.expr(
                    node.func.value):
                return True
            return any(self.expr(a) for a in node.args) or any(
                self.expr(kw.value) for kw in node.keywords)
        if isinstance(node, (ast.BinOp,)):
            return self.expr(node.left) or self.expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            return self.expr(node.left) or any(
                self.expr(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            return (self.expr(node.body) or self.expr(node.orelse)
                    or self.expr(node.test))
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        return False

    def assign(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.Assign):
            val = self.expr(stmt.value)
            for t in stmt.targets:
                self._mark(t, val)
        elif isinstance(stmt, ast.AugAssign):
            if self.expr(stmt.value) or self.expr(stmt.target):
                self._mark(stmt.target, True)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._mark(stmt.target, self.expr(stmt.value))
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._mark(stmt.target, self.expr(stmt.iter))

    def _mark(self, target: ast.AST, val: bool) -> None:
        if not val:
            return
        if isinstance(target, ast.Name):
            self.tainted.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._mark(e, True)


def _fn_statements(fn: ast.AST) -> List[ast.stmt]:
    if isinstance(fn, ast.Lambda):
        return []
    return list(fn.body)


def _check_traced_fn(fn: ast.AST, parents: _Parents, path: str
                     ) -> List[Violation]:
    out: List[Violation] = []
    taint = _Taint(fn)
    qual = parents.qualname(fn)
    body = _fn_statements(fn)
    # two forward passes so loop-carried assignments settle
    for _ in range(2):
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign, ast.For)):
                    taint.assign(node)
    nodes = ast.walk(fn.body) if isinstance(fn, ast.Lambda) else \
        iter([n for s in body for n in ast.walk(s)])
    for node in nodes:
        # don't descend into nested defs — they get their own pass if
        # they are themselves traced
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            continue
        if isinstance(node, ast.Call):
            name = _call_name(node)
            last = _last_attr(name)
            tainted_arg = any(taint.expr(a) for a in node.args)
            if isinstance(node.func, ast.Name) and \
                    node.func.id in _HOST_CAST_CALLS and tainted_arg:
                out.append(Violation(
                    "L001", path, node.lineno, qual,
                    f"{node.func.id}() on a traced value forces a "
                    "host sync (ConcretizationTypeError under jit)"))
            elif last in _HOST_SYNC_METHODS and isinstance(
                    node.func, ast.Attribute) and taint.expr(
                        node.func.value):
                out.append(Violation(
                    "L001", path, node.lineno, qual,
                    f".{last}() on a traced value forces a host sync"))
            elif name and "." in name and name.split(".")[0] in \
                    _NP_ROOTS and last in ("asarray", "array") \
                    and tainted_arg:
                out.append(Violation(
                    "L001", path, node.lineno, qual,
                    f"{name}() materialises a traced value on host"))
            elif last == "device_get" and tainted_arg:
                out.append(Violation(
                    "L001", path, node.lineno, qual,
                    "jax.device_get on a traced value inside a traced "
                    "function"))
        elif isinstance(node, (ast.If, ast.While)) and taint.expr(
                node.test):
            out.append(Violation(
                "L002", path, node.lineno, qual,
                "Python branch on a traced value (use jnp.where / "
                "lax.cond / pl.when)"))
        elif isinstance(node, ast.Assert) and taint.expr(node.test):
            out.append(Violation(
                "L002", path, node.lineno, qual,
                "assert on a traced value (use checkify or a static "
                "shape check)"))
    return out


# ---------------------------------------------------------------------------
# L004 — unsynced device timing
# ---------------------------------------------------------------------------


def _is_time_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = _call_name(node) or ""
    return (name.startswith("time.") and _last_attr(name) in _TIME_FNS) \
        or name in ("perf_counter", "monotonic")


def _walk_skip_fns(stmts: Sequence[ast.stmt]) -> List[ast.AST]:
    """All nodes under ``stmts``, not descending into nested ``def``
    bodies (a nested def's body doesn't execute in this region).
    Lambdas ARE descended into: the repo idiom passes them inline to
    eagerly-applied combinators (``tree_map(lambda x:
    x.block_until_ready(), r)``), so their bodies do run here."""
    out: List[ast.AST] = []

    def visit(n: ast.AST) -> None:
        out.append(n)
        for c in ast.iter_child_nodes(n):
            if not isinstance(c, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                visit(c)

    for s in stmts:
        visit(s)
    return out


def _check_timing(fn_body: Sequence[ast.stmt], qual: str, path: str
                  ) -> List[Violation]:
    out: List[Violation] = []
    starts: Dict[str, int] = {}           # var -> lineno of t0 = time.*()
    spans: List[Tuple[str, int, int]] = []  # (var, start_line, end_line)
    nodes = _walk_skip_fns(fn_body)
    for node in nodes:
        if isinstance(node, ast.Assign) and _is_time_call(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    starts[t.id] = node.lineno
        elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
            if _is_time_call(node.left) and isinstance(
                    node.right, ast.Name) and node.right.id in starts:
                spans.append((node.right.id, starts[node.right.id],
                              node.lineno))
    for var, lo, hi in spans:
        device: Optional[ast.Call] = None
        synced = False
        for node in nodes:
            line = getattr(node, "lineno", None)
            if line is None or not (lo < line <= hi):
                continue
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node) or ""
            last = _last_attr(name)
            root = name.split(".")[0] if name else ""
            if last in _SYNC_CALLS or (root in _NP_ROOTS and last in
                                       ("asarray", "array")):
                synced = True
            elif (root in ("jnp", "jax") and last not in _NON_DISPATCH) \
                    or last.lstrip("_") in _DEVICE_HINTS:
                device = device or node
        if device is not None and not synced:
            out.append(Violation(
                "L004", path, device.lineno, qual,
                f"timed region ({var}: lines {lo}..{hi}) dispatches "
                f"device work ({_call_name(device)}) with no "
                "block_until_ready/device_get — measures enqueue, not "
                "completion"))
    return out


# ---------------------------------------------------------------------------
# L005 — lifecycle pairing
# ---------------------------------------------------------------------------


def _stmts_after(node: ast.AST, parents: _Parents,
                 fn: ast.AST) -> List[ast.stmt]:
    """Statements that can still execute after ``node`` succeeded,
    walking out through enclosing blocks up to ``fn``. Handlers of an
    enclosing ``try`` are included only when a later try-body statement
    can raise after the acquire; ``finally`` and ``else`` always run."""
    # the statement containing `node`
    cur: Optional[ast.AST] = node
    while cur is not None and not isinstance(cur, ast.stmt):
        cur = parents.parent.get(cur)
    out: List[ast.stmt] = []
    while cur is not None and cur is not fn:
        block = parents.parent.get(cur)
        if block is None or block is fn and not isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            break
        hit = False
        for field in ("body", "orelse", "finalbody"):
            seq = getattr(block, field, None)
            if isinstance(seq, list) and cur in seq:
                hit = True
                idx = seq.index(cur)
                out.extend(seq[idx + 1:])
                if isinstance(block, ast.Try) and field == "body":
                    if idx + 1 < len(seq):     # later try-body stmt can
                        for h in block.handlers:  # raise -> handler runs
                            out.extend(h.body)
                    out.extend(block.orelse)
                    out.extend(block.finalbody)
        if not hit and isinstance(block, ast.ExceptHandler) and \
                cur in block.body:
            out.extend(block.body[block.body.index(cur) + 1:])
        if block is fn:
            break
        cur = block if isinstance(
            block, (ast.stmt, ast.excepthandler)) else None
        if cur is None:
            break
    return out


def _check_lifecycles(fn: ast.AST, parents: _Parents, path: str
                      ) -> List[Violation]:
    out: List[Violation] = []
    body = _fn_statements(fn)
    if not body:
        return out
    qual = parents.qualname(fn)
    all_calls = [n for s in body for n in ast.walk(s)
                 if isinstance(n, ast.Call)]
    released = {_last_attr(_call_name(c)) for c in all_calls}
    for call in all_calls:
        attr = _last_attr(_call_name(call))
        if attr not in _ACQUIRE_RELEASE:
            continue
        if not isinstance(call.func, ast.Attribute):
            continue                      # bare name: not a method call
        partners = _ACQUIRE_RELEASE[attr]
        if partners & released:
            continue                      # paired somewhere in the fn
        risky = None
        for stmt in _stmts_after(call, parents, fn):
            for n in ast.walk(stmt):
                if isinstance(n, ast.Call):
                    last = _last_attr(_call_name(n))
                    if last not in _SAFE_CALLS and last not in partners:
                        risky = n
                        break
            if risky is not None:
                break
        if risky is not None:
            out.append(Violation(
                "L005", path, call.lineno, qual,
                f"{attr}() with no matching "
                f"{'/'.join(sorted(partners))} in this function, and a "
                f"later call ({_call_name(risky) or '?'}:{risky.lineno})"
                " can raise — the exception path leaks the reference"))
    return out


# ---------------------------------------------------------------------------
# L006 — prefill dispatch shapes must come from the bucket ladders
# ---------------------------------------------------------------------------

_BUCKET_FNS = {"_prefill_fn", "_suffix_fn", "_verify_fn"}
_BUCKET_SOURCES = {"bucket_for", "pad_shape", "make_buckets"}
_BUCKET_ATTRS = {"chunk_len", "max_len", "len_buckets", "batch_buckets",
                 "page", "speculate_k"}
_BUCKET_CALLS = {"range", "min", "max", "len", "sum", "sorted", "tuple",
                 "list"}


def _collect_blessed(tree: ast.AST) -> Set[str]:
    """Names bound (anywhere in the file) to bucket-ladder-derived
    values. Two propagation passes so chained assignments settle."""
    blessed: Set[str] = set()

    def ok(node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return isinstance(node.value, int)
        if isinstance(node, ast.Name):
            return node.id in blessed
        if isinstance(node, ast.Attribute):
            return node.attr in _BUCKET_ATTRS
        if isinstance(node, ast.Subscript):
            return ok(node.value)
        if isinstance(node, ast.BinOp):
            return ok(node.left) and ok(node.right)
        if isinstance(node, ast.UnaryOp):
            return ok(node.operand)
        if isinstance(node, ast.Call):
            last = _last_attr(_call_name(node))
            if last in _BUCKET_SOURCES:
                return True
            if last in _BUCKET_CALLS:
                return all(ok(a) for a in node.args)
            return False
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(ok(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return ok(node.body) and ok(node.orelse)
        if isinstance(node, ast.BoolOp):
            return all(ok(v) for v in node.values)
        return False

    def mark(target: ast.AST) -> None:
        if isinstance(target, ast.Name):
            blessed.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                mark(e)

    for _ in range(2):
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and ok(node.value):
                for t in node.targets:
                    mark(t)
            elif isinstance(node, ast.AnnAssign) and \
                    node.value is not None and ok(node.value):
                mark(node.target)
            elif isinstance(node, (ast.For, ast.AsyncFor)) and \
                    ok(node.iter):
                mark(node.target)
            elif isinstance(node, ast.comprehension) and ok(node.iter):
                mark(node.target)
    return blessed


def _check_bucket_shapes(tree: ast.AST, parents: _Parents,
                         path: str) -> List[Violation]:
    """L006: the shape-keying argument of every ``_prefill_fn(Bb, Sb)``
    / ``_suffix_fn(Bb, k)`` / ``_verify_fn(Bb, k)`` call site must be
    bucket-derived (``speculate_k`` counts: it is fixed per engine and
    part of the executable ladder, so it keys exactly one extra
    executable family). Only the
    second argument is checked — the batch argument is routinely read
    back off a descriptor array's static shape, which is already
    bucket-sized by construction."""
    out: List[Violation] = []
    blessed = _collect_blessed(tree)

    def ok(node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return isinstance(node.value, int)
        if isinstance(node, ast.Name):
            return node.id in blessed
        if isinstance(node, ast.Attribute):
            return node.attr in _BUCKET_ATTRS
        if isinstance(node, ast.Subscript):
            return ok(node.value)
        if isinstance(node, ast.BinOp):
            return ok(node.left) and ok(node.right)
        if isinstance(node, ast.UnaryOp):
            return ok(node.operand)
        if isinstance(node, ast.Call):
            last = _last_attr(_call_name(node))
            return last in _BUCKET_SOURCES or (
                last in _BUCKET_CALLS and all(ok(a) for a in node.args))
        return False

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _last_attr(_call_name(node)) in _BUCKET_FNS
                and len(node.args) >= 2):
            continue
        arg = node.args[1]
        if not ok(arg):
            fn = _last_attr(_call_name(node))
            out.append(Violation(
                "L006", path, node.lineno, parents.qualname(node),
                f"{fn}() shape argument "
                f"{ast.unparse(arg) if hasattr(ast, 'unparse') else '?'}"
                " is not derived from the bucket ladders (bucket_for/"
                "pad_shape/chunk_len/len_buckets) — every distinct "
                "value keys a fresh XLA executable, breaking the "
                "bounded-compile contract"))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def default_paths(root: str = REPO_ROOT) -> List[str]:
    out: List[str] = []
    for base in ("src/repro", "benchmarks"):
        for dirpath, _dirs, files in os.walk(os.path.join(root, base)):
            for f in sorted(files):
                if f.endswith(".py"):
                    out.append(os.path.join(dirpath, f))
    return out


def lint_source(src: str, path: str) -> List[Violation]:
    """Lint one file's source. ``path`` is the repo-relative name used
    in reports and baseline keys."""
    tree = ast.parse(src, filename=path)
    parents = _Parents(tree)
    out: List[Violation] = []

    # L003 — private _cache_size outside its guarded home
    if path != _CACHE_SIZE_HOME:
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr == "_cache_size":
                out.append(Violation(
                    "L003", path, node.lineno, parents.qualname(node),
                    "private jit._cache_size() outside the guarded "
                    "helper serve/core.py:_wrapper_compiles (use "
                    "serve.core._wrapper_compiles)"))

    # L001/L002 — traced-code hazards
    for fn in find_traced_functions(tree):
        out.extend(_check_traced_fn(fn, parents, path))

    # L004 — unsynced timing, per function and at module level
    fns = [n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in fns:
        out.extend(_check_timing(fn.body, parents.qualname(fn), path))
    out.extend(_check_timing(
        [s for s in tree.body
         if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef))], "<module>", path))

    # L005 — lifecycle pairing in the client modules
    if any(path.endswith(p) or path == p for p in _LIFECYCLE_FILES):
        for fn in fns:
            out.extend(_check_lifecycles(fn, parents, path))

    # L006 — prefill dispatch shapes come from the bucket ladders
    out.extend(_check_bucket_shapes(tree, parents, path))
    return out


def run(paths: Optional[Sequence[str]] = None,
        root: str = REPO_ROOT) -> List[Violation]:
    out: List[Violation] = []
    for p in (paths or default_paths(root)):
        rel = os.path.relpath(p, root) if os.path.isabs(p) else p
        with open(os.path.join(root, rel), encoding="utf-8") as fh:
            out.extend(lint_source(fh.read(), rel.replace(os.sep, "/")))
    return out
