"""dlint hazard rules D001–D005.

Each rule is a callable ``rule(ctx: ModuleContext) -> Iterator[Finding]``
with ``rule_id``/``title``/``hint`` attributes and an optional ``scope``
(repo-path substrings the rule is restricted to). They encode the hazard
classes that cost this repo real benchmark regressions in earlier rounds —
the reference C++ program shows its sync points and transfer sizes in the
source, while tracing hides ours; these rules make the same classes visible
at lint time:

  D001  implicit device->host sync in a hot-path module
  D002  jax.jit retrace traps (static_argnames drift / non-static literals)
  D003  jitted function closing over mutable module/instance state
  D004  per-step list-comp feeding jnp.asarray in the decode step
  D005  time.time() deltas around device work without block_until_ready
  D006  tp collective issued outside parallel/tp.py's _ici_* helpers
  D007  implicit dtype promotion: a bf16/f16 value mixed with an explicit
        f32 operand silently upcasts the whole expression
  D008  monotonic/perf_counter delta around device work with neither a
        sync nor a span — invisible to the timeline, measures dispatch

False-positive policy: rules stay *narrow* (better to miss a hazard than to
train people to pragma reflexively); intentional sites carry
``# dlint: allow[Dnnn] reason`` pragmas and pre-existing debt lives in
``tools/dlint_baseline.txt``.
"""

from __future__ import annotations

import ast
from typing import Iterator

from .lint import Finding, ModuleContext

HOT_PATH_SCOPE = ("runtime/", "ops/", "parallel/")

# call targets (post alias-resolution) that force a device->host transfer
_SYNC_CALLS = {
    "numpy.asarray": "np.asarray on a device value blocks on the transfer",
    "jax.device_get": "device_get is an explicit device->host sync",
    "jax.block_until_ready": "block_until_ready drains the device queue",
}
# numpy.asarray over these argument forms is host-side staging, not a sync
_HOST_LITERALS = (ast.List, ast.Tuple, ast.Dict, ast.Set, ast.Constant,
                  ast.ListComp, ast.GeneratorExp, ast.DictComp, ast.SetComp)


def _finding(ctx: ModuleContext, node: ast.AST, rule_id: str, message: str,
             hint: str) -> Finding:
    line = getattr(node, "lineno", 0)
    snippet = (ctx.lines[line - 1].strip()
               if 0 < line <= len(ctx.lines) else "")
    return Finding(rule=rule_id, path=ctx.relpath, line=line,
                   message=message, hint=hint, context=ctx.qualname(node),
                   snippet=snippet)


def rule(rule_id: str, title: str, hint: str, scope=None):
    def deco(fn):
        fn.rule_id, fn.title, fn.hint, fn.scope = rule_id, title, hint, scope
        return fn
    return deco


@rule("D001", "implicit device->host sync in hot-path module",
      "keep the hot path async; if the sync is intentional, annotate it "
      "with `# dlint: allow[D001] <reason>`",
      scope=HOT_PATH_SCOPE)
def d001_implicit_sync(ctx: ModuleContext) -> Iterator[Finding]:
    """np.asarray / .item() / device_get / block_until_ready — and
    float()/int()/bool() wrapped directly around a jnp/jax call result —
    inside runtime/, ops/, or parallel/. Every one of these blocks the
    Python thread on the device stream; in the decode loop that turns an
    async dispatch pipeline into lock-step round-trips."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        target = ctx.call_target(node)
        if target in _SYNC_CALLS:
            if (target == "numpy.asarray" and node.args
                    and isinstance(node.args[0], _HOST_LITERALS)):
                continue  # host literal in, host array out — no device sync
            yield _finding(ctx, node, "D001",
                           f"implicit device->host sync: {_SYNC_CALLS[target]}",
                           d001_implicit_sync.hint)
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr == "item" and not node.args
              and not node.keywords):
            yield _finding(ctx, node, "D001",
                           ".item() forces a device->host sync",
                           d001_implicit_sync.hint)
        elif (isinstance(node.func, ast.Name)
              and node.func.id in ("float", "int", "bool")
              and len(node.args) == 1 and isinstance(node.args[0], ast.Call)):
            inner = ctx.call_target(node.args[0])
            if inner and inner.split(".", 2)[0] in ("jax", "jnp") or (
                    inner and inner.startswith("jax.numpy.")):
                yield _finding(
                    ctx, node, "D001",
                    f"{node.func.id}() on a jax value syncs the device",
                    d001_implicit_sync.hint)


def _def_param_names(fn: ast.AST) -> tuple[set[str], bool, list[str]]:
    """(named params, has **kwargs, positional order) of a def/lambda."""
    a = fn.args
    positional = [p.arg for p in a.posonlyargs + a.args]
    names = set(positional) | {p.arg for p in a.kwonlyargs}
    return names, a.kwarg is not None, positional


@rule("D002", "jax.jit retrace trap",
      "declare compile-time parameters in static_argnames (and only "
      "parameters that exist)")
def d002_retrace_trap(ctx: ModuleContext) -> Iterator[Finding]:
    """Two traps around jit static arguments:

    (a) ``static_argnames`` naming a parameter the function doesn't have —
        dead weight at best, and it silently stops being static when the
        real parameter is renamed;
    (b) a call into a module-local jitted function passing a str/bool
        literal to a parameter NOT in static_argnames — strings fail at
        trace time, and branch-y bools retrace per value.
    """
    for def_node, (site, static) in ctx.jitted_defs.items():
        if isinstance(def_node, ast.Lambda):
            continue
        names, has_kwargs, _ = _def_param_names(def_node)
        if has_kwargs:
            continue
        for s in sorted(static - names):
            yield _finding(
                ctx, site, "D002",
                f"static_argnames names '{s}' but "
                f"{def_node.name}() has no such parameter",
                "static_argnames must match the signature")

    # (b): literal str/bool flowing into a jitted callable, non-static
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not isinstance(node.func,
                                                            ast.Name):
            continue
        callee = ctx.jitted_names.get(node.func.id)
        if node.func.id not in ctx.jitted_names or callee is None:
            continue
        static = ctx.jit_static.get(callee, set())
        names, has_kwargs, positional = _def_param_names(callee)
        if has_kwargs:
            continue

        def literal(expr):
            return (isinstance(expr, ast.JoinedStr)
                    or (isinstance(expr, ast.Constant)
                        and isinstance(expr.value, (str, bool))))

        for i, arg in enumerate(node.args):
            if literal(arg) and i < len(positional) \
                    and positional[i] not in static:
                yield _finding(
                    ctx, node, "D002",
                    f"literal {ast.dump(arg)[:40]} passed to traced "
                    f"parameter '{positional[i]}' of jitted "
                    f"{node.func.id}()", d002_retrace_trap.hint)
        for kw in node.keywords:
            if kw.arg and literal(kw.value) and kw.arg in names \
                    and kw.arg not in static:
                yield _finding(
                    ctx, node, "D002",
                    f"literal passed to traced parameter '{kw.arg}' of "
                    f"jitted {node.func.id}()", d002_retrace_trap.hint)


def _mutable_globals(ctx: ModuleContext) -> set[str]:
    """Module-level names bound to a mutable display ({} / [] / set())."""
    out: set[str] = set()
    for stmt in ctx.tree.body:
        if isinstance(stmt, ast.Assign):
            if isinstance(stmt.value, (ast.Dict, ast.List, ast.Set,
                                       ast.DictComp, ast.ListComp,
                                       ast.SetComp)):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return out


@rule("D003", "jitted function closes over mutable state",
      "pass the value as an argument (traced or static) — closures are "
      "baked in at trace time and silently go stale")
def d003_jit_closure(ctx: ModuleContext) -> Iterator[Finding]:
    """A jitted function reading ``self.attr`` or a mutable module global
    captures whatever the value was at FIRST trace; later mutations are
    invisible (or worse, trigger surprise retraces via weak refs)."""
    mutable = _mutable_globals(ctx)
    for def_node in ctx.jitted_defs:
        params, _, _ = _def_param_names(def_node)
        # one dedup namespace per kind: `self.cache` and a module global
        # `cache` are distinct hazards and must both be reported
        seen: set[tuple[str, str]] = set()
        for node in ast.walk(def_node):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self" and "self" not in params
                    and ("attr", node.attr) not in seen):
                seen.add(("attr", node.attr))
                yield _finding(
                    ctx, node, "D003",
                    f"jitted function reads self.{node.attr} from its "
                    f"closure", d003_jit_closure.hint)
            elif (isinstance(node, ast.Name) and node.id in mutable
                  and isinstance(node.ctx, ast.Load)
                  and node.id not in params
                  and ("global", node.id) not in seen):
                seen.add(("global", node.id))
                yield _finding(
                    ctx, node, "D003",
                    f"jitted function reads mutable module global "
                    f"'{node.id}'", d003_jit_closure.hint)


@rule("D004", "per-step host list materialization in the decode step",
      "stage rows into one persistent numpy buffer and upload it in a "
      "single jnp.asarray call",
      scope=("runtime/",))
def d004_hot_loop_alloc(ctx: ModuleContext) -> Iterator[Finding]:
    """``jnp.asarray([f(s) for s in pool])`` in a per-step function builds
    B boxed Python objects + one fresh host array + one tiny transfer PER
    LIST — per decode step. Fires inside functions named step*/\\_step* and
    inside explicit loops in runtime/ modules; the fix is one pre-allocated
    staging buffer and one upload."""
    asarray_targets = ("jax.numpy.asarray", "jax.numpy.array")

    def in_step_fn(node):
        fn = ctx.enclosing_function(node)
        return (fn is not None and isinstance(fn, (ast.FunctionDef,
                                                   ast.AsyncFunctionDef))
                and fn.name.lstrip("_").startswith("step"))

    # names bound to list comprehensions inside step functions, so
    # `x = [..]; jnp.asarray(x)` is caught too
    comp_names: set[tuple[ast.AST, str]] = set()
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Assign) and in_step_fn(node)
                and isinstance(node.value, (ast.ListComp, ast.List))):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    comp_names.add((ctx.enclosing_function(node), t.id))

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if ctx.call_target(node) not in asarray_targets or not node.args:
            continue
        if not (in_step_fn(node) or ctx.in_loop(node)):
            continue
        arg = node.args[0]
        is_comp = isinstance(arg, (ast.ListComp, ast.List, ast.GeneratorExp))
        is_comp_name = (isinstance(arg, ast.Name)
                        and (ctx.enclosing_function(node),
                             arg.id) in comp_names)
        if is_comp or is_comp_name:
            yield _finding(
                ctx, node, "D004",
                "per-step list materialized into jnp.asarray",
                d004_hot_loop_alloc.hint)


@rule("D005", "time.time() delta around device work",
      "use time.perf_counter() and block_until_ready() so the interval "
      "measures device work, not dispatch")
def d005_bare_time(ctx: ModuleContext) -> Iterator[Finding]:
    """A ``time.time()`` delta in a function that dispatches jax work but
    never calls block_until_ready measures only the async dispatch — the
    round-1 'TPU is infinitely fast' trap. (time.monotonic/perf_counter
    deltas with an explicit sync, or a blocking np.asarray, are the
    sanctioned patterns.)"""
    funcs: dict[ast.AST, dict] = {}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not ctx.function_calls_device(node):
            continue
        if ctx.function_calls(node, "block_until_ready"):
            continue
        t_names: set[str] = set()
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Assign)
                    and ctx.enclosing_function(sub) is node
                    and isinstance(sub.value, ast.Call)
                    and ctx.call_target(sub.value) == "time.time"):
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        t_names.add(t.id)
        funcs[node] = {"t_names": t_names}

    for fn, info in funcs.items():
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.BinOp) or not isinstance(sub.op,
                                                                ast.Sub):
                continue
            # a delta inside a NESTED def is that def's business (it gets
            # its own entry iff it dispatches device work) — without this,
            # host-only timeout math in a helper is falsely flagged and a
            # qualifying nested fn is reported twice
            if ctx.enclosing_function(sub) is not fn:
                continue

            def is_time_side(expr):
                if (isinstance(expr, ast.Call)
                        and ctx.call_target(expr) == "time.time"):
                    return True
                return (isinstance(expr, ast.Name)
                        and expr.id in info["t_names"])

            if is_time_side(sub.left) or is_time_side(sub.right):
                yield _finding(
                    ctx, sub, "D005",
                    "time.time() interval around un-synced device work",
                    d005_bare_time.hint)


# jax.lax collectives that would add un-modeled ICI traffic to the tp
# forward; pmean/pmax/pmin included — any reduction over the mesh crosses
# the wire
_COLLECTIVE_CALLS = frozenset(
    f"jax.lax.{name}" for name in
    ("all_gather", "psum", "psum_scatter", "all_to_all", "ppermute",
     "pmax", "pmin", "pmean", "reduce_scatter"))
# the blessed sites: the ONLY functions in parallel/tp.py allowed to bind a
# collective — comm_stats.tp_collective_budget models exactly what flows
# through these, and the J001 contract pins the traced program to it.
# _ici_ppermute is the overlap scheme's ring hop; _ici_ring_reduce builds
# the ring but binds its collective THROUGH _ici_ppermute (blessed here so
# a future inline ppermute refactor stays inside the family).
_TP_COMM_HELPERS = frozenset(("_ici_gather", "_ici_psum", "_ici_scatter",
                              "_ici_ppermute", "_ici_ring_reduce"))


@rule("D006", "tp collective outside the comm-model helpers",
      "route tp collectives through the _ici_* helpers in parallel/tp.py "
      "and land the matching parallel/comm_stats.py budget term in the "
      "same change, or the J001 contract (and every ICI projection) drifts "
      "from the program",
      scope=("parallel/tp.py",))
def d006_unmodeled_collective(ctx: ModuleContext) -> Iterator[Finding]:
    """Every collective the tp forward issues must have a comm_stats term.
    J001 catches traced drift after the fact; this rule catches it at the
    source: any ``jax.lax`` collective call in parallel/tp.py outside the
    _ici_gather/_ici_psum/_ici_scatter helpers is flagged — a new
    collective belongs in a helper (so shard_sim can stand it in locally)
    with its budget entry, not inline in a layer body."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if ctx.call_target(node) not in _COLLECTIVE_CALLS:
            continue
        fn = ctx.enclosing_function(node)
        if fn is not None and getattr(fn, "name", "") in _TP_COMM_HELPERS:
            continue
        yield _finding(
            ctx, node, "D006",
            f"collective {ctx.call_target(node)} issued outside the "
            f"_ici_* comm-model helpers",
            d006_unmodeled_collective.hint)


# dtype names on each side of the D007 promotion hazard, post alias
# resolution (jnp -> jax.numpy). String forms cover .astype("bfloat16").
_LOW_DTYPES = frozenset(("jax.numpy.bfloat16", "jax.numpy.float16",
                         "numpy.float16", "bfloat16", "float16"))
_F32_DTYPES = frozenset(("jax.numpy.float32", "numpy.float32", "float32"))
# calls whose RESULT is a strong-typed f32/f64 scalar or array — unlike a
# bare Python literal (weak-typed, keeps the array's dtype), these win the
# promotion against a bf16/f16 operand
_F32_CONSTRUCTORS = frozenset(("jax.numpy.float32", "numpy.float32",
                               "numpy.float64"))


@rule("D007", "implicit dtype promotion to f32 in a low-precision path",
      "pick ONE dtype for the expression: cast the constant/operand to the "
      "bf16/f16 side (or the value to f32 explicitly) — a silent upcast "
      "doubles the bytes of every downstream read",
      scope=("ops/", "parallel/"))
def d007_dtype_promotion(ctx: ModuleContext) -> Iterator[Finding]:
    """Arithmetic mixing a KNOWN-low-precision local (assigned from
    ``.astype(jnp.bfloat16/float16)`` or a dtype=bf16/f16 builder) with an
    EXPLICIT f32 operand (``jnp.float32(...)``/``np.float32(...)``
    constructors — strong-typed, unlike weak Python literals — or a local
    assigned from ``.astype(jnp.float32)``). JAX promotes the whole
    expression to f32 silently: the Q40/bf16 memory saving evaporates one
    op downstream, with no error and no visible cast. Stays narrow by
    design: both sides must be provably typed within the same function —
    a bare ``x * 0.5`` never fires (weak scalars keep the array dtype)."""

    def dtype_class(expr) -> str | None:
        """'low' / 'f32' for a dtype-expression (jnp.bfloat16, "float16",
        np.float32, ...), else None."""
        name = None
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            name = expr.value
        elif isinstance(expr, (ast.Attribute, ast.Name)):
            name = ctx.dotted(expr)
        if name in _LOW_DTYPES:
            return "low"
        if name in _F32_DTYPES:
            return "f32"
        return None

    def value_class(expr, local) -> str | None:
        """'low' / 'f32' for a value expression: a tracked local name, an
        .astype(...) call, or a dtype=... builder / f32 constructor."""
        if isinstance(expr, ast.Name):
            return local.get(expr.id)
        if not isinstance(expr, ast.Call):
            return None
        if (isinstance(expr.func, ast.Attribute)
                and expr.func.attr == "astype" and expr.args):
            return dtype_class(expr.args[0])
        if ctx.call_target(expr) in _F32_CONSTRUCTORS:
            return "f32"
        for kw in expr.keywords:
            if kw.arg == "dtype":
                return dtype_class(kw.value)
        return None

    # per-function map of local name -> 'low' | 'f32'
    locals_of: dict = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            cls = value_class(node.value, {})
            if cls is not None:
                fn = ctx.enclosing_function(node)
                locals_of.setdefault(fn, {})[node.targets[0].id] = cls

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.BinOp):
            continue
        local = locals_of.get(ctx.enclosing_function(node), {})
        sides = {value_class(node.left, local),
                 value_class(node.right, local)}
        if sides == {"low", "f32"}:
            yield _finding(
                ctx, node, "D007",
                "bf16/f16 value mixed with an explicit f32 operand — the "
                "expression silently upcasts to f32",
                d007_dtype_promotion.hint)


# the clocks the obs stack standardized on (D005 owns the time.time()
# spelling); a delta of either around un-synced device work is the same
# dispatch-vs-execution trap, PLUS a hole in the span timeline
_D008_CLOCKS = frozenset(("time.monotonic", "time.perf_counter"))


def _calls_span(ctx: ModuleContext, func: ast.AST) -> bool:
    """Does this def open a span? Matches ``tracer.span(...)``,
    ``self._spans.span(...)``, and guard helpers like ``self._span(...)``
    — the final attribute segment, underscores stripped, is 'span'."""
    for node in ast.walk(func):
        if isinstance(node, ast.Call):
            t = ctx.call_target(node)
            if t is not None and t.rsplit(".", 1)[-1].lstrip("_") == "span":
                return True
    return False


def _calls_blocking_asarray(ctx: ModuleContext, func: ast.AST) -> bool:
    """np.asarray over a non-literal is a blocking transfer — the
    sanctioned sync D005's docstring blesses (host literals don't sync)."""
    for node in ast.walk(func):
        if (isinstance(node, ast.Call)
                and ctx.call_target(node) == "numpy.asarray" and node.args
                and not isinstance(node.args[0], _HOST_LITERALS)):
            return True
    return False


@rule("D008", "timed region wraps device work with neither a sync nor a span",
      "open a span (obs/spans.SpanTracer; the timeline then owns the "
      "region) or drain with block_until_ready / a blocking np.asarray "
      "— otherwise the interval "
      "measures dispatch and /debug/timeline has a hole",
      scope=("runtime/", "parallel/"))
def d008_span_hygiene(ctx: ModuleContext) -> Iterator[Finding]:
    """A ``time.monotonic()``/``time.perf_counter()`` delta in a function
    that dispatches jax work but never syncs (block_until_ready, a
    blocking np.asarray) and never opens a
    span. D005 catches the time.time() spelling of the dispatch trap;
    this rule covers the monotonic clocks AND enforces that timed device
    regions appear in the span timeline (ISSUE 5)."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not ctx.function_calls_device(node):
            continue
        if (ctx.function_calls(node, "block_until_ready")
                or _calls_span(ctx, node)
                or _calls_blocking_asarray(ctx, node)):
            continue
        t_names: set[str] = set()
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Assign)
                    and ctx.enclosing_function(sub) is node
                    and isinstance(sub.value, ast.Call)
                    and ctx.call_target(sub.value) in _D008_CLOCKS):
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        t_names.add(t.id)

        def is_clock_side(expr):
            if (isinstance(expr, ast.Call)
                    and ctx.call_target(expr) in _D008_CLOCKS):
                return True
            return isinstance(expr, ast.Name) and expr.id in t_names

        for sub in ast.walk(node):
            if not isinstance(sub, ast.BinOp) or not isinstance(sub.op,
                                                                ast.Sub):
                continue
            # deltas inside a nested def are that def's business (same
            # ownership rule as D005)
            if ctx.enclosing_function(sub) is not node:
                continue
            if is_clock_side(sub.left) or is_clock_side(sub.right):
                yield _finding(
                    ctx, sub, "D008",
                    "monotonic/perf_counter interval around device work "
                    "with no sync and no span",
                    d008_span_hygiene.hint)


RULES = (d001_implicit_sync, d002_retrace_trap, d003_jit_closure,
         d004_hot_loop_alloc, d005_bare_time, d006_unmodeled_collective,
         d007_dtype_promotion, d008_span_hygiene)
