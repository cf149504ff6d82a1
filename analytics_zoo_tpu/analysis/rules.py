"""zoolint's built-in rule set — the JAX/TPU failure modes this stack
actually has, one rule per class.  Each docstring states the *why* and
the runtime-diagnostics counterpart (docs/static-analysis.md renders
the full catalog):

=========  ==========================================================
JIT001     impure jitted/traced functions (side effects fire once at
           trace time, then silently never again)
SYNC002    implicit device→host syncs in train/step/predict loops
           (stalls the dispatch pipeline every iteration)
COMPILE003 recompile hazards (jit-in-loop, f-strings on traced
           values, shape-derived Python scalars as traced args) —
           the static twin of diagnostics.CompileMonitor's churn
           warnings
COMPILE011 direct jax.jit/pjit construction in analytics_zoo_tpu/
           outside the compile/ chokepoint — the program has no
           name and no warm() (use compile.engine_jit)
DONATE004  training steps that thread params/opt-state through jit
           without donate_argnums (double HBM for the update)
RACE005    module-level mutable state written without a lock in
           modules that run WorkerPool/MetricsServer/serving threads
RNG006     PRNG key consumed twice with no split/fold_in between
           (identical "random" numbers, silently)
=========  ==========================================================
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from analytics_zoo_tpu.analysis.core import (
    ModuleContext, Rule, _dotted, register_rule)

# --------------------------------------------------------------- helpers


def _is_none_guard(test: ast.AST, target: str) -> bool:
    """``target is None`` / ``target == None`` / ``not target`` — the
    guard test of the platform's lazy-init idiom (``target`` is the
    dotted form: a bare name or ``self.attr`` chain)."""
    if isinstance(test, ast.Compare) and len(test.ops) == 1 and \
            isinstance(test.ops[0], (ast.Is, ast.Eq)) and \
            isinstance(test.comparators[0], ast.Constant) and \
            test.comparators[0].value is None and \
            _dotted(test.left) == target:
        return True
    return isinstance(test, ast.UnaryOp) and \
        isinstance(test.op, ast.Not) and \
        _dotted(test.operand) == target


def _local_bindings(fn: ast.AST) -> Set[str]:
    """Names bound INSIDE ``fn`` (params + every assignment form), not
    descending into nested functions — the complement is the
    closed-over/global set JIT001 guards."""
    names: Set[str] = set()
    args = getattr(fn, "args", None)
    if args is not None:
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            names.add(a.arg)
        if args.vararg:
            names.add(args.vararg.arg)
        if args.kwarg:
            names.add(args.kwarg.arg)

    def collect_target(t: ast.AST) -> None:
        # only true BINDINGS: ``x = ...``/``x, y = ...`` bind names,
        # ``x[k] = ...``/``x.a = ...`` mutate an existing object and
        # must not shadow the closed-over name they mutate
        if isinstance(t, ast.Name):
            names.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for elt in t.elts:
                collect_target(elt)
        elif isinstance(t, ast.Starred):
            collect_target(t.value)

    body = fn.body if isinstance(fn.body, list) else [fn.body]
    stack: List[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                names.add(node.name)
            continue   # nested scope: its bindings are not ours
        if isinstance(node, ast.Assign):
            for t in node.targets:
                collect_target(t)
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            collect_target(node.target)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            collect_target(node.target)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            collect_target(node.optional_vars)
        elif isinstance(node, ast.comprehension):
            collect_target(node.target)
        elif isinstance(node, ast.NamedExpr):
            collect_target(node.target)
        elif isinstance(node, ast.ClassDef):
            names.add(node.name)
            continue
        stack.extend(ast.iter_child_nodes(node))
    return names


def _base_name(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an attribute/subscript chain (``a`` for
    ``a.b[0].c``)."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


_MUTATING_METHODS = {
    "append", "extend", "add", "update", "insert", "remove", "discard",
    "pop", "popitem", "clear", "setdefault", "appendleft", "extendleft",
    "sort", "reverse", "__setitem__",
}


# ================================================================ JIT001


@register_rule
class ImpureJitRule(Rule):
    """Side effects inside jit/trace-compiled functions.

    Why: a jitted function's Python body runs ONCE, at trace time.  A
    ``print``/``time.time``/``random.random`` call inside it fires
    during tracing and never again; mutation of closed-over or global
    state bakes the traced value in forever.  The program then runs
    wrong *silently* — there is no runtime error to catch, which is
    why this is an error-severity static check (the runtime
    counterpart, ``jax.debug.callback``, is the sanctioned escape
    hatch and is exempt).
    """

    rule_id = "JIT001"
    severity = "error"
    doc = ("side effect in a jit/trace-compiled function (fires once "
           "at trace time, never per step)")

    IMPURE_CALLS = {
        "print": "print() inside jit runs at trace time only — use "
                 "jax.debug.print",
        "input": "input() inside jit blocks tracing, never runs per "
                 "step",
        "breakpoint": "breakpoint() inside jit fires at trace time "
                      "only",
        "time.time": "host clock read inside jit is frozen at trace "
                     "time — time outside the jitted call",
        "time.perf_counter": "host clock read inside jit is frozen at "
                             "trace time — time outside the jitted "
                             "call",
        "time.monotonic": "host clock read inside jit is frozen at "
                          "trace time",
        "time.process_time": "host clock read inside jit is frozen at "
                             "trace time",
        "time.sleep": "time.sleep inside jit sleeps once at trace "
                      "time, never per step",
        "datetime.datetime.now": "host clock read inside jit is "
                                 "frozen at trace time",
        "os.urandom": "host entropy inside jit is drawn once at trace "
                      "time — use jax.random",
        "uuid.uuid4": "host entropy inside jit is drawn once at trace "
                      "time",
    }
    #: module prefixes whose every callable is host-RNG (frozen at
    #: trace time — jax.random is the in-jit mechanism)
    IMPURE_PREFIXES = ("random.", "numpy.random.")
    #: calls whose arguments are ALLOWED to do host work (the
    #: sanctioned side-channel out of a traced program)
    CALLBACK_HOSTS = {
        "jax.debug.print", "jax.debug.callback", "jax.pure_callback",
        "jax.experimental.io_callback",
    }

    def visit_Call(self, node: ast.Call, ctx: ModuleContext) -> None:
        fn = ctx.enclosing_function(node)
        if fn is None or id(fn) not in ctx.traced_functions:
            return
        if self._inside_callback(node, ctx):
            return
        name = ctx.resolve(node.func)
        if name is not None:
            if name in self.IMPURE_CALLS:
                self.report(node, self.IMPURE_CALLS[name])
                return
            for prefix in self.IMPURE_PREFIXES:
                if name.startswith(prefix):
                    self.report(
                        node,
                        f"host RNG '{name}' inside jit is drawn once "
                        f"at trace time — thread a jax.random key "
                        f"instead")
                    return
        # mutating method on closed-over/global state
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _MUTATING_METHODS:
            # ``.update`` is also optax's PURE GradientTransformation
            # method — ``tx.update(grads, state, params)``.  dict's
            # update takes ONE positional mapping; two or more args is
            # the optimizer signature, not a container mutation.
            if node.func.attr == "update" and len(node.args) >= 2:
                return
            base = _base_name(node.func.value)
            if base and base not in _local_bindings(fn) and \
                    base != "self":
                self.report(
                    node,
                    f"jitted function mutates closed-over/global "
                    f"'{base}' via .{node.func.attr}() — the "
                    f"mutation happens at trace time only")

    def visit_Global(self, node: ast.Global, ctx: ModuleContext) -> None:
        self._flag_scope_decl(node, ctx, "global")

    def visit_Nonlocal(self, node: ast.Nonlocal,
                       ctx: ModuleContext) -> None:
        self._flag_scope_decl(node, ctx, "nonlocal")

    def _flag_scope_decl(self, node: ast.AST, ctx: ModuleContext,
                         kind: str) -> None:
        fn = ctx.enclosing_function(node)
        if fn is None or id(fn) not in ctx.traced_functions:
            return
        # the lazy-init singleton idiom (``global X; if X is None:
        # X = ctor(); return X``) memoizes HOST state — calling such a
        # getter at trace time is the platform's config-read
        # convention, not a trace-time-only mutation of program state
        flagged = [n for n in node.names
                   if not self._memoized_only(fn, ctx, n)]
        if not flagged:
            return
        names = ", ".join(flagged)
        self.report(
            node,
            f"jitted function declares {kind} '{names}' — writes to "
            f"it happen at trace time only and are invisible to the "
            f"compiled program")

    @staticmethod
    def _memoized_only(fn: ast.AST, ctx: ModuleContext,
                       name: str) -> bool:
        """Every write to ``name`` inside ``fn`` sits under an
        ``if name is None:`` / ``if not name:`` guard (or there is no
        write at all)."""

        def guarded(node: ast.AST) -> bool:
            # the write must sit in the THEN branch of the guard —
            # an ``else:`` write runs exactly when the name is
            # already set, i.e. on every retrace
            prev: ast.AST = node
            cur = ctx.parent(node)
            while cur is not None and cur is not fn:
                if isinstance(cur, ast.If) and \
                        any(child is prev for child in cur.body) and \
                        _is_none_guard(cur.test, name):
                    return True
                prev = cur
                cur = ctx.parent(cur)
            return False

        for node in ast.walk(fn):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Name) and t.id == name and \
                        not guarded(node):
                    return False
        return True

    def visit_Assign(self, node: ast.Assign, ctx: ModuleContext) -> None:
        self._check_store(node, node.targets, ctx)

    def visit_AugAssign(self, node: ast.AugAssign,
                        ctx: ModuleContext) -> None:
        self._check_store(node, [node.target], ctx)

    def _check_store(self, node: ast.AST, targets: List[ast.AST],
                     ctx: ModuleContext) -> None:
        """Subscript/attribute stores into names not bound locally —
        in-place mutation of captured state under tracing."""
        fn = ctx.enclosing_function(node)
        if fn is None or id(fn) not in ctx.traced_functions:
            return
        local = _local_bindings(fn)
        for tgt in targets:
            if not isinstance(tgt, (ast.Subscript, ast.Attribute)):
                continue
            base = _base_name(tgt)
            if base and base not in local and base != "self":
                self.report(
                    node,
                    f"jitted function writes into closed-over/global "
                    f"'{base}' — the store happens at trace time "
                    f"only")

    def _inside_callback(self, node: ast.AST, ctx: ModuleContext) -> bool:
        cur: Optional[ast.AST] = ctx.parent(node)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if isinstance(cur, ast.Call) and \
                    ctx.resolve(cur.func) in self.CALLBACK_HOSTS:
                return True
            cur = ctx.parent(cur)
        return False


# =============================================================== SYNC002


@register_rule
class HostSyncRule(Rule):
    """Implicit device→host syncs inside hot loops.

    Why: ``float(loss)`` / ``.item()`` / ``np.asarray(out)`` on a
    device value blocks the host until the device catches up — inside
    a train/step/predict loop that serializes every iteration and
    empties the dispatch pipeline (the reason PR 1's step-latency
    histogram shows dispatch-to-dispatch time: steady-state training
    never waits).  The runtime twin is the ``train_step_time_seconds
    {device}`` attribution: a hot loop dominated by ``host_dispatch``
    usually hides one of these.  Flagged only for values that came out
    of a function call (device results); host scalars are exempt.
    """

    rule_id = "SYNC002"
    severity = "warning"
    doc = ("implicit device→host sync in a train/step/predict loop "
           "(serializes the dispatch pipeline)")

    SCALAR_CASTS = {"float", "int", "bool"}
    ARRAY_PULLS = {"numpy.asarray", "numpy.array", "jax.device_get"}
    #: calls whose results are host values — casting them is fine
    HOST_SOURCES = (
        "time.", "len", "range", "enumerate", "os.", "math.",
        "numpy.", "id", "sorted", "min", "max", "sum", "abs", "round",
        "str", "repr", "perf_counter", "get_config",
        "int", "float", "bool",
        # host metadata, not device arrays
        "jax.devices", "jax.local_devices", "jax.device_count",
        "jax.local_device_count", "jax.process_count",
        "jax.process_index",
    )
    #: method names whose results are host values regardless of the
    #: receiver (string/dict/env plumbing — the interprocedural
    #: hot-loop marks would otherwise taint every config parser)
    HOST_METHODS = {
        "strip", "lstrip", "rstrip", "split", "rsplit", "lower",
        "upper", "format", "join", "decode", "encode", "group",
        "get", "gethostname", "getvalue", "items", "keys", "values",
        "read", "readline",
        # NOT "copy": list/dict.copy() is host plumbing, but
        # jax.Array.copy() preserves device residency — classified by
        # the receiver below instead
    }

    def visit_Call(self, node: ast.Call, ctx: ModuleContext) -> None:
        fn = ctx.enclosing_function(node)
        if not ctx.is_hot_function(fn) or not ctx.in_loop(node):
            return
        # x.item() — the unambiguous device pull
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "item" and not node.args:
            self.report(
                node,
                ".item() in a hot loop blocks on the device every "
                "iteration — batch results and pull once outside the "
                "loop")
            return
        name = ctx.resolve(node.func)
        if name in self.SCALAR_CASTS and len(node.args) == 1 and \
                isinstance(node.args[0], ast.Name):
            argname = node.args[0].id
            if self._device_sourced(argname, fn):
                self.report(
                    node,
                    f"{name}({argname}) in a hot loop forces a "
                    f"device→host sync per iteration — accumulate on "
                    f"device (or sync once per epoch) instead")
        elif name in self.ARRAY_PULLS and node.args and \
                isinstance(node.args[0], ast.Name):
            argname = node.args[0].id
            if self._device_sourced(argname, fn):
                self.report(
                    node,
                    f"{name.split('.')[-1]}({argname}) in a hot loop "
                    f"copies device→host every iteration — keep the "
                    f"value on device or move the pull out of the "
                    f"loop")

    def visit_If(self, node: ast.If, ctx: ModuleContext) -> None:
        """Branching on a device value = an implicit sync too."""
        fn = ctx.enclosing_function(node)
        if not ctx.is_hot_function(fn) or not ctx.in_loop(node):
            return
        test = node.test
        if isinstance(test, ast.Name) and \
                self._device_sourced(test.id, fn, jit_only=True):
            self.report(
                node,
                f"branching on device value '{test.id}' in a hot loop "
                f"syncs every iteration — use jax.lax.cond inside the "
                f"step, or branch on a host-side counter")

    def _device_sourced(self, name: str, fn: ast.AST,
                        jit_only: bool = False,
                        _seen: Optional[Set[str]] = None) -> bool:
        """Was ``name`` assigned (anywhere in ``fn``) from a function
        call that plausibly returns device values?  Parameters and
        host-source calls don't count — precision over recall."""
        if _seen is None:
            _seen = set()
        if name in _seen:
            return False   # copy-chain cycle: stay conservative
        _seen.add(name)
        ctx = self._ctx
        assert ctx is not None
        # explicit source-order queue so nested defs/lambdas are
        # genuinely skipped (their locals are a different scope;
        # ast.walk would descend into them) and the FIRST assignment
        # in source order decides — an explicit host init like
        # ``loss = None`` keeps later device rebinds conservative
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        queue: List[ast.AST] = list(body)
        i = 0
        while i < len(queue):
            node = queue[i]
            i += 1
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            queue.extend(ast.iter_child_nodes(node))
            if not isinstance(node, ast.Assign):
                continue
            bound = False
            for tgt in node.targets:
                for sub in ast.walk(tgt):
                    if isinstance(sub, ast.Name) and sub.id == name:
                        bound = True
            if not bound:
                continue
            value = node.value
            if isinstance(value, ast.Await):
                value = value.value
            if not isinstance(value, ast.Call):
                return False   # literal / arithmetic — host
            if isinstance(value.func, ast.Attribute) and \
                    value.func.attr in self.HOST_METHODS:
                return False   # string/dict/env plumbing
            if isinstance(value.func, ast.Attribute) and \
                    value.func.attr == "copy":
                # device-ness passes through .copy(): a jax.Array
                # copy is still on device, a list/dict copy is host
                recv = value.func.value
                if isinstance(recv, ast.Name):
                    return self._device_sourced(recv.id, fn,
                                                jit_only=jit_only,
                                                _seen=_seen)
                return False   # non-name receiver: host default
            vname = ctx.resolve(value.func) or ""
            if jit_only:
                target = _dotted(value.func)
                if target in ctx.jitted_callables:
                    return True
                wrapped = ctx._wrapped_function(value.func, value)
                return wrapped is not None and \
                    id(wrapped) in ctx.traced_functions
            if any(vname == h or vname.startswith(h)
                   for h in self.HOST_SOURCES if h.endswith(".")) or \
                    vname in self.HOST_SOURCES:
                return False
            return True
        return False


# ============================================================ COMPILE003


@register_rule
class RecompileHazardRule(Rule):
    """Recompile churn, caught before the first run.

    Why: every novel (shape, dtype, static-arg value) combination
    seen by a jitted callable triggers a fresh XLA compile — seconds
    to minutes each.  ``diagnostics.CompileMonitor`` flags the churn
    at runtime *after you have paid for it*; this rule flags the three
    patterns that cause it in source: (1) ``jax.jit`` called inside a
    loop (a fresh cache per iteration), (2) f-strings/str() on traced
    values (forces concretization → trace error or silent constant),
    (3) shape-derived Python scalars (``len(x)``, ``x.shape[i]``)
    passed as *traced* args — the repo convention is a numpy scalar
    or ``static_argnums`` (see trainer.train_step_at).
    """

    rule_id = "COMPILE003"
    severity = "warning"
    doc = ("recompile hazard: jit-in-loop, traced-value "
           "stringification, or shape-derived scalar as a traced arg")

    def visit_Call(self, node: ast.Call, ctx: ModuleContext) -> None:
        name = ctx.resolve(node.func)
        if name in ctx.JIT_WRAPPERS:
            if ctx.in_loop(node) and not self._memoized(node, ctx):
                self.report(
                    node,
                    "jax.jit called inside a loop builds a fresh "
                    "compile cache entry per iteration — hoist the "
                    "jit out of the loop")
            return
        # str()/repr()/format() of a traced parameter inside jit
        fn = ctx.enclosing_function(node)
        if fn is not None and id(fn) in ctx.traced_functions and \
                name in ("str", "repr", "format") and node.args and \
                isinstance(node.args[0], ast.Name) and \
                node.args[0].id in self._params(fn):
            self.report(
                node,
                f"{name}() of traced value "
                f"'{node.args[0].id}' inside jit forces "
                f"concretization (trace error or baked-in constant)")
            return
        # shape-derived scalar passed as a traced arg to a known-jitted
        # callable (assignment- or decorator-defined) with no statics
        # declared
        target = _dotted(node.func)
        if target is None or target not in ctx.jitted_callables:
            return
        if any(kw.arg in ("static_argnums", "static_argnames")
               for kw in ctx.jitted_callables[target]):
            return
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if self._shape_derived(arg, ctx):
                self.report(
                    arg,
                    f"shape-derived Python scalar passed as a traced "
                    f"arg to jitted '{target}' — a new value retraces "
                    f"(pass a numpy scalar, or declare "
                    f"static_argnums)", line=node.lineno)

    def visit_JoinedStr(self, node: ast.JoinedStr,
                        ctx: ModuleContext) -> None:
        fn = ctx.enclosing_function(node)
        if fn is None or id(fn) not in ctx.traced_functions:
            return
        params = self._params(fn)
        for value in node.values:
            if isinstance(value, ast.FormattedValue) and \
                    isinstance(value.value, ast.Name) and \
                    value.value.id in params:
                self.report(
                    node,
                    f"f-string interpolates traced value "
                    f"'{value.value.id}' inside jit — forces "
                    f"concretization; use jax.debug.print for runtime "
                    f"values")
                return

    @staticmethod
    def _memoized(node: ast.Call, ctx: ModuleContext) -> bool:
        """A jit built under an ``if self._step is None: self._step =
        jax.jit(...)`` guard compiles ONCE no matter how hot the
        enclosing code is — the platform's own lazy-build idiom."""
        target: Optional[str] = None
        cur = ctx.parent(node)
        while isinstance(cur, ast.Call):   # monitor.wrap(jax.jit(..))
            cur = ctx.parent(cur)
        if isinstance(cur, ast.Assign) and len(cur.targets) == 1:
            target = _dotted(cur.targets[0])
        if target is None:
            return False
        prev: ast.AST = cur
        guard = ctx.parent(cur)
        while guard is not None and not isinstance(
                guard, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # only the THEN branch of the None-check memoizes; a build
            # in the ``else:`` runs on every pass through the guard
            if isinstance(guard, ast.If) and \
                    any(child is prev for child in guard.body) and \
                    _is_none_guard(guard.test, target):
                return True
            prev = guard
            guard = ctx.parent(guard)
        return False

    @staticmethod
    def _params(fn: ast.AST) -> Set[str]:
        args = getattr(fn, "args", None)
        if args is None:
            return set()
        out = {a.arg for a in
               (args.posonlyargs + args.args + args.kwonlyargs)}
        out.discard("self")
        return out

    @staticmethod
    def _shape_derived(node: ast.AST, ctx: ModuleContext) -> bool:
        if isinstance(node, ast.Call) and \
                ctx.resolve(node.func) == "len":
            return True
        if isinstance(node, ast.Attribute) and node.attr == "shape":
            return True
        if isinstance(node, ast.Subscript):
            v = node.value
            return isinstance(v, ast.Attribute) and v.attr == "shape"
        return False


# ============================================================ COMPILE011


@register_rule
class EngineChokepointRule(Rule):
    """Every engine-built jit must go through the ``compile/``
    chokepoint.

    Why: ``analytics_zoo_tpu.compile.engine_jit`` is the one place a
    compiled program is built: it gives the program its name
    (``key_hint``) and ``warm()``, the compile ahead of the first
    request or step.  A direct ``jax.jit``/``pjit`` construction has
    neither, and a change to how programs are built (a compiler
    option, a name in the profile) would miss it.  Scoped to
    ``analytics_zoo_tpu/`` (examples/tests/scripts are free to jit
    directly); ``compile/`` itself is the one place allowed to touch
    the raw wrappers.
    """

    rule_id = "COMPILE011"
    severity = "error"
    doc = ("direct jax.jit/pjit construction outside the compile/ "
           "chokepoint — the program has no name and no warm() "
           "(use engine_jit)")

    SCOPE = "analytics_zoo_tpu/"
    EXEMPT = ("analytics_zoo_tpu/compile/",)

    def _in_scope(self, ctx: ModuleContext) -> bool:
        rp = ctx.relpath
        return rp.startswith(self.SCOPE) and \
            not any(rp.startswith(e) for e in self.EXEMPT)

    def _flag(self, node: ast.AST, name: str) -> None:
        self.report(
            node,
            f"direct {name}(...) bypasses the engine_jit chokepoint — "
            f"this program gets no name and no warm(); build "
            f"it with analytics_zoo_tpu.compile.engine_jit (same "
            f"static_argnums/donate_argnums/shardings semantics)")

    def visit_Call(self, node: ast.Call, ctx: ModuleContext) -> None:
        if not self._in_scope(ctx):
            return
        name = ctx.resolve(node.func)
        if name in ctx.RAW_JIT_WRAPPERS:
            self._flag(node, name)

    def visit_FunctionDef(self, node: ast.FunctionDef,
                          ctx: ModuleContext) -> None:
        """Decorator forms visit_Call cannot see: bare ``@jax.jit``
        (an Attribute, not a Call) and ``@partial(jax.jit, ...)``
        (jax.jit is an argument, not the callee).  The ``@jax.jit(..)``
        call form is already a Call and reports there."""
        if not self._in_scope(ctx):
            return
        for dec in node.decorator_list:
            dname = ctx.resolve(dec)
            if dname in ctx.RAW_JIT_WRAPPERS:
                self._flag(dec, dname)
            elif isinstance(dec, ast.Call):
                fname = ctx.resolve(dec.func)
                if fname in ("functools.partial", "partial") and \
                        dec.args and \
                        ctx.resolve(dec.args[0]) in ctx.RAW_JIT_WRAPPERS:
                    self._flag(dec, ctx.resolve(dec.args[0]))


# ============================================================= DONATE004


@register_rule
class DonateRule(Rule):
    """Training steps must donate their state buffers.

    Why: a train step maps (params, opt_state, ...) -> (params,
    opt_state, ...).  Without ``donate_argnums`` XLA must keep the
    input AND output trees live simultaneously — double the HBM for
    the largest arrays in the program, which halves the largest model
    that fits.  Detected on the jit callsite of any function that
    threads an optimizer-state parameter through; eval/predict steps
    (no opt state) are exempt by construction.
    """

    rule_id = "DONATE004"
    severity = "warning"
    doc = ("train-step jit without donate_argnums doubles param/"
           "opt-state HBM")

    STATE_PARAMS = {"opt_state", "optimizer_state", "opt_states"}

    def visit_Call(self, node: ast.Call, ctx: ModuleContext) -> None:
        if ctx.resolve(node.func) not in ctx.JIT_WRAPPERS or \
                not node.args:
            return
        if any(kw.arg in ("donate_argnums", "donate_argnames")
               for kw in node.keywords):
            return
        self._check_step(node, ctx._wrapped_function(node.args[0], node))

    def visit_FunctionDef(self, node: ast.FunctionDef,
                          ctx: ModuleContext) -> None:
        """The decorator forms: bare ``@jax.jit`` (no kwargs possible
        → can never donate) and ``@partial(jax.jit, ...)`` (donation
        kwargs live on the partial call)."""
        for dec in node.decorator_list:
            if ctx.resolve(dec) in ctx.JIT_WRAPPERS:
                self._check_step(dec, node)
            elif isinstance(dec, ast.Call):
                fname = ctx.resolve(dec.func)
                # @jax.jit(...) call form, and @partial(jax.jit, ...):
                # in both, donation kwargs live on the call
                is_jit = fname in ctx.JIT_WRAPPERS or (
                    fname in ("functools.partial", "partial")
                    and dec.args
                    and ctx.resolve(dec.args[0]) in ctx.JIT_WRAPPERS)
                if is_jit and not any(
                        kw.arg in ("donate_argnums", "donate_argnames")
                        for kw in dec.keywords):
                    self._check_step(dec, node)

    def _check_step(self, site: ast.AST, fn) -> None:
        args = getattr(fn, "args", None)
        if args is None:
            return
        names = {a.arg for a in
                 (args.posonlyargs + args.args + args.kwonlyargs)}
        hit = names & self.STATE_PARAMS
        if hit:
            self.report(
                site,
                f"jitted step threads '{sorted(hit)[0]}' through "
                f"without donate_argnums — input and output state "
                f"trees stay live together (double HBM for the "
                f"biggest arrays)")


# =============================================================== RACE005


@register_rule
class SharedStateRule(Rule):
    """Unlocked module-level mutable state in thread-running modules.

    Why: ``data.stages.WorkerPool`` threads, ``PrefetchIterator``
    daemons, the ``MetricsServer`` scrape thread and serving's decode
    pool all execute library code concurrently with the main thread.
    A module-level dict/list mutated without a lock from code those
    threads reach is a data race: CPython makes *some* single ops
    atomic, but read-modify-write sequences (``d[k] = d.get(k) + 1``,
    check-then-set) interleave and corrupt silently.  Scoped to
    modules that demonstrably run threads (imports threading /
    concurrent.futures or instantiates the platform's pool/server
    classes) so pure single-threaded registries don't false-positive.
    """

    rule_id = "RACE005"
    severity = "error"
    doc = ("module-level mutable state mutated without a lock in a "
           "thread-running module")

    def check_module(self, ctx: ModuleContext) -> None:
        if not ctx.threaded:
            return
        shared = set(ctx.module_mutables)
        # names rebound via ``global X`` anywhere also count as shared
        # (the None-then-lazy-init singleton pattern)
        global_decls: Set[str] = set()
        for node in ctx.all_nodes:
            if isinstance(node, ast.Global):
                global_decls.update(node.names)
        shared |= global_decls
        if not shared:
            return
        reads = self._read_counts(ctx, shared)
        for node in ctx.all_nodes:
            fn = ctx.enclosing_function(node)
            if fn is None:
                continue   # module-level init runs before threads start
            name, how = self._mutation_of(node, ctx, shared)
            if name is None:
                continue
            # a name only ever touched in one place isn't shared state
            if reads.get(name, 0) < 2:
                continue
            if self._under_lock(node, ctx):
                continue
            self.report(
                node,
                f"module-level mutable '{name}' {how} without holding "
                f"a lock, in a module that runs threads "
                f"({ctx.thread_evidence}) — wrap the access in a "
                f"threading.Lock")

    def _mutation_of(self, node: ast.AST, ctx: ModuleContext,
                     shared: Set[str]) -> Tuple[Optional[str], str]:
        fn = ctx.enclosing_function(node)
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, (ast.Subscript, ast.Attribute)):
                    base = _base_name(tgt)
                    if base in shared and \
                            self._names_module_global(fn, base):
                        return base, "written into"
                elif isinstance(tgt, ast.Name) and tgt.id in shared \
                        and self._declared_global(fn, tgt.id):
                    return tgt.id, "rebound"
        elif isinstance(node, ast.AugAssign):
            base = _base_name(node.target)
            if base in shared:
                if isinstance(node.target, ast.Name):
                    if not self._declared_global(fn, base):
                        return None, ""
                elif not self._names_module_global(fn, base):
                    return None, ""   # local shadow
                return base, "updated in place"
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _MUTATING_METHODS:
            base = _base_name(node.func.value)
            if base in shared and \
                    self._names_module_global(fn, base):
                return base, f"mutated via .{node.func.attr}()"
        elif isinstance(node, ast.Delete):
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript):
                    base = _base_name(tgt)
                    if base in shared and \
                            self._names_module_global(fn, base):
                        return base, "deleted from"
        return None, ""

    def _names_module_global(self, fn: Optional[ast.AST],
                             name: str) -> bool:
        """Does ``name`` inside ``fn`` refer to the MODULE global?  A
        local binding of the same name shadows it (not a shared-state
        mutation) — unless the function says ``global name``."""
        if fn is None:
            return True
        if self._declared_global(fn, name):
            return True
        return name not in _local_bindings(fn)

    @staticmethod
    def _declared_global(fn: Optional[ast.AST], name: str) -> bool:
        if fn is None:
            return False
        for node in ast.walk(fn):
            if isinstance(node, ast.Global) and name in node.names:
                return True
        return False

    def _read_counts(self, ctx: ModuleContext,
                     shared: Set[str]) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for node in ctx.all_nodes:
            if isinstance(node, ast.Name) and node.id in shared:
                counts[node.id] = counts.get(node.id, 0) + 1
        return counts

    @staticmethod
    def _under_lock(node: ast.AST, ctx: ModuleContext) -> bool:
        """Any enclosing ``with X:`` where X (or its call target)
        names something lock-ish — the pragmatic guard test."""
        cur = ctx.parent(node)
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
            if isinstance(cur, ast.With):
                for item in cur.items:
                    expr = item.context_expr
                    if isinstance(expr, ast.Call):
                        expr = expr.func
                    name = (_dotted(expr) or "").lower()
                    if "lock" in name or "mutex" in name or \
                            "guard" in name:
                        return True
            cur = ctx.parent(cur)
        return False


# ================================================================ RNG006


@register_rule
class KeyReuseRule(Rule):
    """A PRNG key consumed by two primitives with no split between.

    Why: jax PRNG keys are VALUES, not stateful generators — two
    ``jax.random.normal(key, ...)`` calls with the same key return the
    *identical* numbers.  Dropout masks equal across layers,
    initializations correlated, augmentation repeated: all silent.
    Consumption = passing the key to a sampling primitive or as an
    ``rng=``/``key=`` kwarg; ``split``/``fold_in``/``PRNGKey`` are
    derivations, and rebinding the name re-arms it.  Loop bodies are
    evaluated twice so a consume-in-loop with no rebind inside the
    loop is caught (the second iteration reuses the key).
    """

    rule_id = "RNG006"
    severity = "error"
    doc = ("PRNG key consumed twice without split/fold_in — "
           "identical random numbers, silently")

    DERIVE = {"split", "fold_in", "PRNGKey", "key", "clone",
              "key_data", "wrap_key_data"}

    def check_module(self, ctx: ModuleContext) -> None:
        for fn in ctx.functions:
            if isinstance(fn, ast.Lambda):
                continue
            self._check_function(fn, ctx)

    # -- per-function linear scan with branch-aware merge ---------------
    def _check_function(self, fn: ast.AST, ctx: ModuleContext) -> None:
        consumed: Dict[str, ast.AST] = {}   # key name -> first consumer
        reported: Set[Tuple[int, int]] = set()
        self._scan(fn.body, consumed, reported, ctx, fn)

    def _scan(self, stmts: List[ast.stmt], consumed: Dict[str, ast.AST],
              reported: Set[Tuple[int, int]], ctx: ModuleContext,
              fn: ast.AST,
              break_sink: Optional[Dict[str, ast.AST]] = None) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue   # nested scopes get their own pass
            if isinstance(stmt, ast.Break):
                # a break path leaves the loop BODY but still reaches
                # the code after the loop — its consumptions flow to
                # the enclosing loop's post-loop state, not the rest
                # of the body
                if break_sink is not None:
                    for k, v in consumed.items():
                        break_sink.setdefault(k, v)
                continue
            if isinstance(stmt, ast.If):
                # the test expression evaluates first, on every path
                self._apply_expr(stmt.test, consumed, reported, ctx)
                # each branch starts from the current state; afterwards
                # a key consumed in EITHER branch counts as consumed
                # (max-merge: one use per executed path is fine) — but
                # a branch that TERMINATES (return/raise/break/
                # continue) never reaches the code after the If, so
                # its consumptions must not poison the fall-through
                # state (``if small: return normal(rng); ...use rng``)
                before = dict(consumed)
                body_state = dict(before)
                self._scan(stmt.body, body_state, reported, ctx, fn,
                           break_sink)
                else_state = dict(before)
                self._scan(stmt.orelse, else_state, reported, ctx, fn,
                           break_sink)
                body_term = self._terminates(stmt.body)
                else_term = self._terminates(stmt.orelse)
                consumed.clear()
                if body_term and not else_term:
                    consumed.update(else_state)
                elif else_term and not body_term:
                    consumed.update(body_state)
                elif body_term and else_term:
                    consumed.update(before)   # code after is dead-ish
                else:
                    consumed.update(body_state)
                    for k, v in else_state.items():
                        consumed.setdefault(k, v)
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                # iterable evaluates ONCE, before the loop
                self._apply_expr(stmt.iter, consumed, reported, ctx)
                # two passes ≈ two iterations: a consume with no rebind
                # inside the loop body reuses the key on iteration 2;
                # the loop target rebinds fresh per iteration.  Breaks
                # inside the body collect in THIS loop's sink and
                # merge into the post-loop state below.
                sink: Dict[str, ast.AST] = {}
                for _ in range(2):
                    for name in self._bound_names(stmt.target):
                        consumed.pop(name, None)
                    self._scan(stmt.body, consumed, reported, ctx, fn,
                               sink)
                self._scan(stmt.orelse, consumed, reported, ctx, fn,
                           break_sink)
                for k, v in sink.items():
                    consumed.setdefault(k, v)
                continue
            if isinstance(stmt, ast.While):
                sink = {}
                for _ in range(2):   # test re-evaluates per iteration
                    self._apply_expr(stmt.test, consumed, reported, ctx)
                    self._scan(stmt.body, consumed, reported, ctx, fn,
                               sink)
                self._scan(stmt.orelse, consumed, reported, ctx, fn,
                           break_sink)
                for k, v in sink.items():
                    consumed.setdefault(k, v)
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._apply_expr(item.context_expr, consumed,
                                     reported, ctx)
                    if item.optional_vars is not None:
                        for name in self._bound_names(
                                item.optional_vars):
                            consumed.pop(name, None)
                self._scan(stmt.body, consumed, reported, ctx, fn,
                           break_sink)
                continue
            if isinstance(stmt, ast.Try):
                self._scan(stmt.body, consumed, reported, ctx, fn,
                           break_sink)
                for h in stmt.handlers:
                    self._scan(h.body, consumed, reported, ctx, fn,
                               break_sink)
                self._scan(stmt.orelse, consumed, reported, ctx, fn,
                           break_sink)
                self._scan(stmt.finalbody, consumed, reported, ctx, fn,
                           break_sink)
                continue
            # expression statement / assignment: consumptions first,
            # then rebinds (RHS evaluates before the LHS binds)
            self._apply_expr(stmt, consumed, reported, ctx)
            for name in self._rebinds(stmt):
                consumed.pop(name, None)

    def _apply_expr(self, node: ast.AST, consumed: Dict[str, ast.AST],
                    reported: Set[Tuple[int, int]],
                    ctx: ModuleContext) -> None:
        """Record/flag the key consumptions inside one expression or
        simple statement."""
        for name, site in self._consumptions(node, ctx):
            if name in consumed:
                pos = (site.lineno, site.col_offset)
                if pos not in reported:
                    reported.add(pos)
                    first = consumed[name]
                    self.report(
                        site,
                        f"PRNG key '{name}' already consumed at "
                        f"line {first.lineno} — split it "
                        f"(jax.random.split) or fold_in a counter "
                        f"before reusing")
            else:
                consumed[name] = site

    @classmethod
    def _terminates(cls, stmts: List[ast.stmt]) -> bool:
        """Does this branch body end on a statement whose path never
        reaches the code AFTER the enclosing If?  Return/raise leave
        the function; break leaves the loop body (its consumptions
        still reach post-loop code — the break sink carries them
        there, they just must not poison the rest of the body).
        A trailing If BOTH of whose arms terminate is itself a
        terminator (``if ...: raise A else: raise B``).
        ``continue`` is NOT a terminator: it re-enters the loop
        header, so a key consumed before a ``continue`` is consumed
        again on the next matching iteration (the two-pass loop scan
        needs the state to survive the merge to see it)."""
        if not stmts:
            return False
        last = stmts[-1]
        if isinstance(last, (ast.Return, ast.Raise, ast.Break)):
            return True
        return isinstance(last, ast.If) and \
            cls._terminates(last.body) and cls._terminates(last.orelse)

    @staticmethod
    def _bound_names(target: ast.AST) -> Set[str]:
        names: Set[str] = set()

        def bind(t: ast.AST) -> None:
            if isinstance(t, ast.Name):
                names.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for elt in t.elts:
                    bind(elt)
            elif isinstance(t, ast.Starred):
                bind(t.value)

        bind(target)
        return names

    def _consumptions(self, stmt: ast.stmt, ctx: ModuleContext
                      ) -> List[Tuple[str, ast.AST]]:
        out: List[Tuple[str, ast.AST]] = []
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            seen_here: Set[str] = set()
            name = ctx.resolve(node.func)
            if name and name.startswith("jax.random."):
                prim = name.rsplit(".", 1)[1]
                if prim in self.DERIVE:
                    continue
                if node.args and isinstance(node.args[0], ast.Name):
                    out.append((node.args[0].id, node))
                    seen_here.add(node.args[0].id)
            else:
                # rng= is the platform's key-threading kwarg
                # (model.apply(..., rng=k)); ``key=`` is NOT counted —
                # it names dict/sort/protobuf keys far more often than
                # PRNG keys
                for kw in node.keywords:
                    if kw.arg == "rng" and \
                            isinstance(kw.value, ast.Name):
                        out.append((kw.value.id, node))
                        seen_here.add(kw.value.id)
            # interprocedural: the project layer resolved this call to
            # a function that consumes a key parameter — the argument
            # passed there is consumed HERE (one entry per name)
            for nm in ctx.rng_call_consumes.get(
                    (node.lineno, node.col_offset), ()):
                if nm not in seen_here:
                    out.append((nm, node))
                    seen_here.add(nm)
        return out

    @classmethod
    def _rebinds(cls, stmt: ast.stmt) -> Set[str]:
        """Names genuinely REBOUND by ``stmt``.  Only binding targets
        count — ``out[rng] = v`` or ``obj.rng = v`` must not re-arm
        ``rng`` (a subscript index / attribute base is a *read*)."""
        names: Set[str] = set()
        if isinstance(stmt, ast.Assign):
            for tgt in stmt.targets:
                names |= cls._bound_names(tgt)
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            names |= cls._bound_names(stmt.target)
        return names
