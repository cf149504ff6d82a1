"""zoolint v3 rule families — the flow-sensitive typestate checks.

Built on :mod:`analysis.cfg` (statement-level CFG with exception
edges + forward fixpoint engine), fed by the PR 7 project facts
(``ctx.jitted_callables`` carries literal ``donate_argnums`` positions
across module boundaries).  Catalog (docs/static-analysis.md renders
the full entries with their runtime twins):

=========  ==========================================================
DONATE012  use-after-donate: a value passed in a donated position of
           an ``engine_jit``/jit call is read again on some later
           path — a runtime error on TPU, a silent no-op on the CPU
           tier-1 runs (rebinding re-arms; ``.warm`` never executes
           and is exempt)
ACK013     stream-record obligations in ``serving/``: every consumed
           record must be discharged exactly once per ownership path
           (ack / ``dead_letter`` / quarantine / serve / a re-raise
           that reaches the loop boundary — double-discharge and
           leak both fire), and every locally-created
           ``engine.Request`` must reach ``complete``/``fail`` (or
           escape to the engine) on all paths — a leaked Request is
           a client blocked until transport timeout
RES015     exception-path resource release: acquisitions with a
           release obligation — breaker half-open probe slots
           (``allow()`` → ``record_success``/``record_failure``),
           manually ``.acquire()``d locks/semaphores, spawned
           processes and non-daemon threads — not discharged on
           every outgoing edge, exception edges INCLUDED
           (generalizing LOCK010's ``with``-only view)
=========  ==========================================================

All three are ``check_module`` rules: they pre-filter cheaply (no
donating callables / not under ``serving/`` / no acquire-ish call in
the source → no CFG is ever built), so the full-repo gate stays
within the PR 7 wall-time envelope.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

from analytics_zoo_tpu.analysis.cfg import (
    EXC, FALSE, NEXT, TRUE, CFG, CFGNode, State, build_cfg,
    run_forward)
from analytics_zoo_tpu.analysis.core import (
    ModuleContext, Rule, _dotted, donated_positions, register_rule)

#: abstract obligation facts
OWNED = "owned"
DONE = "done"
ESCAPED = "escaped"


# ---------------------------------------------------------------- helpers


def _pos(node: ast.AST) -> Tuple[int, int]:
    return (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))


def _end_pos(node: ast.AST) -> Tuple[int, int]:
    return (getattr(node, "end_lineno", getattr(node, "lineno", 0)),
            getattr(node, "end_col_offset",
                    getattr(node, "col_offset", 0)))


def _walk_evaluated(roots: Sequence[ast.AST]):
    """Walk the expression roots, PRUNING nested function/class
    bodies: a ``def``/``lambda`` inside a statement is *defined*
    there, not run — scanning its body at the definition site would
    poison/read/discharge state for code that executes later, if
    ever (the same asymmetry ``cfg._stmt_can_raise`` keeps).
    Decorators (and lambda argument defaults) DO evaluate at the
    definition and stay in the walk."""
    stack = list(roots)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            stack.extend(node.decorator_list)
            if isinstance(node, ast.ClassDef):
                stack.extend(node.bases)
                stack.extend(kw.value for kw in node.keywords)
            else:
                stack.extend(node.args.defaults)
                stack.extend(d for d in node.args.kw_defaults if d)
            continue
        if isinstance(node, ast.Lambda):
            stack.extend(node.args.defaults)
            stack.extend(d for d in node.args.kw_defaults if d)
            continue
        stack.extend(ast.iter_child_nodes(node))


def _dotted_loads(exprs: Sequence[ast.AST]
                  ) -> List[Tuple[str, ast.AST]]:
    """Every dotted Load read in the expression roots — full chains
    AND their prefixes (reading ``self._tokens.shape`` reads
    ``self._tokens``), each with its ast node for positions."""
    out: List[Tuple[str, ast.AST]] = []
    for sub in _walk_evaluated(exprs):
        if isinstance(sub, (ast.Name, ast.Attribute)) and \
                isinstance(getattr(sub, "ctx", None), ast.Load):
            d = _dotted(sub)
            if d:
                out.append((d, sub))
    return out


def _bind_names(t: ast.AST, names: Set[str]) -> None:
    """Collect the dotted names a binding TARGET binds — plain names
    and attribute chains (``self._tokens``) both re-arm; subscript
    stores mutate, they don't rebind."""
    if isinstance(t, (ast.Name, ast.Attribute)):
        d = _dotted(t)
        if d:
            names.add(d)
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            _bind_names(e, names)
    elif isinstance(t, ast.Starred):
        _bind_names(t.value, names)


def _binding_targets(stmt: ast.AST) -> Set[str]:
    names: Set[str] = set()
    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            _bind_names(t, names)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        _bind_names(stmt.target, names)
    elif isinstance(stmt, ast.NamedExpr):
        _bind_names(stmt.target, names)
    return names


def _loop_targets(stmt: ast.AST) -> Set[str]:
    names: Set[str] = set()
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        _bind_names(stmt.target, names)
    return names


def _method_call(exprs: Sequence[ast.AST], var: str,
                 attrs: Set[str]) -> Optional[ast.Call]:
    """The first ``var.attr(...)`` call in the expressions with
    ``attr`` in ``attrs`` (receiver must be the bare Name)."""
    for call in _calls_in(exprs):
        f = call.func
        if isinstance(f, ast.Attribute) and f.attr in attrs and \
                isinstance(f.value, ast.Name) and f.value.id == var:
            return call
    return None


def _calls_in(exprs: Sequence[ast.AST]) -> List[ast.Call]:
    return [sub for sub in _walk_evaluated(exprs)
            if isinstance(sub, ast.Call)]


def _contains_name(expr: ast.AST, name: str) -> bool:
    return any(isinstance(sub, ast.Name) and sub.id == name
               for sub in _walk_evaluated([expr]))


def _cfg_for(ctx: ModuleContext, fn: ast.AST) -> CFG:
    """One CFG per function per module run, shared by the three
    rules (cached on the context)."""
    cache = getattr(ctx, "_zoolint_cfgs", None)
    if cache is None:
        cache = {}
        ctx._zoolint_cfgs = cache
    cfg = cache.get(id(fn))
    if cfg is None:
        cfg = build_cfg(fn)
        cache[id(fn)] = cfg
    return cfg


def _functions(ctx: ModuleContext) -> List[ast.AST]:
    return [fn for fn in ctx.functions
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))]


def _closure_reads(ctx: ModuleContext, fn: ast.AST,
                   names: Set[str]) -> Set[str]:
    """Which of ``names`` are read inside a scope nested in ``fn``
    (a lambda/def closing over them) — those escape tracking."""
    if not names:
        return set()
    out: Set[str] = set()
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Name) and sub.id in names and \
                ctx.enclosing_function(sub) is not fn:
            out.add(sub.id)
    return out


def _truthy_edges(test: ast.AST) -> Optional[Tuple[str, str]]:
    """(edge-when-call-truthy, edge-when-falsy) when the If test is a
    bare ``call(...)`` / ``not call(...)``; None for anything else."""
    if isinstance(test, ast.Call):
        return (TRUE, FALSE)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not) \
            and isinstance(test.operand, ast.Call):
        return (FALSE, TRUE)
    return None


def _bare_test_call(test: ast.AST) -> Optional[ast.Call]:
    if isinstance(test, ast.Call):
        return test
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not) \
            and isinstance(test.operand, ast.Call):
        return test.operand
    return None


def _escapes(ctx: ModuleContext, exprs: Sequence[ast.AST],
             var: str) -> bool:
    """Does ``var`` escape in these expressions — passed as a call
    argument, returned/yielded, or stored into an attribute/subscript
    target?  Receiver-position uses (``var.fail()``, ``var.done``)
    are not escapes."""
    for root in exprs:
        for sub in _walk_evaluated([root]):
            if not (isinstance(sub, ast.Name) and sub.id == var
                    and isinstance(sub.ctx, ast.Load)):
                continue
            cur: Optional[ast.AST] = sub
            parent = ctx.parent(cur)
            while isinstance(parent, ast.Attribute):
                cur, parent = parent, ctx.parent(parent)
            while parent is not None:
                if isinstance(parent, ast.Call):
                    if cur is not parent.func:
                        return True
                    cur, parent = parent, ctx.parent(parent)
                    continue
                if isinstance(parent, (ast.Return, ast.Yield,
                                       ast.YieldFrom)):
                    return True
                if isinstance(parent, ast.Assign) and \
                        cur is parent.value and any(
                            isinstance(t, (ast.Attribute,
                                           ast.Subscript))
                            for t in parent.targets):
                    return True
                if isinstance(parent, (ast.FunctionDef,
                                       ast.AsyncFunctionDef,
                                       ast.Lambda)) or \
                        isinstance(parent, ast.stmt):
                    break
                cur, parent = parent, ctx.parent(parent)
    return False


# ================================================================ DONATE012


@register_rule
class UseAfterDonateRule(Rule):
    """Reading a buffer after it was donated to a jit call.

    Why: ``donate_argnums`` hands the argument's buffer to XLA — on
    TPU the input array is *gone* the moment the call dispatches, and
    touching it afterwards is a runtime error.  On CPU donation is a
    no-op, so the tier-1 suite can never fail on this: the single
    worst TPU-native bug class is invisible to every test this repo
    can run.  Flow-sensitive: flagged when a donated value is read on
    SOME later path (exception edges included — a donating call that
    raises may already have consumed its buffers, which is why
    ``DecodeSlotPool`` rebuilds state in its handlers).  Rebinding
    re-arms the name (``params, opt = step(params, opt)`` is the
    sanctioned pattern); ``.warm(...)`` compiles without executing
    and never donates.
    """

    rule_id = "DONATE012"
    severity = "error"
    doc = ("use-after-donate: a buffer passed in a donated position "
           "of a jit call is read again on some path (silent on CPU, "
           "fatal on TPU)")

    def check_module(self, ctx: ModuleContext) -> None:
        donating: Dict[str, Set[int]] = {}
        for name, kws in ctx.jitted_callables.items():
            pos = donated_positions(kws)
            if pos:   # None (unmappable) and {} (no donation) exempt
                donating[name] = pos
        if not donating:
            return
        for fn in _functions(ctx):
            if any(_dotted(c.func) in donating
                   for c in ast.walk(fn) if isinstance(c, ast.Call)):
                self._check_function(ctx, fn, donating)

    # ------------------------------------------------------------ per-fn
    def _donate_events(self, node: CFGNode, donating: Dict[str, Set[int]]
                       ) -> List[Tuple[Tuple[int, int], ast.Call, str,
                                       List[str]]]:
        """(completion pos, call, callee, donated arg names) for every
        donating call in this node — completion position is the END
        of the call: its arguments are read before the buffers are
        consumed."""
        out = []
        for call in _calls_in(node.exprs):
            target = _dotted(call.func)
            pos = donating.get(target or "")
            if not pos:
                continue
            names = []
            for i, arg in enumerate(call.args):
                if i in pos:
                    d = _dotted(arg)
                    if d:
                        names.append(d)
            if names:
                out.append((_end_pos(call), call, target, names))
        return out

    def _check_function(self, ctx: ModuleContext, fn: ast.AST,
                        donating: Dict[str, Set[int]]) -> None:
        cfg = _cfg_for(ctx, fn)

        def transfer(node: CFGNode, state: State
                     ) -> Dict[Optional[str], State]:
            events = self._donate_events(node, donating)
            if not events and node.kind not in ("stmt", "for", "with"):
                return {None: state}
            poisoned = dict(state)
            for _p, call, target, names in events:
                for name in names:
                    poisoned[name] = poisoned.get(
                        name, frozenset()) | {(call.lineno, target)}
            out: Dict[Optional[str], State] = {EXC: poisoned}
            bound: Set[str] = set()
            if node.kind == "stmt" and node.stmt is not None:
                bound = _binding_targets(node.stmt)
            elif node.kind == "with" and node.stmt is not None:
                for item in node.stmt.items:
                    if item.optional_vars is not None:
                        bound |= _binding_targets(
                            ast.Assign(targets=[item.optional_vars],
                                       value=ast.Constant(value=None)))
            rebound = {k: v for k, v in poisoned.items()
                       if k not in bound}
            out[None] = rebound
            if node.kind == "for" and node.stmt is not None:
                loop_bound = _loop_targets(node.stmt)
                out[TRUE] = {k: v for k, v in poisoned.items()
                             if k not in loop_bound}
            return out

        in_states = run_forward(cfg, {}, transfer)

        reported: Set[Tuple[int, int, str]] = set()
        for node in cfg.nodes:
            state = in_states.get(node.idx)
            if state is None or not node.exprs:
                continue
            events = self._donate_events(node, donating)
            marks = sorted((pos, target, name)
                           for pos, _c, target, names in events
                           for name in names)
            working = dict(state)
            reads = sorted(((_pos(n), d, n)
                            for d, n in _dotted_loads(node.exprs)),
                           key=lambda t: t[0])
            mi = 0
            for rpos, dname, rnode in reads:
                while mi < len(marks) and marks[mi][0] <= rpos:
                    _p, target, name = marks[mi]
                    working[name] = working.get(
                        name, frozenset()) | {(node.line, target)}
                    mi += 1
                hits = working.get(dname)
                if not hits:
                    continue
                key = (rnode.lineno, rnode.col_offset, dname)
                if key in reported:
                    continue
                reported.add(key)
                dline, target = sorted(hits)[0]
                self.report(
                    rnode,
                    f"'{dname}' was donated to jitted '{target}' "
                    f"(line {dline}) and is read again here — on TPU "
                    f"the donated buffer no longer exists (CPU runs "
                    f"hide this: donation is a no-op off-accelerator)."
                    f" Rebind it from the call's result, or drop it "
                    f"from donate_argnums",
                    line=rnode.lineno)


# ================================================================= ACK013


#: call-name tails that discharge a consumed stream record / leased
#: shard (the batchjobs ledger settles by commit or release)
_ACK_NAMES = {
    "xack", "ack", "_ack", "dead_letter", "_dead_letter",
    "quarantine", "_quarantine",
    "commit_shard", "_commit_shard", "release_shard",
    "_release_shard",
}
#: claim sources: reading one of these hands the caller records it
#: now OWES an ack for (XREADGROUP delivers exactly-once; XAUTOCLAIM
#: re-delivers another worker's pending entries; claim_shards leases
#: batch shards that must be committed or released)
_CLAIM_NAMES = {"xreadgroup", "xautoclaim", "claim_shards"}


@register_rule
class AckObligationRule(Rule):
    """Exactly-once discharge of consumed stream records + the
    ``engine.Request`` completion contract, in ``serving/`` — and the
    same obligation over leased batch shards in ``batchjobs/``.

    Why: every protocol bug the chaos/storm harnesses caught lately
    was a *path-sensitive obligation* bug — a record claimed on one
    path and never discharged (or discharged twice) on another.  A
    consumed record that completes an iteration without ack /
    ``dead_letter`` / quarantine / serve stays pending forever and
    feeds the poison-attempt ledger blame it never earned (the PR 13
    reclaim defect quarantined INNOCENT records exactly this way —
    its fixture lives in the test suite); a double discharge
    overwrites a delivered result with an error.  A locally-created
    ``Request`` that can reach function exit without ``complete()``/
    ``fail()``/escaping to the engine is a client blocked until its
    transport timeout.  A path that ends in a propagating raise is
    NOT a leak: the Redis loop dying un-acked IS the PEL-reclaim
    contract ("a re-raise that reaches the loop boundary").

    The batchjobs claim→settle loop carries the identical shape: a
    shard returned by ``claim_shards`` must reach ``commit_shard`` /
    ``release_shard`` or propagate a raise on every path — a shard
    that completes an iteration still OWNED is leased-but-never-
    settled, invisible to peers until the lease times out, and a
    double settle is the duplicate-commit race the O_EXCL marker
    exists to absorb.  Same rule, second ledger, so ``batchjobs/`` is
    in scope too.
    """

    rule_id = "ACK013"
    severity = "error"
    doc = ("serving/batchjobs obligation: consumed record or leased "
           "shard not discharged exactly once, or a Request that can "
           "miss complete()/fail() on some path")

    SCOPE = ("analytics_zoo_tpu/serving/",
             "analytics_zoo_tpu/batchjobs/")

    def check_module(self, ctx: ModuleContext) -> None:
        if not ctx.relpath.startswith(self.SCOPE):
            return
        for fn in _functions(ctx):
            self._check_requests(ctx, fn)
            self._check_records(ctx, fn)

    # ---------------------------------------------------------- requests
    def _request_creations(self, ctx: ModuleContext, fn: ast.AST
                           ) -> Dict[int, Tuple[str, ast.Assign]]:
        """id(stmt) -> (var, stmt) for ``r = Request(...)``."""
        out: Dict[int, Tuple[str, ast.Assign]] = {}
        for sub in ast.walk(fn):
            if not (isinstance(sub, ast.Assign)
                    and len(sub.targets) == 1
                    and isinstance(sub.targets[0], ast.Name)
                    and isinstance(sub.value, ast.Call)):
                continue
            if ctx.enclosing_function(sub) is not fn:
                continue
            resolved = ctx.resolve(sub.value.func) or ""
            if resolved == "Request" or resolved.endswith(".Request"):
                out[id(sub)] = (sub.targets[0].id, sub)
        return out

    def _check_requests(self, ctx: ModuleContext, fn: ast.AST) -> None:
        creations = self._request_creations(ctx, fn)
        if not creations:
            return
        names = {var for var, _s in creations.values()}
        captured = _closure_reads(ctx, fn, names)
        cfg = _cfg_for(ctx, fn)

        def transfer(node: CFGNode, state: State
                     ) -> Dict[Optional[str], State]:
            out = dict(state)
            exc_out = dict(state)
            per_edge: Dict[Optional[str], State] = {}
            # guards refine: if X.done / if not X.done
            if node.kind == "if" and node.stmt is not None:
                test = node.stmt.test
                recv = None
                positive = True
                if isinstance(test, ast.UnaryOp) and \
                        isinstance(test.op, ast.Not):
                    test, positive = test.operand, False
                if isinstance(test, ast.Attribute) and \
                        test.attr == "done" and \
                        isinstance(test.value, ast.Name) and \
                        test.value.id in names:
                    recv = f"req:{test.value.id}"
                if recv is not None and recv in state:
                    done_state = (state[recv] - {OWNED}) | {DONE}
                    not_done = state[recv] - {DONE}
                    per_edge[TRUE if positive else FALSE] = {
                        **out, recv: done_state}
                    per_edge[FALSE if positive else TRUE] = {
                        **out, recv: not_done}
                    per_edge[None] = out
                    return per_edge
            for var in names:
                if var in captured:
                    continue
                key = f"req:{var}"
                # creation arms on the normal edge only (a raising
                # constructor never produced the object)
                if node.kind == "stmt" and \
                        id(node.stmt) in creations and \
                        creations[id(node.stmt)][0] == var:
                    out[key] = frozenset({OWNED})
                    continue
                if key not in out:
                    continue
                call = _method_call(node.exprs, var,
                                    {"complete", "fail"})
                if call is not None:
                    if DONE in out[key]:
                        self._double(ctx, call, var)
                    out[key] = (out[key] - {OWNED}) | {DONE}
                    exc_out[key] = out[key]
                elif _escapes(ctx, node.exprs, var):
                    out[key] = (out[key] - {OWNED}) | {ESCAPED}
                    exc_out[key] = out[key]
                # rebinding the name drops the old obligation's
                # tracking (the object is unreachable — still a
                # leak semantically, but untrackable; precision
                # over recall)
                if node.kind == "stmt" and node.stmt is not None and \
                        var in _binding_targets(node.stmt) and \
                        id(node.stmt) not in creations:
                    out.pop(key, None)
            per_edge[None] = out
            per_edge[EXC] = exc_out
            return per_edge

        in_states = run_forward(cfg, {}, transfer)
        exit_state = in_states.get(cfg.exit, {})
        for var, stmt in creations.values():
            if var in captured:
                continue
            if OWNED in exit_state.get(f"req:{var}", frozenset()):
                self.report(
                    stmt,
                    f"Request '{var}' can reach function exit without "
                    f"complete()/fail() and without being handed to "
                    f"the engine — its client blocks until the "
                    f"transport timeout (discharge it on every "
                    f"non-raising path)")

    def _double(self, ctx: ModuleContext, call: ast.Call,
                var: str) -> None:
        key = (call.lineno, call.col_offset, var)
        if not hasattr(self, "_doubles"):
            self._doubles: Set[Tuple[int, int, str]] = set()
        if key in self._doubles:
            return
        self._doubles.add(key)
        self.report(
            call,
            f"Request '{var}' may already be completed/failed on this "
            f"path — a second discharge overwrites the delivered "
            f"outcome (guard with 'if not {var}.done:')")

    # ----------------------------------------------------------- records
    def _claim_vars(self, ctx: ModuleContext, fn: ast.AST) -> Set[str]:
        """Names holding records consumed from a claim source
        (xreadgroup/xautoclaim), chased through filter rebinds."""
        claimed: Set[str] = set()
        assigns = [s for s in ast.walk(fn)
                   if isinstance(s, ast.Assign)
                   and ctx.enclosing_function(s) is fn]
        changed = True
        while changed:
            changed = False
            for s in assigns:
                tgt = s.targets[0] if len(s.targets) == 1 else None
                if not isinstance(tgt, ast.Name) or \
                        tgt.id in claimed:
                    continue
                src = s.value
                is_claim = (isinstance(src, ast.Call)
                            and isinstance(src.func, ast.Attribute)
                            and src.func.attr in _CLAIM_NAMES)
                derives = any(_contains_name(src, c) for c in claimed)
                if is_claim or derives:
                    claimed.add(tgt.id)
                    changed = True
        return claimed

    def _record_loops(self, ctx: ModuleContext, fn: ast.AST
                      ) -> List[Tuple[ast.For, str, Optional[str]]]:
        """(loop, id-var, fields-var) for every ``for`` over claimed
        records; the id var (the first loop-target element, or the
        bare target) is what discharge calls must mention — acks go
        by entry id — and the fields var (second element, when the
        target unpacks) is what distinguishes a SETTLEMENT from an
        inspection."""
        claimed = self._claim_vars(ctx, fn)
        out: List[Tuple[ast.For, str, Optional[str]]] = []
        for sub in ast.walk(fn):
            if not isinstance(sub, (ast.For, ast.AsyncFor)):
                continue
            it = sub.iter
            over_claim = (isinstance(it, ast.Name)
                          and it.id in claimed) or (
                isinstance(it, ast.Call)
                and isinstance(it.func, ast.Attribute)
                and it.func.attr in _CLAIM_NAMES)
            if not over_claim:
                continue
            tgt = sub.target
            if isinstance(tgt, ast.Tuple) and tgt.elts and \
                    isinstance(tgt.elts[0], ast.Name):
                fields = tgt.elts[1].id if (
                    len(tgt.elts) > 1
                    and isinstance(tgt.elts[1], ast.Name)) else None
                out.append((sub, tgt.elts[0].id, fields))
            elif isinstance(tgt, ast.Name):
                out.append((sub, tgt.id, None))
        return out

    def _discharging_call(self, ctx: ModuleContext, fn: ast.AST,
                          call: ast.Call, id_name: str,
                          fields_name: Optional[str]) -> bool:
        """Does this call discharge the record ``id_name``?  The
        discharge-vocabulary names (ack/dead-letter/quarantine
        family) discharge with the id alone — acks go by entry id.
        An ownership TRANSFER to a ``self.``-method / local function
        must carry the record's PAYLOAD too (the fields var, when
        the loop unpacks one): settling a record needs its data,
        while an inspection/logging helper typically takes only the
        key — treating those as discharges minted spurious
        double-settle findings.  Builtins and unresolvable calls
        never discharge."""
        args = list(call.args) + [kw.value for kw in call.keywords]
        if not any(_contains_name(a, id_name) for a in args):
            return False
        f = call.func
        vocab = (f.attr if isinstance(f, ast.Attribute) else
                 f.id if isinstance(f, ast.Name) else None)
        if vocab in _ACK_NAMES:
            return True
        if fields_name is not None and \
                not any(_contains_name(a, fields_name) for a in args):
            return False
        if isinstance(f, ast.Attribute):
            d = _dotted(f)
            return bool(d and d.count(".") == 1 and
                        d.split(".")[0] in ("self", "cls"))
        if isinstance(f, ast.Name):
            return ctx._local_function_named(call, f.id) is not None
        return False

    def _check_records(self, ctx: ModuleContext, fn: ast.AST) -> None:
        loops = self._record_loops(ctx, fn)
        if not loops:
            return
        cfg = _cfg_for(ctx, fn)
        keys = {id(loop): (f"rec:{i}", id_name, fields_name)
                for i, (loop, id_name, fields_name)
                in enumerate(loops)}
        by_id = {id(loop): loop for loop, _n, _f in loops}
        doubles: Set[Tuple[int, int]] = set()

        def transfer(node: CFGNode, state: State
                     ) -> Dict[Optional[str], State]:
            out = dict(state)
            per_edge: Dict[Optional[str], State] = {}
            if node.kind == "for" and id(node.stmt) in keys:
                key = keys[id(node.stmt)][0]
                armed = dict(out)
                armed[key] = frozenset({OWNED})
                per_edge[TRUE] = armed
                per_edge[None] = out
                return per_edge
            # a guard call that takes the record discharges it on the
            # path where it answers truthy (the already-served /
            # already-handled idiom)
            if node.kind == "if" and node.stmt is not None:
                edges = _truthy_edges(node.stmt.test)
                call = _bare_test_call(node.stmt.test)
                if edges and call is not None:
                    for key, id_name, fields_name in keys.values():
                        if key in out and self._discharging_call(
                                ctx, fn, call, id_name, fields_name):
                            t_state = dict(out)
                            t_state[key] = \
                                (out[key] - {OWNED}) | {DONE}
                            per_edge[edges[0]] = t_state
                            per_edge[edges[1]] = out
                            per_edge[None] = out
                            return per_edge
            exc_out = dict(out)
            for key, id_name, fields_name in keys.values():
                if key not in out:
                    continue
                for call in _calls_in(node.exprs):
                    if self._discharging_call(ctx, fn, call, id_name,
                                              fields_name):
                        if DONE in out[key]:
                            pos = (call.lineno, call.col_offset)
                            if pos not in doubles:
                                doubles.add(pos)
                                self.report(
                                    call,
                                    f"record '{id_name}' may already "
                                    f"be discharged on this path — a "
                                    f"second ack/judgment here double-"
                                    f"settles it (the PR 13 reclaim "
                                    f"class: an error result can "
                                    f"overwrite a delivered one)")
                        out[key] = (out[key] - {OWNED}) | {DONE}
                        # the discharge RAISING keeps the obligation:
                        # a swallowed broker failure leaves the record
                        # un-discharged on the handler path
            per_edge[None] = out
            per_edge[EXC] = exc_out
            return per_edge

        in_states = run_forward(cfg, {}, transfer)
        loops_by_key = {keys[i][0]: (by_id[i], keys[i][1])
                        for i in keys}
        leaked: Set[str] = set()
        for node in cfg.nodes:
            if node.kind != "for" or id(node.stmt) not in keys:
                continue
            key, id_name, _fields = keys[id(node.stmt)]
            state = in_states.get(node.idx, {})
            if OWNED in state.get(key, frozenset()) and \
                    key not in leaked:
                leaked.add(key)
                self.report(
                    by_id[id(node.stmt)],
                    f"record '{id_name}' consumed from the stream can "
                    f"complete an iteration without ack/dead_letter/"
                    f"serve on some path — it stays pending forever "
                    f"and accumulates poison-attempt blame (discharge "
                    f"it, or let the exception propagate to the loop "
                    f"boundary)")
        exit_state = in_states.get(cfg.exit, {})
        for key, (loop, id_name) in loops_by_key.items():
            if key in leaked:
                continue
            if OWNED in exit_state.get(key, frozenset()):
                leaked.add(key)
                self.report(
                    loop,
                    f"record '{id_name}' consumed from the stream can "
                    f"reach function exit without ack/dead_letter/"
                    f"serve on some path (break/early-return without "
                    f"discharging)")


# ================================================================= RES015


_PROC_DISCHARGE = {"wait", "communicate", "terminate", "kill"}


@register_rule
class ExceptionPathReleaseRule(Rule):
    """Acquire/release obligations checked on EVERY outgoing edge —
    exception edges included.

    Why: LOCK010 sees only ``with``-scoped locking; the bugs that
    actually shipped were *manual* protocols — the PR 9 breaker
    half-open probe slot leaked on a command-error re-raise path,
    wedging the breaker HALF_OPEN forever while readiness read ok.
    Tracked obligations: a claimed breaker probe slot
    (``X.allow()`` truthy → ``X.record_success()``/
    ``X.record_failure()`` on every path, propagating raises
    included), a manually ``.acquire()``d lock/semaphore (must reach
    ``.release()``), a spawned ``subprocess.Popen`` (must be
    waited/terminated or handed off — else a zombie), and a
    ``.start()``ed non-daemon ``threading.Thread`` (must be joined or
    handed off — else interpreter exit blocks).  ``with`` remains the
    preferred form; this rule covers what ``with`` cannot express.
    """

    rule_id = "RES015"
    severity = "warning"
    doc = ("resource acquired (probe slot / .acquire() / Popen / "
           "non-daemon Thread) can leak on an exception or "
           "early-exit path")

    def check_module(self, ctx: ModuleContext) -> None:
        src = ctx.source
        if not (".allow(" in src or ".acquire(" in src
                or "Popen(" in src or "Thread(" in src):
            return
        for fn in _functions(ctx):
            self._check_function(ctx, fn)

    # ------------------------------------------------------------ shapes
    @staticmethod
    def _lockish(recv: str) -> bool:
        tail = recv.rsplit(".", 1)[-1].lower()
        return "lock" in tail or "sem" in tail or "mutex" in tail

    @staticmethod
    def _breakerish(recv: str) -> bool:
        return "breaker" in recv.rsplit(".", 1)[-1].lower()

    def _recv_call(self, exprs: Sequence[ast.AST], attr: str,
                   pred) -> Optional[Tuple[str, ast.Call]]:
        for call in _calls_in(exprs):
            f = call.func
            if isinstance(f, ast.Attribute) and f.attr == attr:
                recv = _dotted(f.value)
                if recv and pred(recv):
                    return recv, call
        return None

    def _thread_creations(self, ctx: ModuleContext, fn: ast.AST
                          ) -> Set[str]:
        """Local names bound to a NON-daemon threading.Thread —
        daemonized either by the constructor keyword or by the
        ``t.daemon = True`` attribute form."""
        out: Set[str] = set()
        daemonized: Set[str] = set()
        for sub in ast.walk(fn):
            if not (isinstance(sub, ast.Assign)
                    and len(sub.targets) == 1):
                continue
            tgt = sub.targets[0]
            if isinstance(tgt, ast.Attribute) and \
                    tgt.attr == "daemon" and \
                    isinstance(tgt.value, ast.Name) and \
                    isinstance(sub.value, ast.Constant) and \
                    sub.value.value:
                daemonized.add(tgt.value.id)
                continue
            if not (isinstance(tgt, ast.Name)
                    and isinstance(sub.value, ast.Call)):
                continue
            resolved = ctx.resolve(sub.value.func) or ""
            if resolved != "threading.Thread" and \
                    not resolved.endswith(".Thread"):
                continue
            daemon = next((kw.value for kw in sub.value.keywords
                           if kw.arg == "daemon"), None)
            if isinstance(daemon, ast.Constant) and daemon.value:
                continue
            out.add(tgt.id)
        return out - daemonized

    def _popen_creations(self, ctx: ModuleContext, fn: ast.AST
                         ) -> Dict[int, str]:
        out: Dict[int, str] = {}
        for sub in ast.walk(fn):
            if not (isinstance(sub, ast.Assign)
                    and len(sub.targets) == 1
                    and isinstance(sub.targets[0], ast.Name)
                    and isinstance(sub.value, ast.Call)):
                continue
            resolved = ctx.resolve(sub.value.func) or ""
            if resolved == "subprocess.Popen" or \
                    resolved.endswith(".Popen"):
                out[id(sub)] = sub.targets[0].id
        return out

    # ------------------------------------------------------------- check
    def _check_function(self, ctx: ModuleContext, fn: ast.AST) -> None:
        threads = self._thread_creations(ctx, fn)
        popens = self._popen_creations(ctx, fn)
        has_probe = any(
            isinstance(c.func, ast.Attribute) and c.func.attr == "allow"
            and _dotted(c.func.value)
            and self._breakerish(_dotted(c.func.value))
            for c in ast.walk(fn) if isinstance(c, ast.Call))
        has_acquire = any(
            isinstance(c.func, ast.Attribute)
            and c.func.attr == "acquire" and _dotted(c.func.value)
            and self._lockish(_dotted(c.func.value))
            for c in ast.walk(fn) if isinstance(c, ast.Call))
        if not (threads or popens or has_probe or has_acquire):
            return
        captured = _closure_reads(ctx, fn, threads | set(
            popens.values()))
        sites: Dict[str, ast.AST] = {}
        #: obligation key -> the Name its acquiring call's result was
        #: bound to (``ok = b.allow()``) — a later ``if ok:`` / ``if
        #: not ok:`` refines: nothing was acquired on the falsy arm
        guard_vars: Dict[str, str] = {}
        cfg = _cfg_for(ctx, fn)

        def transfer(node: CFGNode, state: State
                     ) -> Dict[Optional[str], State]:
            out = dict(state)
            per_edge: Dict[Optional[str], State] = {}
            if node.kind == "if" and node.stmt is not None:
                # guard-variable refinement: the bound result of the
                # acquiring call decides whether anything was acquired
                test = node.stmt.test
                positive = True
                if isinstance(test, ast.UnaryOp) and \
                        isinstance(test.op, ast.Not):
                    test, positive = test.operand, False
                if isinstance(test, ast.Name):
                    doomed = [k for k, v in guard_vars.items()
                              if v == test.id and k in out]
                    if doomed:
                        falsy = dict(out)
                        for k in doomed:
                            falsy.pop(k)
                        per_edge[FALSE if positive else TRUE] = falsy
                        per_edge[TRUE if positive else FALSE] = out
                        per_edge[None] = out
                        return per_edge
            # breaker probe / manual acquire in an if-test arm on the
            # truthy edge only (``if not b.allow(): raise`` claims no
            # slot on the raising arm)
            if node.kind == "if" and node.stmt is not None:
                edges = _truthy_edges(node.stmt.test)
                call = _bare_test_call(node.stmt.test)
                if edges and isinstance(
                        getattr(call, "func", None), ast.Attribute):
                    attr = call.func.attr
                    recv = _dotted(call.func.value)
                    key = None
                    if attr == "allow" and recv and \
                            self._breakerish(recv):
                        key = f"probe:{recv}"
                    elif attr == "acquire" and recv and \
                            self._lockish(recv):
                        key = f"lock:{recv}"
                    if key is not None:
                        sites.setdefault(key, call)
                        armed = dict(out)
                        armed[key] = frozenset({OWNED})
                        per_edge[edges[0]] = armed
                        per_edge[edges[1]] = out
                        per_edge[None] = out
                        return per_edge
            exc_keeps = dict(out)

            def note_guard(key: str, call: ast.Call) -> None:
                # ``ok = X.allow()`` / ``got = lock.acquire(False)``:
                # remember the bound name so a later ``if ok:`` can
                # prove the falsy arm acquired nothing
                stmt = node.stmt
                if isinstance(stmt, ast.Assign) and \
                        len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], ast.Name) and \
                        stmt.value is call:
                    guard_vars[key] = stmt.targets[0].id

            # statement-position acquisitions arm on the normal edge
            hit = self._recv_call(node.exprs, "allow", self._breakerish)
            if hit is not None:
                recv, call = hit
                sites.setdefault(f"probe:{recv}", call)
                out[f"probe:{recv}"] = frozenset({OWNED})
                note_guard(f"probe:{recv}", call)
            hit = self._recv_call(node.exprs, "acquire", self._lockish)
            if hit is not None:
                recv, call = hit
                sites.setdefault(f"lock:{recv}", call)
                out[f"lock:{recv}"] = frozenset({OWNED})
                note_guard(f"lock:{recv}", call)
            if node.kind == "stmt" and id(node.stmt) in popens:
                var = popens[id(node.stmt)]
                if var not in captured:
                    sites.setdefault(f"proc:{var}", node.stmt)
                    out[f"proc:{var}"] = frozenset({OWNED})
            for var in threads:
                if var in captured:
                    continue
                if _method_call(node.exprs, var, {"start"}):
                    sites.setdefault(f"thread:{var}", node.stmt
                                     or node.exprs[0])
                    out[f"thread:{var}"] = frozenset({OWNED})
            # discharges (apply on every edge: a release that raises
            # still released first in every pattern this models)
            for key in list(out):
                kind, _, name = key.partition(":")
                done = False
                if kind == "probe":
                    done = bool(
                        self._recv_is(node.exprs, name,
                                      {"record_success",
                                       "record_failure"}))
                elif kind == "lock":
                    done = bool(self._recv_is(node.exprs, name,
                                              {"release"}))
                elif kind == "proc":
                    done = bool(
                        _method_call(node.exprs, name,
                                     _PROC_DISCHARGE)) or \
                        _escapes(ctx, node.exprs, name)
                elif kind == "thread":
                    done = bool(_method_call(node.exprs, name,
                                             {"join"})) or \
                        _escapes(ctx, node.exprs, name)
                if done:
                    out[key] = (out[key] - {OWNED}) | {DONE}
                    if key in exc_keeps:
                        exc_keeps[key] = out[key]
                # an acquisition armed by THIS node stays absent from
                # the exception-edge state: the acquiring call raising
                # means nothing was acquired
            per_edge[None] = out
            per_edge[EXC] = exc_keeps
            return per_edge

        in_states = run_forward(cfg, {}, transfer)
        messages = {
            "probe": ("half-open probe slot claimed by {n}.allow() is "
                      "not released on some path — record_success()/"
                      "record_failure() must run on every outcome, "
                      "exception edges included (a leaked slot wedges "
                      "the breaker HALF_OPEN forever: the PR 9 class)"),
            "lock": ("'{n}' is .acquire()d but a path exits without "
                     ".release() — every thread behind it deadlocks "
                     "(prefer 'with {n}:'; this is the manual-protocol "
                     "case LOCK010 cannot see)"),
            "proc": ("spawned process '{n}' can leak on some path — "
                     "wait()/communicate()/terminate() it (or hand it "
                     "to a monitor) on every exit, or it zombies"),
            "thread": ("non-daemon thread '{n}' is start()ed but a "
                       "path exits without join() — interpreter "
                       "shutdown blocks on it (join in a finally, or "
                       "mark it daemon)"),
        }
        reported: Set[str] = set()
        for exit_idx in (cfg.exit, cfg.raise_exit):
            state = in_states.get(exit_idx, {})
            for key, facts in state.items():
                if OWNED not in facts or key in reported:
                    continue
                reported.add(key)
                kind, _, name = key.partition(":")
                site = sites.get(key)
                if site is None:
                    continue
                self.report(site, messages[kind].format(n=name))

    @staticmethod
    def _recv_is(exprs: Sequence[ast.AST], recv: str,
                 attrs: Set[str]) -> Optional[ast.Call]:
        for call in _calls_in(exprs):
            f = call.func
            if isinstance(f, ast.Attribute) and f.attr in attrs and \
                    _dotted(f.value) == recv:
                return call
        return None
