"""MC001 engine: bounded model checker for the scheduler lifecycle.

Extracts the Phase-transition writes and queue-membership operations
from `serving/scheduler.py` BY AST (no import, no execution), then
exhaustively explores a small abstract configuration space — two model
requests, every scheduling axis both ways, preempt / cancel / kill /
shed events interleaved — against a declarative transition spec, and
reports every REACHABLE illegal transition or queue/phase divergence
together with the event trace that produces it.

The abstraction:

  state      per model request: (phase, set of queues it sits in),
             starting at the pseudo-phase NEW. Pool geometry, clocks
             and KV contents are abstracted away: conditions over them
             evaluate to "unknown" and fork BOTH ways (memoized per
             event application, so `self.sc.chunked` is one value
             within one pass — which covers every axis setting as a
             superset).
  events     the public SchedulerCore methods that transitively touch
             lifecycle state (phase writes or queue append/remove),
             interpreted abstractly from their AST — plus declarative
             driver events (submit / seat / prefill-done / finish /
             kill-restart) modeling what the engine, simulator and
             cluster do between core calls.
  loops      single-iteration abstraction: a `for r in <queue-ish>`
             forks over each request currently in the iterable (plus
             the empty path) and runs the body once — interleavings
             beyond one iteration are reached through repeated events.

What is checked:

  * every `r.phase = Phase.X` write against the ALLOWED edge set
    (e.g. PAUSED -> SHED without an unwind is illegal);
  * every `queue.remove(r)` actually has `r` in that queue;
  * at event end, a request sits in at most one queue, and the queue
    it sits in is PHASE_QUEUES[its phase] (a live phase with NO queue
    is legal: that is a request handed to the driver mid-admission);
  * event outcome contracts (cancel() must terminally cancel any
    live-queued request — the "cancel misses a queue" bug class).

Everything is deterministic: BFS over a sorted event list with
memoized per-(state, event, binding) application, so two runs on the
same file produce byte-identical reports and the shortest trace wins.
"""

from __future__ import annotations

import ast
from collections import deque
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

try:
    from tools.analyze.core import FileContext, Violation
except ImportError:  # run as a plain script: tools/analyze on sys.path
    from core import FileContext, Violation

RULE_ID = "MC001"

N_REQUESTS = 2
MAX_STATES = 4000
MAX_LEAVES = 512          # per event application
MAX_INLINE_DEPTH = 5

# Declarative transition spec: the legal Phase edges (NEW is the
# pre-submit pseudo-phase). Anything else reachable is a violation.
ALLOWED_EDGES: Dict[str, FrozenSet[str]] = {
    "NEW": frozenset({"QUEUED"}),
    "QUEUED": frozenset({"PREFILL", "DECODE", "CANCELLED", "SHED"}),
    "PREFILL": frozenset({"DECODE", "PAUSED", "CANCELLED", "QUEUED"}),
    "DECODE": frozenset({"FINISHED", "PAUSED", "CANCELLED", "QUEUED"}),
    "PAUSED": frozenset({"PREFILL", "DECODE", "CANCELLED", "QUEUED"}),
    "FINISHED": frozenset(),
    "CANCELLED": frozenset(),
    "SHED": frozenset(),
}

# Direct-invocation preconditions for extracted events: shed_request's
# documented contract is WAITING-only (admission-gate rejection), so
# the checker only fires it on QUEUED requests — calling it on running
# work through another event (the corpus twin's bug) is still explored
# and still illegal.
EVENT_PRECONDITIONS: Dict[str, str] = {"shed_request": "QUEUED"}

# Outcome contracts: after cancel(r) on a request that sat in a live
# queue, the request must be terminally CANCELLED.
OUTCOME_MUST_CANCEL = "cancel"

_QUEUE_OPS = ("append", "appendleft", "remove")

# abstract values ----------------------------------------------------------
UNKNOWN = ("unknown",)


def _union(qnames: FrozenSet[str], extras: Tuple[int, ...] = (),
           filtered: bool = False) -> tuple:
    return ("union", qnames, extras, filtered)


class _Extract:
    """AST-extracted model of one scheduler file."""

    def __init__(self, tree: ast.Module) -> None:
        self.phase_queues: Dict[str, str] = {}
        self.live_queues: Tuple[str, ...] = ()
        self.methods: Dict[str, ast.FunctionDef] = {}
        self.cls: Optional[ast.ClassDef] = None
        for node in tree.body:
            if isinstance(node, ast.Assign) and node.targets:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Name):
                    if tgt.id == "PHASE_QUEUES":
                        self._read_phase_queues(node.value)
                    elif tgt.id == "LIVE_QUEUES":
                        self._read_live(node.value)
            elif isinstance(node, ast.AnnAssign) and isinstance(
                    node.target, ast.Name):
                if node.target.id == "PHASE_QUEUES" and node.value:
                    self._read_phase_queues(node.value)
                elif node.target.id == "LIVE_QUEUES" and node.value:
                    self._read_live(node.value)
            elif isinstance(node, ast.ClassDef) \
                    and node.name == "SchedulerCore":
                self.cls = node
                for item in node.body:
                    if isinstance(item, ast.FunctionDef):
                        self.methods[item.name] = item

    def _read_phase_queues(self, node: ast.AST) -> None:
        if not isinstance(node, ast.Dict):
            return
        for k, v in zip(node.keys, node.values):
            if isinstance(k, ast.Attribute) \
                    and isinstance(v, ast.Constant) \
                    and isinstance(v.value, str):
                self.phase_queues[k.attr] = v.value

    def _read_live(self, node: ast.AST) -> None:
        if isinstance(node, (ast.Tuple, ast.List)):
            self.live_queues = tuple(
                e.value for e in node.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, str))

    @property
    def complete(self) -> bool:
        return bool(self.cls and self.phase_queues and self.live_queues)

    def lifecycle_methods(self) -> FrozenSet[str]:
        """Methods that TRANSITIVELY write phases or touch queues."""
        direct = set()
        calls: Dict[str, set] = {}
        for name, fn in self.methods.items():
            calls[name] = set()
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        if isinstance(t, ast.Attribute) \
                                and t.attr == "phase":
                            direct.add(name)
                if isinstance(sub, ast.Call) and isinstance(
                        sub.func, ast.Attribute):
                    if sub.func.attr in _QUEUE_OPS:
                        direct.add(name)
                    if isinstance(sub.func.value, ast.Name) \
                            and sub.func.value.id == "self" \
                            and sub.func.attr in self.methods:
                        calls[name].add(sub.func.attr)
        touched = set(direct)
        changed = True
        while changed:
            changed = False
            for name, callees in calls.items():
                if name not in touched and callees & touched:
                    touched.add(name)
                    changed = True
        return frozenset(touched)


class _Explorer:
    """Deterministic BFS over the abstract state space."""

    def __init__(self, ctx: FileContext, ex: _Extract) -> None:
        self.ctx = ctx
        self.ex = ex
        self.queues = tuple(sorted(set(ex.phase_queues.values())))
        self.live_phases = frozenset(
            p for p, q in ex.phase_queues.items()
            if q in ex.live_queues)
        self.queue_of = dict(ex.phase_queues)
        self.phase_of_queue = {q: p for p, q in ex.phase_queues.items()}
        self.ops_methods = ex.lifecycle_methods()
        self.events = self._build_events()
        self.violations: Dict[Tuple[int, str], Violation] = {}
        self._app_cache: Dict[tuple, Tuple[tuple, ...]] = {}

    # ------------------------------------------------------------- events
    def _build_events(self) -> List[Tuple[str, object]]:
        events: List[Tuple[str, object]] = []
        pq = self.queue_of
        if "QUEUED" in pq:
            events.append(("submit", ("builtin", "NEW", None,
                                      "QUEUED", (pq["QUEUED"],))))
        if "PREFILL" in pq and "QUEUED" in pq:
            events.append(("seat", ("builtin", "QUEUED", "handed",
                                    "PREFILL", ())))
        if "DECODE" in pq and "PREFILL" in pq:
            events.append(("prefill_done", (
                "builtin", "PREFILL", "handed", "DECODE",
                (pq["DECODE"],))))
            events.append(("chunk_done", (
                "builtin", "PREFILL", pq["PREFILL"], "DECODE",
                (pq["DECODE"],))))
        if "FINISHED" in pq and "DECODE" in pq:
            events.append(("finish", (
                "builtin", "DECODE", pq["DECODE"], "FINISHED",
                (pq["FINISHED"],))))
        if "QUEUED" in pq:
            events.append(("kill_restart", (
                "builtin", "*live-queued*", None, "QUEUED",
                (pq["QUEUED"],))))
        for name in sorted(self.ops_methods):
            if name.startswith("_"):
                continue
            events.append((name, self.ex.methods[name]))
        return events

    # ------------------------------------------------------------ explore
    def run(self) -> List[Violation]:
        init = ((("NEW", frozenset()),) * N_REQUESTS)
        parents: Dict[tuple, Tuple[Optional[tuple], str]] = {
            init: (None, "")}
        todo = deque([init])
        seen = {init}
        while todo and len(seen) < MAX_STATES:
            state = todo.popleft()
            for label, spec in self.events:
                for binding in self._bindings(spec):
                    call = (f"{label}(r{binding})"
                            if binding is not None else f"{label}()")
                    trace = self._trace(parents, state) + call
                    nexts = self._apply(state, spec, binding, trace)
                    for ns in nexts:
                        if ns not in seen:
                            seen.add(ns)
                            parents[ns] = (state, call)
                            todo.append(ns)
        return sorted(self.violations.values(),
                      key=lambda v: (v.line, v.message))

    def _bindings(self, spec: object) -> List[Optional[int]]:
        if isinstance(spec, tuple):  # builtin: always per-request
            return list(range(N_REQUESTS))
        fn = spec
        params = [a.arg for a in fn.args.args[1:]]
        if params and params[0] == "r":
            return list(range(N_REQUESTS))
        return [None]

    def _trace(self, parents: Dict, state: tuple) -> str:
        steps: List[str] = []
        cur: Optional[tuple] = state
        while cur is not None:
            prev, label = parents[cur]
            if label:
                steps.append(label)
            cur = prev
        steps.reverse()
        return " -> ".join(steps) + (" -> " if steps else "")

    # ------------------------------------------------- event application
    def _apply(self, state: tuple, spec: object,
               binding: Optional[int], trace: str) -> Tuple[tuple, ...]:
        key = (state, id(spec), binding)
        if key in self._app_cache:
            return self._app_cache[key]
        if isinstance(spec, tuple):
            out = self._apply_builtin(state, spec, binding)
        else:
            out = self._apply_method(state, spec, binding, trace)
        self._app_cache[key] = out
        return out

    def _apply_builtin(self, state: tuple, spec: tuple,
                       binding: int) -> Tuple[tuple, ...]:
        _, pre_phase, pre_queue, post_phase, post_queues = spec
        phase, qs = state[binding]
        if pre_phase == "*live-queued*":
            if phase not in self.live_phases or not qs:
                return ()
        elif phase != pre_phase:
            return ()
        if pre_queue == "handed" and qs:
            return ()
        if pre_queue not in (None, "handed") and pre_queue not in qs:
            return ()
        return (self._set(state, binding, post_phase,
                          frozenset(post_queues)),)

    def _apply_method(self, state: tuple, fn: ast.FunctionDef,
                      binding: Optional[int],
                      trace: str) -> Tuple[tuple, ...]:
        if binding is not None:
            pre = EVENT_PRECONDITIONS.get(fn.name)
            if pre is not None and state[binding][0] != pre:
                return ()
        env: Dict[str, object] = {"__memo__": {}, "__lastop__": {}}
        params = [a.arg for a in fn.args.args[1:]]
        for i, p in enumerate(params):
            env[p] = ("req", binding) if i == 0 and binding is not None \
                else UNKNOWN
        interp = _Interp(self, trace)
        leaves = interp.exec_block(state, env, fn.body)
        out = []
        pre_live_q = binding is not None and bool(state[binding][1]) \
            and state[binding][0] in self.live_phases
        for st, en, _ctrl, _val in leaves:
            ok = self._check_end(st, en, fn, trace)
            if fn.name == OUTCOME_MUST_CANCEL and pre_live_q \
                    and st[binding][0] != "CANCELLED":
                self._flag(fn.lineno, "outcome", (
                    f"cancel() left a live-queued request "
                    f"un-cancelled (phase {st[binding][0]}) "
                    f"[trace: {trace}]"))
                ok = False
            if ok:
                out.append(st)
        return tuple(dict.fromkeys(out))

    # ------------------------------------------------------------ checks
    def _set(self, state: tuple, i: int, phase: str,
             qs: FrozenSet[str]) -> tuple:
        reqs = list(state)
        reqs[i] = (phase, qs)
        return tuple(reqs)

    def _flag(self, line: int, kind: str, message: str) -> None:
        key = (line, kind)
        if key not in self.violations:
            self.violations[key] = Violation(
                RULE_ID, self.ctx.path, line, message)

    def _check_end(self, state: tuple, env: Dict, fn: ast.FunctionDef,
                   trace: str) -> bool:
        lastop = env.get("__lastop__", {})
        ok = True
        for i, (phase, qs) in enumerate(state):
            line = lastop.get(i, fn.lineno)
            if len(qs) > 1:
                self._flag(line, f"multiqueue-r{i}", (
                    f"request r{i} ends {fn.name}() in "
                    f"{len(qs)} queues ({', '.join(sorted(qs))}) "
                    f"[trace: {trace}]"))
                ok = False
            for q in qs:
                want = self.phase_of_queue.get(q)
                if want is not None and want != phase:
                    self._flag(line, f"divergence-r{i}", (
                        f"queue/phase divergence: r{i} sits in "
                        f"'{q}' (the {want} queue) with phase "
                        f"{phase} after {fn.name}() "
                        f"[trace: {trace}]"))
                    ok = False
        return ok

    def check_transition(self, i: int, old: str, new: str, line: int,
                         trace: str) -> None:
        allowed = ALLOWED_EDGES.get(old)
        if allowed is not None and new not in allowed:
            self._flag(line, "edge", (
                f"illegal transition {old} -> {new} for r{i} "
                f"(spec allows {old} -> "
                f"{{{', '.join(sorted(allowed)) or 'nothing'}}}) "
                f"[trace: {trace}]"))

    def check_remove(self, i: int, q: str, present: bool, line: int,
                     trace: str) -> None:
        if not present:
            self._flag(line, "remove", (
                f"removes r{i} from '{q}' while not a member "
                f"[trace: {trace}]"))


class _Interp:
    """Abstract interpreter for one event application. Statement
    execution is monadic: every step maps a set of (state, env) paths
    to its successors; unknown conditions fork both ways with a
    per-application memo keyed on the expression's dump."""

    def __init__(self, xp: _Explorer, trace: str) -> None:
        self.xp = xp
        self.trace = trace
        self.n_leaves = 0

    # leaves: (state, env, ctrl, value); ctrl in fall/return/break/continue
    def exec_block(self, state: tuple, env: Dict,
                   stmts: Sequence[ast.stmt]) -> List[tuple]:
        paths = [(state, env)]
        done: List[tuple] = []
        for st in stmts:
            nxt: List[tuple] = []
            for s, e in paths:
                for leaf in self._stmt(s, e, st):
                    if leaf[2] == "fall":
                        nxt.append((leaf[0], leaf[1]))
                    else:
                        done.append(leaf)
            paths = nxt[:MAX_LEAVES]
            if not paths:
                break
        out = [(s, e, "fall", None) for s, e in paths]
        out.extend(done)
        return out[:MAX_LEAVES]

    # ------------------------------------------------------- statements
    def _stmt(self, state: tuple, env: Dict,
              st: ast.stmt) -> List[tuple]:
        if isinstance(st, ast.Return):
            if st.value is None:
                return [(state, env, "return", ("const", None))]
            return [(s, e, "return", v)
                    for s, e, v in self.eval(state, env, st.value)]
        if isinstance(st, ast.Break):
            return [(state, env, "break", None)]
        if isinstance(st, ast.Continue):
            return [(state, env, "continue", None)]
        if isinstance(st, ast.Assign):
            return self._assign(state, env, st)
        if isinstance(st, ast.AugAssign):
            return [(state, env, "fall", None)]
        if isinstance(st, ast.Expr):
            return [(s, e, "fall", None)
                    for s, e, _ in self.eval(state, env, st.value)]
        if isinstance(st, ast.If):
            out: List[tuple] = []
            for s, e, b in self.eval_bool(state, env, st.test):
                out.extend(self.exec_block(
                    s, e, st.body if b else st.orelse))
            return out
        if isinstance(st, (ast.For, ast.While)):
            return self._loop(state, env, st)
        if isinstance(st, ast.With):
            # a context manager (a profiler span) runs its body once
            return self.exec_block(state, env, st.body)
        if isinstance(st, ast.Try):
            out = []
            for leaf in self.exec_block(state, env, st.body):
                if leaf[2] == "fall":
                    out.extend(self.exec_block(
                        leaf[0], leaf[1], st.finalbody))
                else:
                    out.append(leaf)
            return out
        return [(state, env, "fall", None)]

    def _assign(self, state: tuple, env: Dict,
                st: ast.Assign) -> List[tuple]:
        tgt = st.targets[0]
        # r.phase = Phase.X  — the checked transition write
        if isinstance(tgt, ast.Attribute) and tgt.attr == "phase":
            out = []
            for s, e, base in self.eval(state, env, tgt.value):
                if base[0] != "req":
                    out.append((s, e, "fall", None))
                    continue
                i = base[1]
                new = self._phase_const(st.value)
                if new is None:
                    out.append((s, e, "fall", None))
                    continue
                self.xp.check_transition(
                    i, s[i][0], new, st.lineno, self.trace)
                e2 = self._note_op(e, i, st.lineno)
                out.append((self.xp._set(s, i, new, s[i][1]),
                            e2, "fall", None))
            return out
        # next((q for q in X if ...), default) — binding fork
        if isinstance(tgt, ast.Name) and isinstance(st.value, ast.Call) \
                and isinstance(st.value.func, ast.Name) \
                and st.value.func.id == "next" and st.value.args \
                and isinstance(st.value.args[0], ast.GeneratorExp):
            gen = st.value.args[0]
            out = []
            for s, e, src in self.eval(state, env, gen.generators[0].iter):
                members = self._members(s, src)
                dflt = ("const", None)
                e0 = dict(e)
                e0[tgt.id] = dflt
                out.append((s, e0, "fall", None))
                for m in members:
                    e1 = dict(e)
                    e1[tgt.id] = ("req", m)
                    out.append((s, e1, "fall", None))
            return out
        out = []
        for s, e, v in self.eval(state, env, st.value):
            e2 = dict(e)
            if isinstance(tgt, ast.Name):
                e2[tgt.id] = v
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                for el in tgt.elts:
                    if isinstance(el, ast.Name):
                        e2[el.id] = UNKNOWN
            out.append((s, e2, "fall", None))
        return out

    def _loop(self, state: tuple, env: Dict,
              st: ast.stmt) -> List[tuple]:
        """Single-iteration abstraction; break/continue end the loop."""
        entries: List[tuple] = []
        if isinstance(st, ast.For):
            for s, e, src in self.eval(state, env, st.iter):
                entries.append((s, dict(e), None))  # skip path
                if src[0] in ("queue", "union"):
                    for m in self._members(s, src):
                        e1 = dict(e)
                        if isinstance(st.target, ast.Name):
                            e1[st.target.id] = ("req", m)
                        entries.append((s, e1, "body"))
                else:
                    e1 = dict(e)
                    for n in ast.walk(st.target):
                        if isinstance(n, ast.Name):
                            e1[n.id] = UNKNOWN
                    entries.append((s, e1, "body"))
        else:  # While: test forks, body at most once
            for s, e, b in self.eval_bool(state, env, st.test):
                entries.append((s, dict(e), "body" if b else None))
        out: List[tuple] = []
        for s, e, mode in entries:
            if mode is None:
                out.append((s, e, "fall", None))
                continue
            for leaf in self.exec_block(s, e, st.body):
                if leaf[2] in ("fall", "break", "continue"):
                    out.append((leaf[0], leaf[1], "fall", None))
                else:
                    out.append(leaf)
        return out[:MAX_LEAVES]

    # ------------------------------------------------------ expressions
    def eval(self, state: tuple, env: Dict,
             node: ast.AST) -> List[tuple]:
        """-> list of (state, env, value)."""
        if isinstance(node, ast.Name):
            if node.id in env:
                return [(state, env, env[node.id])]
            return [(state, env, UNKNOWN)]
        if isinstance(node, ast.Constant):
            return [(state, env, ("const", node.value))]
        if isinstance(node, ast.Attribute):
            return self._attr(state, env, node)
        if isinstance(node, ast.Call):
            return self._call(state, env, node)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            return self._concat(state, env, node)
        if isinstance(node, (ast.Compare, ast.BoolOp, ast.UnaryOp)):
            return [(s, e, ("const", b))
                    for s, e, b in self.eval_bool(state, env, node)]
        if isinstance(node, ast.List) and not node.elts:
            return [(state, env, _union(frozenset()))]
        if isinstance(node, (ast.ListComp, ast.GeneratorExp)):
            out = []
            for s, e, src in self.eval(
                    state, env, node.generators[0].iter):
                if src[0] in ("queue", "union"):
                    qn = frozenset([src[1]]) if src[0] == "queue" \
                        else src[1]
                    extras = () if src[0] == "queue" else src[2]
                    filt = bool(node.generators[0].ifs) or (
                        src[0] == "union" and src[3])
                    out.append((s, e, _union(qn, extras, filt)))
                else:
                    out.append((s, e, UNKNOWN))
            return out
        if isinstance(node, ast.IfExp):
            out = []
            for s, e, b in self.eval_bool(state, env, node.test):
                out.extend(self.eval(
                    s, e, node.body if b else node.orelse))
            return out
        return [(state, env, UNKNOWN)]

    def _attr(self, state: tuple, env: Dict,
              node: ast.Attribute) -> List[tuple]:
        if isinstance(node.value, ast.Name) \
                and node.value.id == "self" \
                and node.attr in self.xp.queues:
            return [(state, env, ("queue", node.attr))]
        if isinstance(node.value, ast.Name) \
                and node.value.id == "Phase":
            return [(state, env, ("phaseconst", node.attr))]
        out = []
        for s, e, base in self.eval(state, env, node.value):
            if base[0] == "req" and node.attr == "phase":
                out.append((s, e, ("phase", s[base[1]][0], base[1])))
            else:
                out.append((s, e, UNKNOWN))
        return out

    def _concat(self, state: tuple, env: Dict,
                node: ast.BinOp) -> List[tuple]:
        out = []
        for s, e, lv in self.eval(state, env, node.left):
            for s2, e2, rv in self.eval(s, e, node.right):
                merged = self._merge(lv, rv)
                out.append((s2, e2, merged))
        return out

    def _merge(self, a: tuple, b: tuple) -> tuple:
        def parts(v: tuple):
            if v[0] == "queue":
                return frozenset([v[1]]), (), False
            if v[0] == "union":
                return v[1], v[2], v[3]
            return None
        pa, pb = parts(a), parts(b)
        if pa is None or pb is None:
            return UNKNOWN
        return _union(pa[0] | pb[0], pa[1] + pb[1], pa[2] or pb[2])

    def _call(self, state: tuple, env: Dict,
              node: ast.Call) -> List[tuple]:
        func = node.func
        # id(r)
        if isinstance(func, ast.Name) and func.id == "id" \
                and len(node.args) == 1:
            return [(s, e, ("id", v[1]) if v[0] == "req" else UNKNOWN)
                    for s, e, v in self.eval(state, env, node.args[0])]
        # set(map(id, Q)) — membership snapshot
        if isinstance(func, ast.Name) and func.id == "set" \
                and len(node.args) == 1 \
                and isinstance(node.args[0], ast.Call) \
                and isinstance(node.args[0].func, ast.Name) \
                and node.args[0].func.id == "map" \
                and len(node.args[0].args) == 2:
            out = []
            for s, e, src in self.eval(
                    state, env, node.args[0].args[1]):
                out.append((s, e, ("idset", frozenset(
                    self._members(s, src)))))
            return out
        # list(X) passes X through
        if isinstance(func, ast.Name) and func.id == "list" \
                and len(node.args) == 1:
            return self.eval(state, env, node.args[0])
        # self.<method>(...) — inline lifecycle methods
        if isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Name) \
                and func.value.id == "self" \
                and func.attr in self.xp.ex.methods:
            return self._self_call(state, env, node, func.attr)
        # queue mutation: <queue-ish>.append/remove(r)
        if isinstance(func, ast.Attribute) and func.attr in _QUEUE_OPS:
            return self._queue_op(state, env, node, func)
        # anything else: evaluate args (unions pass through), unknown
        out = [(state, env, [])]
        for a in node.args:
            nxt = []
            for s, e, acc in out:
                for s2, e2, v in self.eval(s, e, a):
                    nxt.append((s2, e2, acc + [v]))
            out = nxt[:MAX_LEAVES]
        res = []
        for s, e, vals in out:
            merged: Optional[tuple] = None
            for v in vals:
                if v[0] in ("queue", "union"):
                    merged = v if merged is None \
                        else self._merge(merged, v)
            res.append((s, e, merged if merged is not None else UNKNOWN))
        return res

    def _self_call(self, state: tuple, env: Dict, node: ast.Call,
                   name: str) -> List[tuple]:
        if name not in self.xp.ops_methods:
            # no lifecycle effects: args still flow (unions propagate)
            return self._call(state, env, ast.Call(
                func=ast.Name(id="__opaque__", ctx=ast.Load()),
                args=node.args, keywords=node.keywords)) \
                if node.args else [(state, env, UNKNOWN)]
        depth = env.get("__depth__", 0)
        if not isinstance(depth, int) or depth >= MAX_INLINE_DEPTH:
            return [(state, env, UNKNOWN)]
        fn = self.xp.ex.methods[name]
        params = [a.arg for a in fn.args.args[1:]]
        # evaluate actual args left-to-right
        paths = [(state, env, [])]
        for a in node.args:
            nxt = []
            for s, e, acc in paths:
                for s2, e2, v in self.eval(s, e, a):
                    nxt.append((s2, e2, acc + [v]))
            paths = nxt[:MAX_LEAVES]
        out = []
        for s, e, vals in paths:
            cenv: Dict[str, object] = {
                "__memo__": e["__memo__"],
                "__lastop__": e["__lastop__"],
                "__depth__": depth + 1,
            }
            for i, p in enumerate(params):
                cenv[p] = vals[i] if i < len(vals) else UNKNOWN
            for leaf in self.exec_block(s, cenv, fn.body):
                # effects persist; caller env survives with callee memo
                e2 = dict(e)
                e2["__memo__"] = leaf[1]["__memo__"]
                e2["__lastop__"] = leaf[1]["__lastop__"]
                val = leaf[3] if leaf[2] == "return" else ("const", None)
                out.append((leaf[0], e2, val))
        return out[:MAX_LEAVES]

    def _queue_op(self, state: tuple, env: Dict, node: ast.Call,
                  func: ast.Attribute) -> List[tuple]:
        out = []
        for s, e, target in self.eval(state, env, func.value):
            argpaths = [(s, e, UNKNOWN)]
            if node.args:
                argpaths = self.eval(s, e, node.args[0])
            for s2, e2, arg in argpaths:
                if arg[0] != "req":
                    out.append((s2, e2, UNKNOWN))
                    continue
                i = arg[1]
                if target[0] == "queue":
                    q = target[1]
                    phase, qs = s2[i]
                    e3 = self._note_op(e2, i, node.lineno)
                    if func.attr == "remove":
                        self.xp.check_remove(
                            i, q, q in qs, node.lineno, self.trace)
                        s3 = self.xp._set(s2, i, phase, qs - {q})
                    else:
                        s3 = self.xp._set(s2, i, phase, qs | {q})
                    out.append((s3, e3, UNKNOWN))
                elif target[0] == "union" and func.attr != "remove":
                    # append to a local copy: track the binding
                    new = _union(target[1], target[2] + (i,),
                                 target[3])
                    e3 = dict(e2)
                    if isinstance(func.value, ast.Name):
                        e3[func.value.id] = new
                    out.append((s2, e3, UNKNOWN))
                else:
                    out.append((s2, e2, UNKNOWN))
        return out

    # -------------------------------------------------------- booleans
    def eval_bool(self, state: tuple, env: Dict,
                  node: ast.AST) -> List[tuple]:
        """-> list of (state, env, bool)."""
        if isinstance(node, ast.UnaryOp) and isinstance(
                node.op, ast.Not):
            return [(s, e, not b)
                    for s, e, b in self.eval_bool(
                        state, env, node.operand)]
        if isinstance(node, ast.BoolOp):
            is_and = isinstance(node.op, ast.And)
            paths = [(state, env, is_and)]
            for v in node.values:
                nxt = []
                for s, e, acc in paths:
                    if acc != is_and:       # already short-circuited
                        nxt.append((s, e, acc))
                        continue
                    nxt.extend(self.eval_bool(s, e, v))
                paths = nxt[:MAX_LEAVES]
            return paths
        if isinstance(node, ast.Compare):
            if len(node.ops) == 1:
                got = self._compare(state, env, node)
                if got is not None:
                    return got
            return self._fork(state, env, node)
        if isinstance(node, ast.UnaryOp):
            # non-`not` unary (e.g. -x) in a boolean context: numeric,
            # unknowable here — fork. MUST not bounce back through
            # eval(), which routes UnaryOp to eval_bool again.
            return self._fork(state, env, node)
        out = []
        for s, e, v in self.eval(state, env, node):
            t = self._truthy(s, v)
            if t is not None:
                out.append((s, e, t))
            else:
                out.extend(self._fork(s, e, node))
        return out

    def _compare(self, state: tuple, env: Dict,
                 node: ast.Compare) -> Optional[List[tuple]]:
        op = node.ops[0]
        out: List[tuple] = []
        decided = True
        for s, e, lv in self.eval(state, env, node.left):
            for s2, e2, rv in self.eval(s, e, node.comparators[0]):
                val = self._cmp_value(s2, op, lv, rv)
                if val is None:
                    decided = False
                    out.extend(self._fork(s2, e2, node))
                else:
                    out.append((s2, e2, val))
        return out if out and (decided or out) else None

    def _cmp_value(self, state: tuple, op: ast.cmpop, lv: tuple,
                   rv: tuple) -> Optional[bool]:
        if isinstance(op, (ast.In, ast.NotIn)):
            if lv[0] == "req" and rv[0] in ("queue", "union"):
                got = lv[1] in self._members(state, rv)
                return got if isinstance(op, ast.In) else not got
            if lv[0] == "id" and rv[0] == "idset":
                got = lv[1] in rv[1]
                return got if isinstance(op, ast.In) else not got
            return None
        if isinstance(op, (ast.Is, ast.Eq, ast.IsNot, ast.NotEq)):
            neg = isinstance(op, (ast.IsNot, ast.NotEq))
            if lv[0] == "phase" and rv[0] == "phaseconst":
                got = lv[1] == rv[1]
                return got != neg
            if lv[0] == "const" and rv[0] == "const":
                got = lv[1] is rv[1] if isinstance(
                    op, (ast.Is, ast.IsNot)) else lv[1] == rv[1]
                return got != neg
            if rv == ("const", None) and lv[0] in (
                    "req", "queue", "union", "idset", "phase"):
                return neg  # a bound value is never None
            if lv == ("const", None) and rv[0] in (
                    "req", "queue", "union", "idset", "phase"):
                return neg
        return None

    def _truthy(self, state: tuple, v: tuple) -> Optional[bool]:
        if v[0] == "const":
            return bool(v[1])
        if v[0] in ("req", "id", "phase"):
            return True
        if v[0] == "queue":
            return bool(self._members(state, v))
        if v[0] == "union":
            members = self._members(state, v)
            if not members:
                return False
            return None if v[3] else True  # filtered: may be empty
        if v[0] == "idset":
            return bool(v[1])
        return None

    def _fork(self, state: tuple, env: Dict,
              node: ast.AST) -> List[tuple]:
        key = ast.dump(node)
        memo = env["__memo__"]
        if key in memo:
            return [(state, env, memo[key])]
        out = []
        for b in (True, False):
            e = dict(env)
            e["__memo__"] = dict(memo)
            e["__memo__"][key] = b
            out.append((state, e, b))
        return out

    # ---------------------------------------------------------- helpers
    def _members(self, state: tuple, v: tuple) -> List[int]:
        if v[0] == "queue":
            return [i for i, (_, qs) in enumerate(state)
                    if v[1] in qs]
        if v[0] == "union":
            got = {i for q in v[1]
                   for i, (_, qs) in enumerate(state) if q in qs}
            got.update(v[2])
            return sorted(got)
        if v[0] == "idset":
            return sorted(v[1])
        return []

    def _phase_const(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "Phase":
            return node.attr
        return None

    def _note_op(self, env: Dict, i: int, line: int) -> Dict:
        e = dict(env)
        e["__lastop__"] = dict(e["__lastop__"])
        e["__lastop__"][i] = line
        return e


def check_statemachine(ctx: FileContext) -> List[Violation]:
    """Model-check one scheduler file. Quiet unless the file defines a
    `SchedulerCore` class plus the PHASE_QUEUES / LIVE_QUEUES
    registries the abstraction is extracted from."""
    ex = _Extract(ctx.tree)
    if not ex.complete:
        return []
    return _Explorer(ctx, ex).run()
