"""Resource-lifecycle typestate engine — the DT6xx tier's model half.

The serve/fleet tier is held together by paired-lifecycle protocols:
``PagePool`` leases (``begin`` → ``register``/``handoff`` → ``release``),
``AdapterTable`` pins (``acquire`` → ``release``), bare lock
``acquire``/``release`` pairs, and terminal-status request handles.
Every one of those invariants was previously enforced only by runtime
tests; this module proves release-on-all-paths *statically*, before a
chaos test has to cross the leaking path.

**Protocol registry.**  :data:`PROTOCOLS` declares each resource kind as
an acquire→release pair with idempotency, transfer, and intermediate-op
rules.  Two shapes exist:

* *value* protocols — the acquire call's **return value** is the
  resource (``lease = pool.begin(...)``); later ops name it as the
  first argument (``pool.release(lease)``) or as the receiver
  (``handle.cancel()``);
* *receiver* protocols — the resource is keyed by the **receiver**
  (and, for ``keyed_by_arg``, the first argument): ``lock.acquire()``
  / ``lock.release()``, ``adapters.acquire(aid)`` /
  ``adapters.release(aid)``.

Receivers are matched by the last dotted segment (``self.pages`` →
``pages``) against each protocol's receiver pattern, so the tier only
ever tracks calls it is confident about — the family contract is
silence, never noise.

**Typestate walk.**  For each project function the engine walks an
intraprocedural CFG in structured form: statements are interpreted in
order and control splits into outcome streams — fall-through, return,
raise, break, continue — with ``try``/``except``/``finally``/``with``
composing them exactly like the interpreter does (``finally`` bodies
run on every stream; ``with`` releases its resources on every exit
edge; any statement that *calls* while a resource is held grows a
potential exception edge).  Each stream carries a state mapping live
resources to HELD / RELEASED / TRANSFERRED / TERMINAL, and the walk
emits :class:`LifecycleEvent` records (rule-tagged; severity and
filtering live in ``lifecycle_rules``).

**Ownership transfer is not a leak.**  A resource stops being
leak-tracked the moment ownership demonstrably moves elsewhere: stored
on ``self``/any attribute or container, returned, yielded, captured by
a nested function, passed to an *unknown* callee, or published via a
transfer op (``PagePool.handoff``).  Passing it to a callee the
callgraph resolves to a function that releases that parameter counts
as a *release* (the interprocedural summary below), so a later
explicit release still reports DT602 on non-idempotent protocols.

**Scope and limits** (docs/ANALYSIS.md has the worked catalog): the
walk is intraprocedural over local bindings; cross-method lifecycles
(acquire in one method, release in another — the scheduler storing a
lease on the request) are deliberately out of scope statically and are
covered at runtime by ``analysis.leak_ledger``.  ``except`` handlers
are assumed to catch (typed handlers that let an exception by are a
false *negative*, never a false positive), and receiver-shaped
resources are only leak-tracked when the same function also contains a
matching release — split acquire/release APIs (``__enter__`` acquiring
for ``__exit__``) stay silent.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from .callgraph import FunctionInfo, Project
from .walker import call_name, walk_in_order

__all__ = ["LifecycleEvent", "LifecycleModel", "PROTOCOLS", "Protocol"]

# Statuses a tracked resource moves through.
_HELD = "held"              # acquired, this function owns the release
_WITH = "with"              # held by a `with` block: auto-released
_RELEASED = "released"
_TRANSFERRED = "transferred"  # ownership moved (store/return/unknown call)
_TERMINAL = "terminal"      # a terminal op (handle.cancel) consumed it
_UNACQ = "unacquired"       # guard-false branch: the acquire never happened

# user-callback attribute shapes (same vocabulary as the DT3xx tier's
# callback-under-lock rule, so "un-shimmed user callback" means the
# same thing in both tiers)
_CALLBACK_RE = re.compile(
    r"^on_[a-z0-9_]+$|_(callback|cb|fn|hook)s?$|^(callback|hook)s?$")

# decorators whose generators legitimately hold resources across yield:
# the yield IS the handoff point (contextmanager bodies, pytest
# fixtures' setup/teardown halves)
_YIELD_EXEMPT_DECOS = ("contextmanager", "asynccontextmanager", "fixture")


@dataclasses.dataclass(frozen=True)
class Protocol:
    """One declared acquire→release pairing.

    ``kind`` selects the resource identity: ``"value"`` tracks the
    acquire call's return value through a local name; ``"receiver"``
    keys the resource on the receiver path (plus the first argument
    when ``keyed_by_arg``).  ``idempotent`` releases tolerate a double
    release (``PagePool.release`` checks ``lease.released``); on a
    non-idempotent protocol it is DT602.  ``leak_rule`` names the rule
    a leaked path reports under ("" disables leak tracking — request
    handles are order-checked only).
    """

    name: str
    kind: str                      # "value" | "receiver"
    receiver: str                  # regex over the receiver's last segment
    acquire: Tuple[str, ...]
    release: Tuple[str, ...] = ()
    transfer: Tuple[str, ...] = ()   # release + ownership published
    use: Tuple[str, ...] = ()        # legal only while held
    terminal: Tuple[str, ...] = ()   # consume the resource; repeat = DT605
    idempotent: bool = False
    leak_rule: str = "DT601"
    keyed_by_arg: bool = False

    def ops(self) -> FrozenSet[str]:
        return frozenset(self.acquire + self.release + self.transfer
                         + self.use + self.terminal)


PROTOCOLS: Tuple[Protocol, ...] = (
    # serve/pages.py: PageLease.  release is idempotent by design
    # (cancel racing retirement), so register-after-release is the
    # order violation (DT605), not a double-release.
    Protocol(name="page lease", kind="value",
             receiver=r"(^|_)(pages?|pools?|page_pool)$",
             acquire=("begin",), release=("release",),
             transfer=("handoff",), use=("register",),
             idempotent=True),
    # serve/adapters.py: refcounted pins keyed by adapter id.  A double
    # release over-decrements and can drop another request's pin.
    Protocol(name="adapter pin", kind="receiver",
             receiver=r"(^|_)adapters?(_table)?$",
             acquire=("acquire",), release=("release",),
             keyed_by_arg=True, idempotent=False),
    # bare lock discipline (complements DT3xx, which checks WHICH locks
    # are held, not that they are always dropped)
    Protocol(name="lock", kind="receiver",
             receiver=r"(^|_)(lock|mutex)s?$",
             acquire=("acquire",), release=("release",),
             idempotent=False, leak_rule="DT603"),
    # serve/fleet request handles: cancel is terminal — a re-cancel of
    # an already-terminal handle is the Request state machine violation
    Protocol(name="request handle", kind="value",
             receiver=r"(^|_)(engine|router)s?$",
             acquire=("submit",), terminal=("cancel",),
             leak_rule=""),
)

_ALL_OP_NAMES = frozenset(op for p in PROTOCOLS for op in p.ops())


@dataclasses.dataclass(frozen=True)
class LifecycleEvent:
    """One rule-worthy occurrence; ``lifecycle_rules`` turns these into
    findings (severity, suppression, select/ignore)."""

    rule: str
    path: str
    line: int
    col: int
    message: str


class _Resource:
    """Identity + bookkeeping for one tracked acquisition."""

    __slots__ = ("idx", "proto", "node", "binding", "key", "guard")

    def __init__(self, idx: int, proto: Protocol, node: ast.AST,
                 binding: Optional[str], key: Tuple[str, ...]):
        self.idx = idx
        self.proto = proto
        self.node = node            # the acquire call (finding anchor)
        self.binding = binding      # local name, for value resources
        self.key = key              # (receiver[, arg0]) for receiver kind
        # receiver-kind acquires return a token (bool / table row), not
        # the resource; when that token is bound to a name it becomes
        # the acquisition *guard*: `ok = lock.acquire(timeout=t)` ...
        # `if ok: lock.release()` is release-on-all-paths, because the
        # guard-false branch never acquired
        self.guard: Optional[str] = None


# A state is an immutable mapping resource-idx -> status.
_State = Tuple[Tuple[int, str], ...]
_EMPTY: _State = ()
_MAX_STATES = 16


def _sget(state: _State, idx: int) -> Optional[str]:
    for i, s in state:
        if i == idx:
            return s
    return None


def _sset(state: _State, idx: int, status: str) -> _State:
    return tuple(sorted([(i, s) for i, s in state if i != idx]
                        + [(idx, status)]))


def _sdrop(state: _State, idx: int) -> _State:
    return tuple((i, s) for i, s in state if i != idx)


class _Flows:
    """Outcome streams of one structured-CFG region."""

    __slots__ = ("fall", "ret", "exc", "brk", "cont")

    def __init__(self):
        self.fall: Set[_State] = set()
        self.ret: Set[_State] = set()
        self.exc: List[Tuple[_State, ast.AST]] = []
        self.brk: Set[_State] = set()
        self.cont: Set[_State] = set()

    def merge(self, other: "_Flows", fall: bool = True) -> None:
        if fall:
            self.fall |= other.fall
        self.ret |= other.ret
        self.exc.extend(other.exc)
        self.brk |= other.brk
        self.cont |= other.cont


def _cap(states: Iterable[_State]) -> Set[_State]:
    out = set(states)
    if len(out) > _MAX_STATES:
        out = set(sorted(out)[:_MAX_STATES])
    return out


def _receiver_path(node: ast.AST) -> Optional[str]:
    """Dotted path of a plain receiver (``self.pages`` → "self.pages");
    None for anything computed (calls, subscripts) — those stay silent."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return None


def _arg_key(node: ast.AST) -> str:
    """Stable identity for a keyed first argument (``req.adapter_id``
    matches itself across acquire/release sites)."""
    try:
        return ast.dump(node)
    except Exception:                              # pragma: no cover
        return f"<arg@{getattr(node, 'lineno', 0)}>"


def _is_yield_exempt(fn: ast.AST, src) -> bool:
    for deco in getattr(fn, "decorator_list", []):
        target = deco.func if isinstance(deco, ast.Call) else deco
        name = None
        if isinstance(target, (ast.Name, ast.Attribute)):
            parts = []
            cur = target
            while isinstance(cur, ast.Attribute):
                parts.append(cur.attr)
                cur = cur.value
            if isinstance(cur, ast.Name):
                parts.append(cur.id)
                name = ".".join(reversed(parts))
        canon = src.canonical(name) or name or ""
        if any(canon.endswith(d) for d in _YIELD_EXEMPT_DECOS):
            return True
    return False


def _shimmed(node: ast.AST, fn: ast.AST) -> bool:
    """True when ``node`` sits inside a try-body whose Try has handlers
    (an exception shim) within ``fn`` — the scheduler's callback
    discipline, which DT604 must not flag."""
    cur = getattr(node, "parent", None)
    child = node
    while cur is not None and cur is not fn:
        if isinstance(cur, ast.Try) and cur.handlers \
                and any(child is n or _contains(n, child)
                        for n in cur.body):
            return True
        child = cur
        cur = getattr(cur, "parent", None)
    return False


def _contains(anc: ast.AST, node: ast.AST) -> bool:
    cur: Optional[ast.AST] = node
    while cur is not None:
        if cur is anc:
            return True
        cur = getattr(cur, "parent", None)
    return False


class LifecycleModel:
    """Typestate results over one project: build once, read events.

    ``releasing_params`` is the interprocedural summary — for each
    function key, the set of parameter positions the function releases
    (passes to a protocol release op, or calls ``.release()`` on) —
    propagated through resolved call sites so a helper of a helper
    still counts as a releasing callee.
    """

    def __init__(self, project: Project,
                 protocols: Tuple[Protocol, ...] = PROTOCOLS):
        self.project = project
        self.protocols = protocols
        self._events: List[LifecycleEvent] = []
        self._seen: Set[Tuple[str, str, int, int]] = set()
        # (path, qualname) of every function that passed the prescan
        # gate and got a full typestate walk — the self-check tests
        # assert the serve tier's protocol traffic is actually visited
        self.walked: Set[Tuple[str, str]] = set()
        self.releasing_params: Dict[str, Set[int]] = {}
        self._build_release_summaries()
        for info in list(project.functions.values()):
            self._analyze_function(info)

    def events(self) -> List[LifecycleEvent]:
        return sorted(self._events,
                      key=lambda e: (e.path, e.line, e.rule, e.message))

    # ---------------------------------------------- callee summaries

    def _proto_for_call(self, call: ast.Call
                        ) -> Optional[Tuple[Protocol, str]]:
        """(protocol, op-name) when ``call`` is a recognized protocol op
        on a recognized receiver; None otherwise."""
        if not isinstance(call.func, ast.Attribute):
            return None
        op = call.func.attr
        if op not in _ALL_OP_NAMES:
            return None
        recv = _receiver_path(call.func.value)
        if recv is None:
            return None
        last = recv.rsplit(".", 1)[-1]
        for proto in self.protocols:
            if op in proto.ops() and re.search(proto.receiver, last,
                                               re.IGNORECASE):
                return proto, op
        return None

    def _build_release_summaries(self) -> None:
        direct: Dict[str, Set[int]] = {}
        for info in self.project.functions.values():
            params = info.param_names()
            rel: Set[int] = set()
            for node in ast.walk(info.node):
                if not isinstance(node, ast.Call):
                    continue
                hit = self._proto_for_call(node)
                if hit is None:
                    continue
                proto, op = hit
                if op not in proto.release and op not in proto.transfer:
                    continue
                if proto.kind == "value":
                    # pool.release(lease): the released thing is arg 0
                    if node.args and isinstance(node.args[0], ast.Name) \
                            and node.args[0].id in params:
                        rel.add(params.index(node.args[0].id))
                else:
                    # lock.release(): the released thing is the receiver
                    recv = _receiver_path(node.func.value)
                    if recv in params:
                        rel.add(params.index(recv))
            direct[info.key] = rel
        self.releasing_params = direct
        # propagate through resolved call sites (a helper that only
        # forwards to the real releaser still releases)
        for _ in range(3):
            changed = False
            for info in self.project.functions.values():
                params = info.param_names()
                mine = self.releasing_params[info.key]
                cls = info.qualname.rsplit(".", 1)[0] \
                    if "." in info.qualname else None
                for node in ast.walk(info.node):
                    if not isinstance(node, ast.Call):
                        continue
                    callee = self.project.resolve_call(
                        info.module, node, enclosing_class=cls)
                    if callee is None:
                        continue
                    rel = self.releasing_params.get(callee.key)
                    if not rel:
                        continue
                    for j, arg in enumerate(node.args):
                        if isinstance(arg, ast.Name) \
                                and arg.id in params and j in rel:
                            p = params.index(arg.id)
                            if p not in mine:
                                mine.add(p)
                                changed = True
            if not changed:
                break

    # ------------------------------------------------- per-function

    def _emit(self, rule: str, node: ast.AST, path: str,
              message: str) -> None:
        key = (rule, path, getattr(node, "lineno", 0), 0)
        if key in self._seen:
            return
        self._seen.add(key)
        self._events.append(LifecycleEvent(
            rule=rule, path=path, line=getattr(node, "lineno", 0),
            col=getattr(node, "col_offset", 0), message=message))

    def _analyze_function(self, info: FunctionInfo) -> None:
        fn = info.node
        # cheap gate: no protocol op names and no yields -> nothing to do
        interesting = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) \
                    and node.attr in _ALL_OP_NAMES:
                interesting = True
                break
        if not interesting:
            return
        self.walked.add((info.src.path, info.qualname))
        walker = _FunctionWalk(self, info)
        walker.run()
        self._events.extend(walker.events)


class _FunctionWalk:
    """One function's structured-CFG interpretation."""

    def __init__(self, model: LifecycleModel, info: FunctionInfo):
        self.model = model
        self.info = info
        self.src = info.src
        self.fn = info.node
        self.events: List[LifecycleEvent] = []
        self._seen: Set[Tuple[str, int, int]] = set()
        self.resources: List[_Resource] = []
        self.by_name: Dict[str, int] = {}          # live value bindings
        self.by_key: Dict[Tuple[str, ...], int] = {}  # receiver resources
        self.yield_exempt = _is_yield_exempt(self.fn, self.src)
        self._release_present: Set[Tuple[str, ...]] = set()
        self._prescan_releases()
        cls = info.qualname.rsplit(".", 1)[0] \
            if "." in info.qualname else None
        self._cls = cls

    # ------------------------------------------------------- helpers

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        key = (rule, line, getattr(node, "col_offset", 0))
        if key in self._seen:
            return
        self._seen.add(key)
        self.events.append(LifecycleEvent(
            rule=rule, path=self.src.path, line=line,
            col=getattr(node, "col_offset", 0), message=message))

    def _prescan_releases(self) -> None:
        """Receiver-shaped resources are only leak-tracked when the
        function also contains a matching release (or hands the
        receiver to a callee) — split acquire/release APIs stay
        silent."""
        for node in ast.walk(self.fn):
            if not isinstance(node, ast.Call):
                continue
            hit = self.model._proto_for_call(node)
            if hit is not None:
                proto, op = hit
                if proto.kind == "receiver" and (op in proto.release
                                                 or op in proto.transfer):
                    recv = _receiver_path(node.func.value)
                    key = (proto.name, recv or "")
                    if proto.keyed_by_arg and node.args:
                        key += (_arg_key(node.args[0]),)
                    self._release_present.add(key)
            # receiver object passed somewhere: the callee may release
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                recv = _receiver_path(arg)
                if recv is None:
                    continue
                last = recv.rsplit(".", 1)[-1]
                for proto in self.model.protocols:
                    if proto.kind == "receiver" \
                            and re.search(proto.receiver, last,
                                          re.IGNORECASE):
                        self._release_present.add(
                            (proto.name, recv))
                        if proto.keyed_by_arg:
                            self._release_present.add(
                                (proto.name, recv, "*"))

    def _guard_test(self, test: ast.AST) -> Tuple[Optional[int], bool]:
        """(resource idx, inverted) when ``test`` is a bare acquisition
        guard (``if ok:`` / ``if not ok:``); (None, False) otherwise."""
        inverted = False
        t = test
        if isinstance(t, ast.UnaryOp) and isinstance(t.op, ast.Not):
            inverted = True
            t = t.operand
        if isinstance(t, ast.Name):
            for res in self.resources:
                if res.guard == t.id:
                    return res.idx, inverted
        return None, False

    def _rkey(self, proto: Protocol, recv: str,
              call: ast.Call) -> Tuple[str, ...]:
        key = (proto.name, recv)
        if proto.keyed_by_arg:
            key += (_arg_key(call.args[0]) if call.args else "",)
        return key

    def _desc(self, res: _Resource) -> str:
        line = getattr(res.node, "lineno", 0)
        if res.proto.kind == "value" and res.binding:
            return f"{res.proto.name} `{res.binding}` (line {line})"
        return f"{res.proto.name} acquired on line {line}"

    # ----------------------------------------------------------- run

    def run(self) -> None:
        flows = self._exec_block(self.fn.body, {_EMPTY})
        # fall-through and explicit returns: normal-path leaks
        for state in flows.fall | flows.ret:
            self._check_leaks(state, None)
        for state, node in flows.exc:
            self._check_leaks(state, node)

    def _check_leaks(self, state: _State, raiser: Optional[ast.AST]
                     ) -> None:
        for idx, status in state:
            if status != _HELD:
                continue
            res = self.resources[idx]
            rule = res.proto.leak_rule
            if not rule:
                continue
            if res.proto.kind == "receiver":
                # consistency gate: no release anywhere -> split API
                key = (res.key[0], res.key[1])
                keyed = res.key if len(res.key) > 2 else None
                if key not in self._release_present \
                        and (keyed is None
                             or keyed not in self._release_present) \
                        and (res.key[0], res.key[1], "*") \
                        not in self._release_present:
                    continue
            if raiser is not None:
                what = None
                if isinstance(raiser, ast.Raise):
                    what = "the raise"
                else:
                    for n in walk_in_order(raiser):
                        if isinstance(n, ast.Call):
                            what = f"`{call_name(n) or 'a call'}`"
                            break
                    what = what or "a call"
                msg = (f"{self._desc(res)} is leaked when {what} on "
                       f"line {getattr(raiser, 'lineno', 0)} raises — "
                       f"release it in a finally/except, or transfer "
                       f"ownership before the call")
            else:
                msg = (f"{self._desc(res)} is not released on every "
                       f"return path — use try/finally (or `with`) so "
                       f"early returns cannot leak it")
            if res.proto.leak_rule == "DT603":
                msg = (f"bare .acquire() of {self._desc(res)} is not "
                       f"paired with .release() on every path — "
                       f"use `with`, or release in a finally")
            self._emit(rule, res.node, msg)

    # ----------------------------------------------- the interpreter

    def _exec_block(self, stmts: List[ast.stmt],
                    states: Set[_State]) -> _Flows:
        flows = _Flows()
        cur = _cap(states)
        for stmt in stmts:
            if not cur:
                break
            step = self._exec_stmt(stmt, cur)
            flows.merge(step, fall=False)
            cur = _cap(step.fall)
        flows.fall = cur
        return flows

    def _exec_stmt(self, stmt: ast.stmt, states: Set[_State]) -> _Flows:
        flows = _Flows()
        kind = type(stmt)

        if kind in (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                    ast.ClassDef):
            # a nested scope capturing a tracked name owns it now
            freed = set()
            for n in ast.walk(stmt):
                if isinstance(n, ast.Name) and n.id in self.by_name:
                    freed.add(self.by_name[n.id])
            for state in states:
                for idx in freed:
                    if _sget(state, idx) in (_HELD, _WITH):
                        state = _sset(state, idx, _TRANSFERRED)
                flows.fall.add(state)
            return flows

        if kind is ast.Return:
            for state in states:
                ns, raised = self._eval_expr(stmt.value, state,
                                             escape_names=True) \
                    if stmt.value is not None else (state, False)
                if raised:
                    flows.exc.append((ns, stmt))
                flows.ret.add(ns)
            return flows

        if kind is ast.Raise:
            for state in states:
                ns, _ = self._eval_expr(stmt.exc, state) \
                    if stmt.exc is not None else (state, False)
                flows.exc.append((ns, stmt))
            return flows

        if kind is ast.Break:
            flows.brk = set(states)
            return flows
        if kind is ast.Continue:
            flows.cont = set(states)
            return flows

        if kind in (ast.Assign, ast.AnnAssign, ast.AugAssign):
            return self._exec_assign(stmt, states)

        if kind is ast.Expr:
            for state in states:
                ns, raised = self._eval_expr(stmt.value, state)
                if raised:
                    flows.exc.append((ns, stmt))
                flows.fall.add(ns)
            return flows

        if kind is ast.If:
            gidx, inverted = self._guard_test(stmt.test)
            for state in states:
                ns, raised = self._eval_expr(stmt.test, state)
                if raised:
                    flows.exc.append((ns, stmt))
                then_states, else_states = {ns}, {ns}
                if gidx is not None:
                    status = _sget(ns, gidx)
                    if status == _HELD:
                        # `if ok:` on an acquisition guard: the false
                        # branch models the acquire never happening
                        held = {ns}
                        unacq = {_sset(ns, gidx, _UNACQ)}
                        then_states, else_states = (
                            (unacq, held) if inverted else (held, unacq))
                    elif status == _UNACQ:
                        # guard already known false: the held branch
                        # is infeasible from this state
                        empty: Set[_State] = set()
                        then_states, else_states = (
                            ({ns}, empty) if inverted else (empty, {ns}))
                body = self._exec_block(stmt.body, then_states)
                flows.merge(body)
                other = self._exec_block(stmt.orelse, else_states)
                flows.merge(other)
            return flows

        if kind in (ast.While, ast.For, ast.AsyncFor):
            entry: Set[_State] = set()
            for state in states:
                expr = stmt.test if kind is ast.While else stmt.iter
                ns, raised = self._eval_expr(expr, state)
                if raised:
                    flows.exc.append((ns, stmt))
                entry.add(ns)
            body = self._exec_block(stmt.body, entry)
            flows.merge(body, fall=False)
            after = entry | body.fall | body.brk | body.cont
            flows.brk = set()
            flows.cont = set()
            other = self._exec_block(stmt.orelse, after)
            flows.merge(other)
            return flows

        if kind in (ast.With, ast.AsyncWith):
            return self._exec_with(stmt, states)

        if kind is ast.Try:
            return self._exec_try(stmt, states)

        # Assert, Delete, Global, Import, Pass, ...: evaluate any
        # expressions for protocol ops, keep flowing
        for state in states:
            ns = state
            raised = False
            for expr in ast.iter_child_nodes(stmt):
                if isinstance(expr, ast.expr):
                    ns, r = self._eval_expr(expr, ns)
                    raised = raised or r
            if raised:
                flows.exc.append((ns, stmt))
            flows.fall.add(ns)
        return flows

    # -------------------------------------------------- assignments

    def _exec_assign(self, stmt: ast.stmt, states: Set[_State]) -> _Flows:
        flows = _Flows()
        value = stmt.value
        targets = stmt.targets if isinstance(stmt, ast.Assign) \
            else ([stmt.target] if stmt.value is not None else [])
        simple = (len(targets) == 1 and isinstance(targets[0], ast.Name)
                  and isinstance(stmt, ast.Assign))
        for state in states:
            born: List[int] = []
            ns = state
            raised = False
            acq = self._match_acquire(value) if value is not None else None
            if acq is not None and simple:
                proto, recv, call = acq
                ns, raised = self._eval_expr(
                    value, ns, skip={id(call)})
                # the acquire itself can raise (PagePoolExhausted,
                # AdapterTableFull): that edge leaves with whatever was
                # already held, minus the never-born resource
                raised = raised or self._holds_anything(ns)
                idx = self._birth(proto, recv, call, targets[0].id, ns)
                ns = _sset(ns, idx, _HELD)
                born.append(idx)
            elif acq is not None:
                proto, recv, call = acq
                ns, raised = self._eval_expr(value, ns, skip={id(call)})
                raised = raised or self._holds_anything(ns)
                if proto.kind == "receiver":
                    # pin token stored into an attribute/container:
                    # ownership moved with it — order-track only
                    idx = self._birth(proto, recv, call, None, ns)
                    ns = _sset(ns, idx, _TRANSFERRED)
                # value resource born into a non-name target: escaped
            elif value is not None:
                # a non-name target (attribute, subscript, unpacking)
                # publishes the value: tracked names in it escape
                ns, raised = self._eval_expr(value, ns,
                                             escape_names=not simple)
            # storing a tracked name anywhere transfers ownership;
            # rebinding a tracked local loses our handle on it
            for tgt in targets:
                if isinstance(tgt, ast.Name):
                    idx = self.by_name.pop(tgt.id, None) \
                        if tgt.id in self.by_name and not (
                            simple and born) else None
                    if idx is not None \
                            and _sget(ns, idx) in (_HELD, _WITH):
                        ns = _sset(ns, idx, _TRANSFERRED)
                else:
                    ns, r2 = self._eval_expr(tgt, ns,
                                             escape_names=True)
                    raised = raised or r2
            if raised:
                exc_state = ns
                for idx in born:
                    exc_state = _sdrop(exc_state, idx)
                flows.exc.append((exc_state, stmt))
            flows.fall.add(ns)
        return flows

    def _birth(self, proto: Protocol, recv: str, call: ast.Call,
               binding: Optional[str], state: _State) -> int:
        key = self._rkey(proto, recv, call) if proto.kind == "receiver" \
            else ("value", proto.name, str(getattr(call, "lineno", 0)),
                  str(getattr(call, "col_offset", 0)))
        if proto.kind == "receiver" and key in self.by_key:
            idx = self.by_key[key]
            if binding is not None:
                self.resources[idx].guard = binding
            return idx
        if proto.kind == "value" and binding is not None:
            # one resource a site: a second path's state reaching the
            # same acquire is the same resource (each state tracks its
            # own status), or the name would follow only the last birth
            idx = self.by_name.get(binding)
            if idx is not None and self.resources[idx].key == key:
                return idx
        idx = len(self.resources)
        res = _Resource(idx, proto, call, binding, key)
        self.resources.append(res)
        if proto.kind == "value":
            if binding is not None:
                self.by_name[binding] = idx
        else:
            # the bound result of a receiver acquire is a token, not
            # the resource — remember it as the acquisition guard
            self.by_key[key] = idx
            res.guard = binding
        return idx

    # -------------------------------------------------- with / try

    def _exec_with(self, stmt: ast.stmt, states: Set[_State]) -> _Flows:
        flows = _Flows()
        for state in states:
            ns = state
            raised = False
            with_held: List[int] = []
            for item in stmt.items:
                ctx = item.context_expr
                acq = self._match_acquire(ctx)
                recv = _receiver_path(ctx)
                if acq is not None:
                    proto, r, call = acq
                    ns, r2 = self._eval_expr(ctx, ns, skip={id(call)})
                    raised = (raised or r2
                              or self._holds_anything(ns))
                    binding = item.optional_vars.id \
                        if isinstance(item.optional_vars, ast.Name) \
                        else None
                    idx = self._birth(proto, r, call, binding, ns)
                    ns = _sset(ns, idx, _WITH)
                    with_held.append(idx)
                elif recv is not None:
                    # `with lock:` — the lock object itself manages
                    last = recv.rsplit(".", 1)[-1]
                    proto = next(
                        (p for p in self.model.protocols
                         if p.kind == "receiver" and not p.keyed_by_arg
                         and re.search(p.receiver, last, re.IGNORECASE)),
                        None)
                    if proto is not None:
                        key = (proto.name, recv)
                        idx = self.by_key.get(key)
                        if idx is None:
                            idx = len(self.resources)
                            self.resources.append(_Resource(
                                idx, proto, ctx, None, key))
                            self.by_key[key] = idx
                        ns = _sset(ns, idx, _WITH)
                        with_held.append(idx)
                else:
                    ns, r2 = self._eval_expr(ctx, ns)
                    raised = raised or r2
            if raised:
                flows.exc.append((state, stmt))
            body = self._exec_block(stmt.body, {ns})

            def closed(s: _State) -> _State:
                for idx in with_held:
                    if _sget(s, idx) == _WITH:
                        s = _sdrop(s, idx)
                return s

            flows.fall |= {closed(s) for s in body.fall}
            flows.ret |= {closed(s) for s in body.ret}
            flows.brk |= {closed(s) for s in body.brk}
            flows.cont |= {closed(s) for s in body.cont}
            flows.exc.extend((closed(s), n) for s, n in body.exc)
        return flows

    def _exec_try(self, stmt: ast.Try, states: Set[_State]) -> _Flows:
        body = self._exec_block(stmt.body, states)
        flows = _Flows()
        pending = _Flows()
        pending.ret = body.ret
        pending.brk = body.brk
        pending.cont = body.cont
        if stmt.handlers:
            # assume handlers catch (typed handlers that let one by are
            # a false negative, never noise); `raise` inside a handler
            # re-raises through the exc stream.  Entry includes the
            # try-entry states: an exception can fire before the body's
            # first resource op, and handlers that do their own
            # acquire/release work must be interpreted regardless
            entry = _cap(set(states) | {s for s, _ in body.exc})
            for handler in stmt.handlers:
                hf = self._exec_block(handler.body, entry)
                pending.merge(hf)
        else:
            pending.exc.extend(body.exc)
        pending.fall = body.fall
        if stmt.orelse:
            orelse = self._exec_block(stmt.orelse, pending.fall)
            pending.fall = orelse.fall
            pending.merge(orelse, fall=False)
        if not stmt.finalbody:
            return pending
        # every stream runs the finally; finally's own exits override
        for category in ("fall", "ret", "brk", "cont"):
            for state in getattr(pending, category):
                ff = self._exec_block(stmt.finalbody, {state})
                getattr(flows, category).update(ff.fall)
                flows.merge(ff, fall=False)
                flows.fall -= ff.fall if category != "fall" else set()
        for state, node in pending.exc:
            ff = self._exec_block(stmt.finalbody, {state})
            flows.exc.extend((s, node) for s in ff.fall)
            flows.merge(ff, fall=False)
        return flows

    # ------------------------------------------------- expressions

    def _match_acquire(self, expr: Optional[ast.AST]
                       ) -> Optional[Tuple[Protocol, str, ast.Call]]:
        if not isinstance(expr, ast.Call):
            return None
        hit = self.model._proto_for_call(expr)
        if hit is None:
            return None
        proto, op = hit
        if op not in proto.acquire:
            return None
        recv = _receiver_path(expr.func.value)
        if recv is None:
            return None
        return proto, recv, expr

    def _eval_expr(self, expr: Optional[ast.AST], state: _State,
                   escape_names: bool = False,
                   skip: Optional[Set[int]] = None
                   ) -> Tuple[_State, bool]:
        """Interpret one expression: protocol ops transition resources,
        unknown calls consume (escape) tracked arguments, any call or
        yield grows an exception edge (``raised``)."""
        if expr is None:
            return state, False
        raised = False
        for node in walk_in_order(expr):
            if skip and id(node) in skip:
                continue
            if isinstance(node, (ast.Yield, ast.YieldFrom)):
                state = self._on_yield(node, state)
                continue
            if not isinstance(node, ast.Call):
                continue
            raised = raised or self._holds_anything(state)
            state = self._on_call(node, state)
        if escape_names:
            for node in ast.walk(expr):
                if isinstance(node, ast.Name) \
                        and node.id in self.by_name:
                    idx = self.by_name[node.id]
                    if _sget(state, idx) in (_HELD, _WITH):
                        state = _sset(state, idx, _TRANSFERRED)
        return state, raised

    def _holds_anything(self, state: _State) -> bool:
        return any(s in (_HELD, _WITH) for _, s in state)

    def _on_yield(self, node: ast.AST, state: _State) -> _State:
        if not self.yield_exempt:
            for idx, status in state:
                if status in (_HELD, _WITH):
                    res = self.resources[idx]
                    self._emit(
                        "DT604", node,
                        f"{self._desc(res)} is held across a yield — "
                        f"the consumer runs while the resource is "
                        f"pinned; release first or restructure as a "
                        f"context manager")
        # the yielded value escapes
        val = getattr(node, "value", None)
        if val is not None:
            for n in ast.walk(val):
                if isinstance(n, ast.Name) and n.id in self.by_name:
                    idx = self.by_name[n.id]
                    if _sget(state, idx) in (_HELD, _WITH):
                        state = _sset(state, idx, _TRANSFERRED)
        return state

    def _on_call(self, call: ast.Call, state: _State) -> _State:
        hit = self.model._proto_for_call(call)
        if hit is not None:
            return self._protocol_op(call, hit[0], hit[1], state)
        # op named on the resource value itself: handle.cancel()
        if isinstance(call.func, ast.Attribute) \
                and isinstance(call.func.value, ast.Name) \
                and call.func.value.id in self.by_name:
            idx = self.by_name[call.func.value.id]
            res = self.resources[idx]
            op = call.func.attr
            if op in res.proto.ops():
                return self._transition(call, res, op, state)
        # callback shape while holding: DT604 (locks stay DT303's)
        if isinstance(call.func, ast.Attribute) \
                and _CALLBACK_RE.search(call.func.attr) \
                and not _shimmed(call, self.fn):
            for idx, status in state:
                if status in (_HELD, _WITH) \
                        and self.resources[idx].proto.leak_rule \
                        not in ("DT603",):
                    res = self.resources[idx]
                    self._emit(
                        "DT604", call,
                        f"{self._desc(res)} is held across the user "
                        f"callback `{call_name(call)}` — a callback "
                        f"that raises or blocks strands the resource; "
                        f"release first or shim the callback")
        # unknown call: tracked args escape; a resolved releasing
        # callee releases instead
        callee = None
        rel_params: Set[int] = set()
        for j, arg in enumerate(list(call.args)):
            name = arg.id if isinstance(arg, ast.Name) else None
            recv = _receiver_path(arg)
            idx = None
            if name is not None and name in self.by_name:
                idx = self.by_name[name]
            elif recv is not None:
                for proto in self.model.protocols:
                    if proto.kind != "receiver":
                        continue
                    for key, i in self.by_key.items():
                        if key[1] == recv:
                            idx = i
                            break
            if idx is None:
                continue
            if callee is None:
                callee = self.model.project.resolve_call(
                    self.info.module, call, enclosing_class=self._cls)
                rel_params = self.model.releasing_params.get(
                    callee.key, set()) if callee is not None else set()
            res = self.resources[idx]
            status = _sget(state, idx)
            if j in rel_params:
                if status == _RELEASED and not res.proto.idempotent:
                    self._emit(
                        "DT602", call,
                        f"{self._desc(res)} is released again via "
                        f"`{call_name(call)}` after it was already "
                        f"released — double release of a "
                        f"non-idempotent resource")
                if status in (_HELD, _WITH, _RELEASED):
                    state = _sset(state, idx, _RELEASED)
            elif status in (_HELD, _WITH):
                state = _sset(state, idx, _TRANSFERRED)
        for kw in call.keywords:
            if isinstance(kw.value, ast.Name) \
                    and kw.value.id in self.by_name:
                idx = self.by_name[kw.value.id]
                if _sget(state, idx) in (_HELD, _WITH):
                    state = _sset(state, idx, _TRANSFERRED)
        return state

    def _protocol_op(self, call: ast.Call, proto: Protocol, op: str,
                     state: _State) -> _State:
        recv = _receiver_path(call.func.value)
        if recv is None:
            return state
        if op in proto.acquire:
            if proto.kind == "receiver":
                idx = self._birth(proto, recv, call, None, state)
                if _sget(state, idx) in (None, _RELEASED, _UNACQ):
                    state = _sset(state, idx, _HELD)
            # a value acquire reaching here was not bound by an
            # assignment: the result is discarded -> unreleasable
            elif proto.leak_rule:
                idx = self._birth(proto, recv, call, None, state)
                state = _sset(state, idx, _HELD)
            return state
        # resolve which resource this op addresses
        res: Optional[_Resource] = None
        if proto.kind == "receiver":
            key = self._rkey(proto, recv, call)
            idx = self.by_key.get(key)
            if idx is None and proto.keyed_by_arg:
                # same receiver, unmatched key: not ours to judge
                return state
            if idx is not None:
                res = self.resources[idx]
        else:
            if call.args and isinstance(call.args[0], ast.Name) \
                    and call.args[0].id in self.by_name:
                res = self.resources[self.by_name[call.args[0].id]]
        if res is None:
            return state
        return self._transition(call, res, op, state)

    def _transition(self, call: ast.Call, res: _Resource, op: str,
                    state: _State) -> _State:
        proto = res.proto
        status = _sget(state, res.idx)
        if status is None or status == _UNACQ:
            # unacquired (guard-false) states reach ops only through
            # merge imprecision — stay silent rather than cry wolf
            return state
        if status == _TRANSFERRED:
            # ownership escaped (stored, returned, handed to an unknown
            # callee): we disclaimed knowledge — silence, not DT602
            return state
        opname = call_name(call) or op
        if op in proto.release or op in proto.transfer:
            if status == _RELEASED:
                if not proto.idempotent:
                    self._emit(
                        "DT602", call,
                        f"double release: `{opname}` on {self._desc(res)} "
                        f"which was already released — on a "
                        f"non-idempotent resource this over-releases "
                        f"(a refcount drops someone else's pin)")
                return state
            new = _TRANSFERRED if op in proto.transfer else _RELEASED
            return _sset(state, res.idx, new)
        if op in proto.use:
            if status == _RELEASED:
                rule = "DT605" if proto.idempotent else "DT602"
                self._emit(
                    rule, call,
                    f"protocol-order violation: `{opname}` on "
                    f"{self._desc(res)} after it was released — "
                    f"`{op}` is only legal while the resource is held")
            return state
        if op in proto.terminal:
            if status == _TERMINAL:
                self._emit(
                    "DT605", call,
                    f"`{opname}` re-runs a terminal operation on "
                    f"{self._desc(res)} — the handle already reached "
                    f"a terminal status and must not be re-canceled")
                return state
            return _sset(state, res.idx, _TERMINAL)
        if op in proto.acquire and proto.kind == "value":
            return state
        if status == _RELEASED:
            rule = "DT605" if proto.idempotent else "DT602"
            self._emit(
                rule, call,
                f"use-after-release: `{opname}` touches "
                f"{self._desc(res)} after release")
        return state
