"""Hierarchical statement tracing — the span tree behind `TRACE <stmt>`
(ref: pkg/util/tracing over opentracing spans + executor/trace.go's
TraceExec collecting them into the result set).

Design:

  * A trace is a tree of `Span`s. `trace(name)` opens a root; `span(name)`
    opens a child of the ambient current span. When NO trace is active,
    `span()` yields None at near-zero cost — instrumentation stays in the
    hot paths permanently, like the reference's always-on tracing hooks.
  * The ambient span is a `contextvars.ContextVar`, so nested sync code
    parents correctly. Worker threads (the distsql dispatch pool) do NOT
    inherit context: the dispatcher captures `current_span()` on the
    session thread and passes it as `span(..., parent=...)` — the
    explicit-handoff analog of opentracing's SpanContext propagation.
  * Child attach is lock-protected (concurrent cop tasks append to one
    parent); finished spans are immutable in practice and render without
    the lock.

Durations are perf_counter_ns; a span still inside `with` reports the
elapsed time so a partial tree (failing statement) renders consistently.
A rendered span also carries its start as an offset from the root's, the
thread it ran on and that thread's CPU time between start and finish, so
the tree can be laid on a timeline and `wall - cpu` reads as time spent
waiting: for the GIL, a lock or the device.

The shared clock: the spans named in `HOST_STATES` are the host's states.
Entering or leaving one, for every statement, traced or not, does two
things beside the span.  It enters a `jax.profiler.TraceAnnotation`, which
the profiler stamps itself on the clock of the device's `XLA Ops` line, so
a `jax.profiler` session shows what the engine was doing in each of the
device's idle gaps.  And it moves the thread's state stack (`_ThreadClock`,
thread-local, no lock): at every transition one `perf_counter_ns()` is read
and the wall time since the last transition is charged to the state that
was on top.  So a state is charged its SELF time, a nested state takes its
own, and on one thread every nanosecond between the bottom state's open and
close belongs to exactly one state.  A thread's sums go to the process-wide
counters of `HOST_STATES` when its stack empties: once a wire command
(`server.command`, the bottom that `server/server.py` opens), once a
dispatch pool task (`pool_task`), and at the close of the outermost state
where a `Session` or `run_program` is driven with nothing around it.
Conservation, exact by construction: over finished commands, the states'
wall ns - HOST_POOL_NS = SERVER_HANDLE_NS.

CPU is read at one boundary only, the bottoms': `thread_time_ns()` as an
outermost bottom closes, charged whole to SERVER_CPU_NS (a command, the
serving thread's) or HOST_POOL_CPU_NS (a task, the worker's), and for a
thread that serves command after command no sooner than `CPU_READ_NS` after
its last read.  Not at every transition, as the wall clock is: on the chip's
host the thread CPU clock is a system call of 6 us that ticks at 10 ms,
where `perf_counter_ns` takes 78 ns, and read at every transition it cost a
fifth of `tpch_q1q6_params` (PERF.md section 6, PR 36).  CPU by state is
what `TRACE` shows: a span carries `cpu_ns`.

The nesting rule: a host state contains only `exec.*` states on its own
thread.  The bottoms `server.command` and `distsql.task` contain anything:
their own share is the time that no state names; and a statement that runs
inside another's state (a correlated subquery that the root merge's
row-at-a-time fallback evaluates) opens a bottom of its own first.  A
breach is counted in `nesting_breaches`, which the tests hold empty.
Enclosing spans (`session.execute`, `distsql.execute_root`, `cop.execute`)
are no states, or every label on the profiler's clock would be its
ancestors.  For the same reason only the `_ANNOTATED` states enter an
annotation, the twelve that did before the clock: not the bottoms, not
`distsql.wait_tasks`, which only waits for other threads' states, and not
`session.probe`, `session.rows` or `columnar.scan`, which became states
for the share of `server.command` they took (PERF.md section 3).
"""

from __future__ import annotations

import contextvars
import json
import threading
import time
from contextlib import contextmanager
from time import perf_counter_ns, thread_time_ns

from jax.profiler import TraceAnnotation

from . import metrics as _m

# state -> the counter of its wall ns; PERF.md section 3 has the table
HOST_STATES = {
    "server.command": _m.HOST_SERVER_COMMAND_NS,
    "session.probe": _m.HOST_PROBE_NS,
    "session.parse": _m.HOST_PARSE_NS,
    "session.plan_cache": _m.HOST_PLAN_CACHE_NS,
    "planner.plan": _m.HOST_PLAN_NS,
    "session.rows": _m.HOST_ROWS_NS,
    "distsql.wait_tasks": _m.HOST_WAIT_TASKS_NS,
    "distsql.task": _m.HOST_TASK_NS,
    "cop.decode": _m.HOST_COP_DECODE_NS,
    "mesh.stack": _m.HOST_MESH_STACK_NS,
    "columnar.gate": _m.COLUMNAR_GATE_WAIT_NS,
    "columnar.scan": _m.HOST_COLUMNAR_SCAN_NS,
    "exec.compile": _m.HOST_EXEC_COMPILE_NS,
    "exec.launch": _m.HOST_EXEC_LAUNCH_NS,
    "exec.wait": _m.PROGRAM_WAIT_NS,
    "exec.readback": _m.PROGRAM_READBACK_NS,
    "distsql.root_merge": _m.HOST_ROOT_MERGE_NS,
    "server.write": _m.SERVER_WRITE_NS,
}
BOTTOM_STATES = frozenset({"server.command", "distsql.task"})
# the states that are on the profiler's clock too
_ANNOTATED = frozenset({
    "session.parse", "session.plan_cache", "planner.plan", "cop.decode",
    "exec.compile", "exec.launch", "exec.wait", "exec.readback",
    "distsql.root_merge", "server.write", "columnar.gate", "mesh.stack",
})

CPU_READ_NS = 20_000_000  # two ticks of the chip host's thread CPU clock: a read sooner tells nothing

nesting_breaches: dict = {}  # (state on top, state opened inside it) -> times; empty on a sound tree


class _Account:
    """One state's wall ns on one thread: charged, and flushed so far."""

    __slots__ = ("state", "wall", "flushed", "open", "counter")

    def __init__(self, state: str):
        self.state = state
        self.wall = self.flushed = 0
        self.open = state in BOTTOM_STATES  # may contain any state, not `exec.*` alone
        self.counter = HOST_STATES[state]


class _ThreadClock:
    """A thread's state stack and its accounts.  Touched by its own
    thread alone."""

    __slots__ = ("stack", "accounts", "wall", "cpu", "cpu_wall", "spent")

    def __init__(self):
        self.stack: list = []     # the accounts of the open states, innermost last
        self.accounts: dict = {}  # state -> _Account
        self.wall = 0             # the wall clock as read at the last transition
        self.cpu = 0              # the thread's CPU clock as an outermost bottom last read it (0: the thread's start)
        self.cpu_wall = 0         # the wall clock at that read
        self.spent: dict = {}     # what the last flush took: {state: wall ns}

    def account(self, state: str) -> _Account:
        acct = self.accounts.get(state)
        if acct is None:
            acct = self.accounts[state] = _Account(state)
        return acct

    def charge(self) -> None:
        """What has passed since the last transition is the innermost
        open state's."""
        now = perf_counter_ns()
        if self.stack:
            self.stack[-1].wall += now - self.wall
        self.wall = now

    def flush(self) -> None:
        """What this thread charged since its last flush, to the
        counters, and into `spent`."""
        spent = self.spent = {}
        for acct in self.accounts.values():
            wall = acct.wall - acct.flushed
            if wall:
                acct.flushed = acct.wall
                acct.counter.inc(wall)
                spent[acct.state] = wall


_thread = threading.local()


def _clock() -> _ThreadClock:
    try:
        return _thread.clock
    except AttributeError:
        clock = _thread.clock = _ThreadClock()
        return clock


# `_push` and `_pop` run twice a state, some 260 times a sysbench operation:
# written flat, `charge` inlined
def _push(state: str) -> int:
    """Open `state` on this thread: what has passed since the last
    transition is the enclosing state's.  Returns the wall clock's read."""
    try:
        c = _thread.clock
    except AttributeError:
        c = _thread.clock = _ThreadClock()
    acct = c.accounts.get(state) or c.account(state)
    stack = c.stack
    now = perf_counter_ns()
    if stack:
        top = stack[-1]
        top.wall += now - c.wall
        if not top.open and not acct.open and state[:5] != "exec.":
            nesting_breaches[top.state, state] = nesting_breaches.get((top.state, state), 0) + 1
    stack.append(acct)
    c.wall = now
    return now


def _pop() -> int:
    """Close this thread's innermost state; with the last one closed the
    thread's sums go to the counters.  Returns the wall clock's read."""
    c = _thread.clock
    stack = c.stack
    now = perf_counter_ns()
    stack.pop().wall += now - c.wall
    c.wall = now
    if not stack:
        c.flush()
    return now


def clock_mark() -> dict:
    """This thread's wall ns by state so far, the open state's share up to
    now included.  `clock_since` of it is what the thread charged in
    between, flushed or not."""
    c = _clock()
    c.charge()
    return {state: a.wall for state, a in c.accounts.items()}


def clock_since(mark: dict) -> dict:
    """{state: wall ns} charged on this thread since `mark`."""
    out = {}
    for state, wall in clock_mark().items():
        wall0 = mark.get(state, 0)
        if wall != wall0:
            out[state] = wall - wall0
    return out


class host_state:
    """A bottom state around a whole unit of a thread's work: a wire
    command (`server.command`), a dispatch pool task (`distsql.task`).  No
    span, no annotation.  Once closed, `wall_ns` is the time between the
    two clock reads that opened and closed it, which is the sum of what
    every state was charged on this thread in between, itself included.

    Where it was the thread's outermost state, `cpu_ns` is the thread's CPU
    time since its CPU clock was last read; `cpu` says when that is.
    "both": as the state opens and as it closes, so `cpu_ns` is the
    state's own.  "close": at the close alone, for a thread that only
    waited for this unit since its last one or since it started (a pool's
    worker).  "ticks": the same, and no sooner than `CPU_READ_NS` after the
    thread's last read (a connection's thread, command after command):
    `cpu_ns` is then 0 for most commands and several commands' for the one
    that reads, and the sum over a thread's commands is its CPU."""

    __slots__ = ("_state", "_cpu", "_t0", "wall_ns", "cpu_ns")

    def __init__(self, state: str, cpu: str = "both"):
        self._state, self._cpu = state, cpu
        self.cpu_ns = 0

    def __enter__(self):
        now = self._t0 = _push(self._state)
        c = _thread.clock
        if self._cpu == "both" and len(c.stack) == 1:
            c.cpu, c.cpu_wall = thread_time_ns(), now
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        now = _pop()
        self.wall_ns = now - self._t0
        c = _thread.clock
        if not c.stack and (self._cpu != "ticks" or now - c.cpu_wall >= CPU_READ_NS):
            cpu = thread_time_ns()
            self.cpu_ns, c.cpu, c.cpu_wall = cpu - c.cpu, cpu, now
        return False


class pool_task(host_state):
    """The hand-over that gives a dispatch pool's worker a clock of its
    own for the length of one task: the bottom state `distsql.task` on the
    worker's thread.  At the task's end the thread's sums go to the state
    counters and, all states together, to HOST_POOL_NS, its CPU time to
    HOST_POOL_CPU_NS, and both to the statement's resource tag
    (`tag.add_host`)."""

    __slots__ = ("_tag",)

    def __init__(self, tag=None):
        super().__init__("distsql.task", cpu="close")  # the executor's thread only waits between tasks
        self._tag = tag

    def __exit__(self, exc_type, exc, tb) -> bool:
        super().__exit__(exc_type, exc, tb)
        spent = _thread.clock.spent  # all of the task: a pool thread's stack is empty between tasks
        _m.HOST_POOL_NS.inc(sum(spent.values()))
        _m.HOST_POOL_CPU_NS.inc(self.cpu_ns)
        if self._tag is not None:
            self._tag.add_host(spent, pool_cpu_ns=self.cpu_ns)
        return False


_current: contextvars.ContextVar = contextvars.ContextVar("tidb_tpu_span", default=None)


class Span:
    """One timed operation with attributes and children."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "thread", "cpu_ns", "_cpu0", "children", "_lock")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs: dict = dict(attrs)
        self.thread = threading.get_native_id()
        self.cpu_ns: int | None = None  # the thread's CPU time inside the span, once finished
        self._cpu0 = time.thread_time_ns()
        self.start_ns = time.perf_counter_ns()
        self.end_ns: int | None = None
        self.children: list[Span] = []  # guarded_by: _lock
        self._lock = threading.Lock()

    # -- building ----------------------------------------------------------
    def child(self, name: str, **attrs) -> "Span":
        sp = Span(name, **attrs)
        with self._lock:
            self.children.append(sp)
        return sp

    def set(self, key: str, value) -> None:
        """Record an attribute (rows, bytes, cache_hit, region_id...)."""
        self.attrs[key] = value

    def finish(self) -> None:
        if self.end_ns is None:
            self.end_ns = time.perf_counter_ns()
            self.cpu_ns = time.thread_time_ns() - self._cpu0

    def child_at(self, name: str, start_ns: int, end_ns: int) -> "Span":
        """A finished child from times taken elsewhere (a duration that a
        listener was handed): no CPU time is known for it."""
        sp = self.child(name)
        sp.start_ns, sp.end_ns = max(start_ns, self.start_ns), end_ns
        return sp

    # -- reading -----------------------------------------------------------
    @property
    def duration_ns(self) -> int:
        end = self.end_ns if self.end_ns is not None else time.perf_counter_ns()
        return end - self.start_ns

    def find(self, name: str) -> list["Span"]:
        """All spans named `name` anywhere under (and including) this one."""
        out = [self] if self.name == name else []
        with self._lock:
            kids = list(self.children)
        for c in kids:
            out.extend(c.find(name))
        return out

    def sum_attr(self, name: str, attr: str) -> int:
        """Sum a numeric attribute over every span named `name` under (and
        including) this one — how a statement-level reader aggregates
        per-dispatch attribution (e.g. `batch_size` / `launches_saved` on
        the distsql.batch_cop spans) without walking the tree by hand."""
        total = 0
        for sp in self.find(name):
            v = sp.attrs.get(attr)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                total += v
        return int(total)

    def to_dict(self, _t0: int | None = None) -> dict:
        t0 = self.start_ns if _t0 is None else _t0
        with self._lock:
            kids = list(self.children)
        d: dict = {"name": self.name, "start_ns": self.start_ns - t0, "duration_ns": self.duration_ns,
                   "thread": self.thread}
        if self.cpu_ns is not None:
            d["cpu_ns"] = self.cpu_ns
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if kids:
            d["children"] = [c.to_dict(t0) for c in kids]
        return d

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), default=str)

    def rows(self, _depth: int = 0, _t0: int | None = None) -> list[tuple]:
        """Flatten to (operation, start_offset_us, duration_us, attrs-json)
        rows, children indented two spaces per level — the `TRACE
        FORMAT='row'` rendering (ref: executor/trace.go dfsTree)."""
        t0 = self.start_ns if _t0 is None else _t0
        with self._lock:
            kids = list(self.children)
        row = (
            "  " * _depth + self.name,
            (self.start_ns - t0) // 1000,
            self.duration_ns // 1000,
            json.dumps(self.attrs, sort_keys=True, default=str) if self.attrs else "",
        )
        out = [row]
        for c in kids:
            out.extend(c.rows(_depth + 1, t0))
        return out


def current_span() -> Span | None:
    """The ambient span of THIS thread's context, or None (tracing off)."""
    return _current.get()


@contextmanager
def trace(name: str, **attrs):
    """Open a root span and make it ambient. The statement entry point."""
    root = Span(name, **attrs)
    token = _current.set(root)
    try:
        yield root
    finally:
        root.finish()
        _current.reset(token)


class span:
    """Child span of `parent` (explicit cross-thread handoff) or of the
    ambient span; yields None, and builds no Span, when neither exists.
    Exceptions are recorded on the span and re-raised, so a failing
    statement leaves a partial tree with `error` attributes.  A name in
    `HOST_STATES` is a host state, trace or no trace: on the profiler's
    clock and on the thread's state clock (the module's docstring); once
    left, `wall_ns` is its wall time between the clock's two reads.

    A class and not a generator: a sysbench operation enters some 130 host
    states and as many plain spans whether or not anything is traced, on
    threads that share one GIL."""

    __slots__ = ("_name", "_parent", "_attrs", "_note", "_sp", "_token", "_t0", "wall_ns")

    def __init__(self, name: str, parent: Span | None = None, **attrs):
        self._name, self._parent, self._attrs = name, parent, attrs
        self._note = self._sp = self._token = self._t0 = self.wall_ns = None

    def __enter__(self) -> Span | None:
        name = self._name
        if name in HOST_STATES:
            self._t0 = _push(name)
            if name in _ANNOTATED:
                self._note = TraceAnnotation(name)
                self._note.__enter__()
        cur = self._parent if self._parent is not None else _current.get()
        if cur is None:
            return None
        sp = self._sp = cur.child(name, **self._attrs)
        self._token = _current.set(sp)
        return sp

    def rename(self, name: str) -> None:
        """Another state's name for the span and for the clock, where what
        the state was is known only inside it (a call that compiled).  The
        state must have opened no state yet: nothing is charged to it
        before its first transition.  The annotation keeps its name."""
        if self._sp is not None:
            self._sp.name = name
        if self._t0 is not None and name != self._name:
            c = _clock()
            c.stack[-1] = c.account(name)
        self._name = name

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self._sp
        if sp is not None:
            if exc is not None:
                sp.attrs["error"] = f"{exc_type.__name__}: {exc}"
            sp.finish()
            _current.reset(self._token)
        if self._t0 is not None:
            if self._note is not None:
                self._note.__exit__(exc_type, exc, tb)
            self.wall_ns = _pop() - self._t0
        return False
