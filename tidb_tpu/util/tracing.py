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

The shared clock: the spans named in `HOST_STATES` also enter a
`jax.profiler.TraceAnnotation`, for every statement, traced or not.  The
profiler stamps them itself, on the clock of the device's `XLA Ops` line,
so a `jax.profiler` session shows what the engine was doing in each of the
device's idle gaps.  They are the states that do not contain one another;
enclosing spans (`session.execute`, `distsql.execute_root`, `cop.execute`)
are left out, or every label would be its ancestors.
"""

from __future__ import annotations

import contextvars
import json
import threading
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

HOST_STATES = frozenset({
    "session.parse", "session.plan_cache", "planner.plan", "cop.decode",
    "exec.compile", "exec.launch", "exec.wait", "exec.readback",
    "distsql.root_merge", "server.write", "columnar.gate", "mesh.stack",
})

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
    ambient span; yields None — and builds no Span — when neither exists.
    Exceptions are recorded on the span and re-raised, so a failing
    statement leaves a partial tree with `error` attributes.  A name in
    `HOST_STATES` is put on the profiler's clock too, trace or no trace.

    A class and not a generator: it is entered some 130 times an operation
    whether or not anything is traced, by threads that share one GIL."""

    __slots__ = ("_name", "_parent", "_attrs", "_note", "_sp", "_token")

    def __init__(self, name: str, parent: Span | None = None, **attrs):
        self._name, self._parent, self._attrs = name, parent, attrs
        self._note = self._sp = self._token = None

    def __enter__(self) -> Span | None:
        if self._name in HOST_STATES:
            self._note = TraceAnnotation(self._name)
            self._note.__enter__()
        cur = self._parent if self._parent is not None else _current.get()
        if cur is None:
            return None
        sp = self._sp = cur.child(self._name, **self._attrs)
        self._token = _current.set(sp)
        return sp

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self._sp
        if sp is not None:
            if exc is not None:
                sp.attrs["error"] = f"{exc_type.__name__}: {exc}"
            sp.finish()
            _current.reset(self._token)
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        return False
