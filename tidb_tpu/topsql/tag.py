"""The per-statement resource tag and its attribution sinks (ref:
pkg/util/topsql/state — the reference carries `sql_digest, plan_digest`
in goroutine pprof labels; here the tag is a contextvar, the same
ambient mechanism util/tracing uses for spans).

The tag is set ONCE per statement at the session boundary, riding the
digest the plan-cache probe already computed in its one lexer pass. The
dispatch pool's workers do NOT inherit contextvars (the PR-2 tracing
seam has the same property), so `select()` captures the tag on the
session thread and each worker `adopt()`s it explicitly — one tag
object shared by every thread of the statement, its counters guarded by
a leaf lock no other lock is ever taken under.

Sinks are free when no tag is ambient: one contextvar read, no lock.

Time comes from the host-state clock (`util/tracing.py`), not from clocks
of the tag's own: the wall time that each thread of the statement charged
to each state lands in `host_ns` (`add_host`: the dispatch pool's workers
at the end of each task, the session's thread at the statement's end),
`device_ns` is the `exec.wait` among it, whatever route launched the
program, and `cpu_ns` the session thread's CPU plus the workers'.
"""

from __future__ import annotations

import contextvars
import threading
from contextlib import contextmanager

from .reporter import COLLECTOR

_tag: contextvars.ContextVar = contextvars.ContextVar("topsql_tag", default=None)


class ResourceTag:
    """Mutable per-statement attribution target. `sql_digest` is the
    plan-cache probe's literal-masked digest (EXECUTE re-points it at
    the underlying prepared statement's, the same join the stmt log
    does); `plan_digest` lands when the planner picks an access path.
    Counter fields accumulate under `_mu` — sinks run on dispatch pool
    threads concurrently with each other."""

    __slots__ = (
        "sql_digest", "plan_digest", "sample_sql", "_mu",
        "cpu_ns", "device_ns", "compile_ns", "backoff_ms", "queue_ms",
        "bytes_to_device", "cop_cache_hits", "host_ns", "pool_cpu_ns",
    )

    def __init__(self, sql_digest: str, sample_sql: str = ""):
        self.sql_digest = sql_digest
        self.plan_digest = ""
        self.sample_sql = sample_sql
        self._mu = threading.Lock()
        with self._mu:  # tags churn per-statement: even init writes lock
            self.cpu_ns = 0  # guarded_by: _mu
            self.device_ns = 0  # guarded_by: _mu
            self.compile_ns = 0  # guarded_by: _mu
            self.backoff_ms = 0.0  # guarded_by: _mu
            self.queue_ms = 0.0  # guarded_by: _mu
            self.bytes_to_device = 0  # guarded_by: _mu
            self.cop_cache_hits = 0  # guarded_by: _mu
            self.host_ns: dict = {}  # guarded_by: _mu; host state -> wall ns, every thread
            self.pool_cpu_ns = 0  # guarded_by: _mu; the dispatch pool's share of cpu_ns

    def add_host(self, spent: dict, pool_cpu_ns: int = 0) -> None:
        """What one thread of the statement charged to the host states,
        {state: wall ns} as the state clock hands it over: a dispatch pool
        task at its end, with the worker's CPU time, and the session's
        thread at the statement's.  Its `exec.wait` is the statement's
        `device_ns`."""
        with self._mu:
            for state, wall in spent.items():
                self.host_ns[state] = self.host_ns.get(state, 0) + wall
            self.device_ns += spent.get("exec.wait", 0)
            self.pool_cpu_ns += pool_cpu_ns

    def add(self, device_ns: int = 0, compile_ns: int = 0,
            bytes_to_device: int = 0, backoff_ms: float = 0.0,
            queue_ms: float = 0.0, cop_cache_hits: int = 0):
        with self._mu:
            self.device_ns += device_ns
            self.compile_ns += compile_ns
            self.bytes_to_device += bytes_to_device
            self.backoff_ms += backoff_ms
            self.queue_ms += queue_ms
            self.cop_cache_hits += cop_cache_hits

    def finish(self, cpu_ns: int) -> dict:
        """Statement end: the session lands its thread's exact CPU delta,
        to which the dispatch pool's is added, and takes the flush
        snapshot."""
        with self._mu:
            self.cpu_ns = cpu_ns + self.pool_cpu_ns
        return self.snapshot()

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "sql_digest": self.sql_digest,
                "plan_digest": self.plan_digest,
                "sample_sql": self.sample_sql,
                "cpu_ns": self.cpu_ns,
                "device_ns": self.device_ns,
                "compile_ns": self.compile_ns,
                "backoff_ms": self.backoff_ms,
                "queue_ms": self.queue_ms,
                "bytes_to_device": self.bytes_to_device,
                "cop_cache_hits": self.cop_cache_hits,
                "host_ns": dict(self.host_ns),
            }


def current_tag() -> ResourceTag | None:
    return _tag.get()


def activate(tag: ResourceTag | None):
    """Install `tag` as the statement's ambient attribution target.
    Returns the token `deactivate` needs; None tags install nothing
    (Top SQL off, or an unlexable statement with no probe digest)."""
    if tag is None:
        return None
    return _tag.set(tag)


def deactivate(token) -> None:
    if token is not None:
        _tag.reset(token)


@contextmanager
def adopt(tag: ResourceTag | None):
    """Cross-thread handoff: a dispatch pool worker adopts the session
    thread's tag for the duration of its task (contextvars do not cross
    ThreadPoolExecutor, exactly like the dispatch_span handoff).  A None
    tag un-tags the block: the coalescer's shared launch belongs to its
    lanes, not to the session whose thread happens to flush it."""
    token = _tag.set(tag)
    try:
        yield
    finally:
        _tag.reset(token)


# ------------------------------------------------------------------ sinks
def record_device(compile_ns: int = 0, bytes_to_device: int = 0) -> None:
    """One cop request's launch, as the store sees it: what its first
    call spent compiling and the bytes it handed to the device.  The time
    the statement waited for the device is not the store's to say: it is
    the state clock's `exec.wait`, on whatever route the program was
    launched (`note_launch`, `ResourceTag.add_host`)."""
    t = _tag.get()
    if t is not None:
        t.add(compile_ns=compile_ns, bytes_to_device=bytes_to_device)


def note_launch(wait_ns: int) -> None:
    """One launch's `exec.wait`, from `exec/launch.py`, into the
    collector's conservation ledger while a statement's tag is ambient: the
    same ns reach the tag's `device_ns` through the state clock, so
    `sum(per-digest device_ns) == sum(launch waits)` is checkable."""
    if _tag.get() is not None:
        COLLECTOR.note_launch(wait_ns)


def record_device_share(wait_ns: int) -> None:
    """A coalesced lane's share of its window's one launch: the flushing
    thread waited for the device on every lane's behalf."""
    t = _tag.get()
    if t is not None:
        t.add(device_ns=wait_ns)
        COLLECTOR.note_launch(wait_ns)


def record_backoff(ms: float) -> None:
    """A Backoffer slept interval attributed to the ambient statement."""
    t = _tag.get()
    if t is not None:
        t.add(backoff_ms=ms)


def record_queue_wait(ms: float) -> None:
    """Admission-gate queue wait attributed to the ambient statement."""
    t = _tag.get()
    if t is not None:
        t.add(queue_ms=ms)


def record_cop_cache_hit() -> None:
    """A region served from the coprocessor cache: zero device time by
    construction (no launch ran) — the hit count keeps the conservation
    story honest instead of looking like lost attribution."""
    t = _tag.get()
    if t is not None:
        t.add(cop_cache_hits=1)
