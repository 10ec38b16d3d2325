"""The one place where a compiled program is called, and where its
outputs come back to the host.

`run_program` calls a jitted function and splits what happens there into
the states that the blob `cop.execute` used to hide: `exec.compile` (a
call in which JAX traced, lowered or compiled), `exec.launch` (a call of
an already compiled program: dispatch only) and `exec.wait` (from the
call's return until the overflow flags are on the host: device queue,
execution, the flags' transfer).  `read_back` covers every further
device-to-host transfer and the decoding, as `exec.readback`.  Each is a
span under the ambient one when a `TRACE` is active, a
`jax.profiler.TraceAnnotation` always (`util/tracing.py`), and a counter
of `util/metrics.py` always.

What JAX did inside a call is heard, not guessed: one `jax.monitoring`
listener, registered when this module is imported, receives the durations
of jaxpr tracing, MLIR lowering and the backend compile, and the
persistent compile cache's hit and miss events.  JAX calls listeners on
the thread that compiles, so a thread-local slot that `run_program` fills
for the length of the call tells the listener whether a compile belongs
to a program or to an eager `jnp` operation outside any program
(`exec.eager_compile`, under whatever span is ambient where it ran).
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from ..util import metrics, tracing

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_calling = threading.local()  # .heard: the _Heard of the program call in progress on this thread


class _Heard:
    """What JAX reported on this thread during one call of a program.
    Jits nested inside the program report trace times of their own that
    overlap the outer one, so `trace_ns` and `lower_ns` are the events as
    heard; backend compiles do not nest."""

    __slots__ = ("trace_ns", "lower_ns", "xla", "persistent_cache")

    def __init__(self):
        self.trace_ns = self.lower_ns = 0
        self.xla: list = []  # (end on perf_counter_ns, duration ns) per backend compile
        self.persistent_cache = "off"  # "hit" / "miss" once JAX's persistent compile cache says so

    @property
    def xla_ns(self) -> int:
        return sum(ns for _, ns in self.xla)

    @property
    def compiled(self) -> bool:
        return bool(self.trace_ns or self.lower_ns or self.xla)


def _on_duration(event: str, seconds: float, **_kw) -> None:
    heard = getattr(_calling, "heard", None)
    if event == _BACKEND_EVENT:
        ns = int(seconds * 1e9)
        metrics.XLA_BACKEND_COMPILE_NS.inc(ns)
        if heard is None:
            metrics.XLA_EAGER_COMPILES.inc()
            cur = tracing.current_span()
            if cur is not None:  # an eager operation inside a traced statement: shown where it ran
                end_ns = time.perf_counter_ns()
                cur.child_at("exec.eager_compile", end_ns - ns, end_ns).set("op", _kw.get("fun_name"))
        else:
            metrics.XLA_COMPILES.inc()
            heard.xla.append((time.perf_counter_ns(), ns))
    elif heard is not None:
        if event == _TRACE_EVENT:
            heard.trace_ns += int(seconds * 1e9)
        elif event == _LOWER_EVENT:
            heard.lower_ns += int(seconds * 1e9)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        metrics.XLA_PERSISTENT_CACHE_HITS.inc()
        verdict = "hit"
    elif event == _CACHE_MISS_EVENT:
        metrics.XLA_PERSISTENT_CACHE_MISSES.inc()
        verdict = "miss"
    else:
        return
    heard = getattr(_calling, "heard", None)
    if heard is not None:
        heard.persistent_cache = verdict


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


class FirstCallGate:
    """Single-flight over a program's first call.  `ProgramCache`'s claim
    covers `build_program`, the Python closure; JAX traces and compiles
    when the jitted function is first CALLED, and threads that call an
    un-traced function at once each trace and compile it.  With one
    program per plan shape every client of a cold server meets the same
    function at once, so calls go through here until one has returned:
    the first holds the lock while JAX traces and compiles, the others
    then find the executable in the function's own cache."""

    __slots__ = ("_mu", "done")

    def __init__(self):
        self._mu = threading.Lock()
        self.done = False  # a call has returned; set once, read without the lock

    def call(self, fn, args):
        if self.done:
            return fn(*args)
        with self._mu:
            out = fn(*args)
        self.done = True
        return out


def run_program(fn, args, operands=(), *, first_call: bool, flags=None, gate: FirstCallGate | None = None):
    """Call the jitted `fn(*args, *operands)`: `args` are the batches,
    `operands` the statement's values (`DAGRequest.program_operands()`),
    counted in `PROGRAM_PARAMS_BOUND` and as the span's `params`.  `gate`
    is the program's `FirstCallGate`.  Where `flags` is given, fetch the
    overflow flags with `flags(outputs)`, which blocks until the device is
    through.  Returns (outputs, flags on the host or None, first-call ns):
    the last is the wall time of call and wait when `first_call` says the
    program was just built, else 0 — what the exec summaries and Top SQL
    attribute to compilation.

    `first_call` names the state on the profiler's clock, which has to be
    named before the call begins; the span and the counters go by what
    the listener heard during the call, so a retrace of an old program for
    a new argument shape is an `exec.compile` too."""
    program = getattr(fn, "__name__", type(fn).__name__)
    heard = _calling.heard = _Heard()
    metrics.PROGRAM_LAUNCHES.inc()
    n_params = sum(len(o) for o in operands)
    if n_params:
        metrics.PROGRAM_PARAMS_BOUND.inc(n_params)
    args = (*args, *operands)
    t0 = time.perf_counter_ns()
    try:
        with tracing.span("exec.compile" if first_call else "exec.launch", program=program, params=n_params) as sp:
            out = fn(*args) if gate is None else gate.call(fn, args)
            t1 = time.perf_counter_ns()
            if sp is not None:
                _describe(sp, heard)
    finally:
        del _calling.heard
    if heard.compiled:
        metrics.XLA_TRACE_LOWER_NS.inc(max(t1 - t0 - heard.xla_ns, 0))
        metrics.PROGRAM_COMPILE_DURATION.observe((t1 - t0) / 1e9)
    on_host = None
    if flags is not None:
        with tracing.span("exec.wait"):
            on_host = flags(out)
        metrics.PROGRAM_WAIT_NS.inc(time.perf_counter_ns() - t1)
    return out, on_host, (time.perf_counter_ns() - t0 if first_call else 0)


def _describe(sp: tracing.Span, heard: _Heard) -> None:
    """Name the call's span by what was heard, and hang each backend
    compile under it as a span of its own, so that a reducer which knows
    only names and durations reads `exec.compile`'s self time as tracing
    and lowering."""
    if not heard.compiled:
        sp.name = "exec.launch"
        return
    sp.name = "exec.compile"
    sp.set("trace_ns", heard.trace_ns)
    sp.set("lower_ns", heard.lower_ns)
    sp.set("xla_ns", heard.xla_ns)
    sp.set("persistent_cache", heard.persistent_cache)
    for end_ns, ns in heard.xla:
        sp.child_at("exec.xla_compile", end_ns - ns, end_ns)


class read_back:
    """The device-to-host side of a launch, as a context manager.  Yields
    `to_host`, which is `np.asarray` counting the device arrays it
    converts and their bytes."""

    __slots__ = ("transfers", "bytes", "_t0", "_span", "_sp")

    def __enter__(self):
        self.transfers = self.bytes = 0
        self._t0 = time.perf_counter_ns()
        self._span = tracing.span("exec.readback")
        self._sp = self._span.__enter__()
        return self.to_host

    def to_host(self, x):
        if isinstance(x, jax.Array):
            self.transfers += 1
            self.bytes += x.nbytes
        return np.asarray(x)

    def __exit__(self, exc_type, exc, tb) -> bool:
        metrics.PROGRAM_READBACK_NS.inc(time.perf_counter_ns() - self._t0)
        metrics.PROGRAM_READBACK_TRANSFERS.inc(self.transfers)
        metrics.PROGRAM_READBACK_BYTES.inc(self.bytes)
        if self._sp is not None:
            self._sp.set("transfers", self.transfers)
            self._sp.set("bytes", self.bytes)
        return self._span.__exit__(exc_type, exc, tb)
