"""The one place where a compiled program is called, and where its
outputs come back to the host.

`run_program` calls a jitted function and splits what happens there into
the states that the blob `cop.execute` used to hide: `exec.compile` (a
call in which JAX traced, lowered or compiled), `exec.launch` (a call of
an already compiled program: dispatch only) and `exec.wait` (from the
call's return until the launch's outputs are on the host: device queue,
execution, and the launch's one transfer).  `read_back` covers what the
host then does with them, the decoding, as `exec.readback`.  Each is a
span under the ambient one when a `TRACE` is active, and always a host
state (`util/tracing.py`): a `jax.profiler.TraceAnnotation`, and wall
time on the thread's state clock, which feeds the states' counters of
`util/metrics.py` (`exec.wait` is PROGRAM_WAIT_NS, `exec.readback`
PROGRAM_READBACK_NS) and Top SQL's `device_ns`.

One device-to-host round trip per launch, in two steps.  The program
itself makes one array of everything the host reads (`HostOutputs`: its
epilogue bit-casts the overflow flags, the need hints, the row counts,
the validity mask and the output columns' leaves to bytes and
concatenates them, offsets from the static shapes), so a launch hands
back one device array where it handed back one per leaf: on the chip a
result array costs ~70 us of dispatch and ~35 us of fetch each, whatever
its size (PERF.md, PR 29).  And as soon as the call has returned, before
anything is waited for, `run_program` starts `copy_to_host_async()` on
what the call returned (the `Fetch`): that buffer, and beside it the
leaves that stay arrays of their own: floats, because the TPU keeps no
IEEE float64 to bit-cast, and leaves of a megabyte and more, which a copy
into the buffer would only double on the device.  `exec.wait` then
converts them, which blocks until the device is through, and the drivers
decode views of the one host array.  A device array converted in
`read_back` was in no launch's fetch, pays a round trip of its own and is
counted as `late` (`PROGRAM_READBACK_LATE`; 0 on every driver).

What JAX did inside a call is heard, not guessed: one `jax.monitoring`
listener, registered when this module is imported, receives the durations
of jaxpr tracing, MLIR lowering and the backend compile, and the
persistent compile cache's hit and miss events.  JAX calls listeners on
the thread that compiles, so a thread-local slot that `run_program` fills
for the length of the call tells the listener whether a compile belongs
to a program or to an eager `jnp` operation outside any program
(`exec.eager_compile`, under whatever span is ambient where it ran).

The way in mirrors the way out: `upload` puts a batch's host leaves onto
the default device in one host-to-device call.  Every leaf that would be
laid into a launch's buffer (`rides_beside` is the one rule for both
directions) is copied into one 8-byte aligned `uint8` buffer on the host;
the buffer and the leaves beside it go in one `jax.device_put` of one
list, and one jitted function per layout (`upload_unpack`, kept in
`UNPACKS`, a `ProgramCache`, so a new layout counts as a program built)
slices and bit-casts the buffer back into the leaves.  The unpack is no
launch: it reads nothing back and does not go through `run_program`, and
only a call in which it compiles is a span (`exec.compile`).
"""

from __future__ import annotations

import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..topsql import note_launch
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


# A leaf of this many bytes rides beside the buffer as an array of its own:
# its transfer outweighs the ~0.1 ms that an array costs, and laying it into
# the buffer would hold a second copy of it on the device (a full-table
# scan's columns).
_BESIDE_BYTES = 1 << 20


def rides_beside(shape, dtype) -> bool:
    """Whether a leaf stays an array of its own beside the byte buffer, in
    a launch's read-back and in an upload alike: floats, because the TPU
    keeps no IEEE float64 to bit-cast, and leaves of `_BESIDE_BYTES` and
    more."""
    return jnp.issubdtype(dtype, jnp.floating) or math.prod(shape) * np.dtype(dtype).itemsize >= _BESIDE_BYTES


class HostOutputs:
    """What the host reads of a program, made one array by the program.

    `program` is the traceable function; `reads(outputs)` names, as any
    pytree, the arrays of its outputs that the host converts (all of them
    where it is left out).  `fn` is the jitted function to launch: the
    program with an epilogue that lays those leaves into one `uint8`
    buffer (8-byte aligned offsets, in the tree's order) and returns a
    `Returned`: the buffer, beside it the leaves that stay arrays of their
    own (floats, and leaves of `_BESIDE_BYTES` and more), and as static
    data of the pytree the layout that its trace saw.  JAX keeps a
    function's output tree per compiled signature, so a call gets the
    layout of the executable that served it, whichever thread traced it
    and whatever other shapes the function was traced for since (a string
    column's byte width follows the batch)."""

    __slots__ = ("fn", "_last")

    def __init__(self, program, reads=None):
        self._last = None  # the latest trace's layout

        def with_epilogue(*args):
            out = program(*args)
            leaves, treedef = jax.tree.flatten(out if reads is None else reads(out))
            parts, own, layout, offset = [], [], [], 0
            for a in leaves:
                if rides_beside(a.shape, a.dtype):
                    layout.append((a.shape, np.dtype(a.dtype), None, len(own)))
                    own.append(a)
                    continue
                nbytes = math.prod(a.shape) * a.dtype.itemsize
                layout.append((a.shape, np.dtype(a.dtype), offset, offset + nbytes))
                parts.append(_as_bytes(a))
                pad = -nbytes % 8
                if pad:
                    parts.append(jnp.zeros(pad, jnp.uint8))
                offset += nbytes + pad
            self._last = tuple(layout)
            return Returned(jnp.concatenate(parts), tuple(own), treedef, self._last)

        with_epilogue.__name__ = with_epilogue.__qualname__ = getattr(program, "__name__", "program")
        self.fn = jax.jit(with_epilogue)

    def leaf_avals(self) -> list:
        """Shape and dtype of each leaf the host reads, as the latest
        trace saw them (the jaxpr auditor checks the region axis on
        these)."""
        return [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype, _start, _stop in self._last]


@jax.tree_util.register_pytree_node_class
class Returned:
    """What a call of `HostOutputs.fn` returns: the buffer and the arrays
    beside it, on the device, and, static, how to read them: per leaf
    (shape, dtype, start, stop) in the buffer, or (shape, dtype, None,
    index) among the arrays beside it."""

    __slots__ = ("buf", "own", "treedef", "layout")

    def __init__(self, buf, own, treedef, layout):
        self.buf, self.own, self.treedef, self.layout = buf, own, treedef, layout

    def tree_flatten(self):
        return (self.buf, self.own), (self.treedef, self.layout)

    @classmethod
    def tree_unflatten(cls, static, arrays):
        return cls(*arrays, *static)

    def read(self):
        """The tree that `reads` named, of host arrays: views of the one
        buffer.  Blocks until the device is through with the call."""
        buf = np.asarray(self.buf)
        leaves = [np.asarray(self.own[stop]) if start is None else buf[start:stop].view(dtype).reshape(shape)
                  for shape, dtype, start, stop in self.layout]
        return self.treedef.unflatten(leaves)


def _as_bytes(a):
    """A non-float array as flat `uint8`, in the host's byte order."""
    if a.dtype == jnp.bool_:
        return a.astype(jnp.uint8).reshape(-1)
    if a.dtype.itemsize == 1:
        return a.view(jnp.uint8).reshape(-1)
    return jax.lax.bitcast_convert_type(a, jnp.uint8).reshape(-1)


class Fetch:
    """The device-to-host copies that one launch started, for the
    `read_back` that follows it: how many arrays, how many bytes."""

    __slots__ = ("transfers", "bytes")

    def __init__(self, returned: Returned):
        arrays = (returned.buf, *returned.own)
        for a in arrays:
            a.copy_to_host_async()
        self.transfers = len(arrays)
        self.bytes = sum(a.nbytes for a in arrays)
        metrics.PROGRAM_FETCHES.inc()


def run_program(outputs: HostOutputs, args, operands=(), *, first_call: bool, gate: FirstCallGate | None = None):
    """Call the jitted `outputs.fn(*args, *operands)`: `args` are the
    batches, `operands` the statement's values
    (`DAGRequest.program_operands()`), counted in `PROGRAM_PARAMS_BOUND`
    (the strings among them in `PROGRAM_STR_PARAMS_BOUND` too) and as the
    span's `params`.  `gate` is the program's `FirstCallGate`.
    The copy of what the call returned is started at once, and converted
    under `exec.wait`, which blocks until the device is through and the
    transfer has landed.  Returns (the outputs that the program's `reads`
    named, as host arrays; the `Fetch`; first-call ns): the last is the
    wall time of the two states, call and wait, when `first_call` says the
    program was just built, else 0: what the exec summaries and Top SQL's
    `compile_ns` attribute to compilation.  The wait alone is what Top SQL
    attributes to the device (`device_ns`, from the state clock; the
    conservation ledger is fed here).

    `first_call` names the state on the profiler's clock, which has to be
    named before the call begins; the span, the state clock and the
    counters go by what the listener heard during the call, so a retrace
    of an old program for a new argument shape is an `exec.compile` too."""
    fn = outputs.fn
    program = getattr(fn, "__name__", type(fn).__name__)
    metrics.PROGRAM_LAUNCHES.inc()
    # one constant a slot; a string takes a row of the bytes and a length
    n_strs = sum(len(o) for o in operands if o.ndim == 2)
    n_params = sum(len(o) for o in operands) - n_strs
    if n_params:
        metrics.PROGRAM_PARAMS_BOUND.inc(n_params)
    if n_strs:
        metrics.PROGRAM_STR_PARAMS_BOUND.inc(n_strs)
    returned, call = _call(fn, (*args, *operands), gate, first_call, program=program, params=n_params)
    wait = tracing.span("exec.wait")
    with wait:
        fetch = Fetch(returned)
        out = returned.read()
    # PROGRAM_WAIT_NS is the clock's; Top SQL's ledger gets the same wait
    note_launch(wait.wall_ns)
    return out, fetch, (call.wall_ns + wait.wall_ns if first_call else 0)


def _call(fn, args, gate: FirstCallGate | None, first_call: bool, **attrs) -> tuple:
    """(what `fn(*args)` returned, its span): the call as the state
    `exec.compile` where JAX traced, lowered or compiled during it, else
    `exec.launch`.  `first_call` names the state on the profiler's clock
    before the call; the span, the clock and the counters go by what was
    heard."""
    heard = _calling.heard = _Heard()
    call = tracing.span("exec.compile" if first_call else "exec.launch", **attrs)
    try:
        with call as sp:
            out = fn(*args) if gate is None else gate.call(fn, args)
            # the state by what was heard, on the clock as in the span
            call.rename("exec.compile" if heard.compiled else "exec.launch")
            if sp is not None and heard.compiled:
                _describe(sp, heard)
    finally:
        del _calling.heard
    if heard.compiled:
        metrics.XLA_TRACE_LOWER_NS.inc(max(call.wall_ns - heard.xla_ns, 0))
        metrics.PROGRAM_COMPILE_DURATION.observe(call.wall_ns / 1e9)
    return out, call


def _describe(sp: tracing.Span, heard: _Heard) -> None:
    """What was heard in a call that compiled, on its span, and each
    backend compile hung under it as a span of its own, so that a reducer
    which knows only names and durations reads `exec.compile`'s self time
    as tracing and lowering."""
    sp.set("trace_ns", heard.trace_ns)
    sp.set("lower_ns", heard.lower_ns)
    sp.set("xla_ns", heard.xla_ns)
    sp.set("persistent_cache", heard.persistent_cache)
    for end_ns, ns in heard.xla:
        sp.child_at("exec.xla_compile", end_ns - ns, end_ns)


class read_back:
    """The host side of a launch after its `Fetch` has landed, as a
    context manager: the decoding.  The span's `transfers` and `bytes` are
    the fetch's.  Yields `to_host`, which is `np.asarray`; a device array
    that reaches it was in no fetch, waits for a round trip of its own
    and is counted, as a transfer and as `late`."""

    __slots__ = ("transfers", "bytes", "late", "_span", "_sp")

    def __init__(self, fetch: Fetch):
        self.transfers, self.bytes, self.late = fetch.transfers, fetch.bytes, 0

    def __enter__(self):
        self._span = tracing.span("exec.readback")
        self._sp = self._span.__enter__()
        return self.to_host

    def to_host(self, x):
        if isinstance(x, jax.Array):
            self.transfers += 1
            self.bytes += x.nbytes
            self.late += 1
        return np.asarray(x)

    def __exit__(self, exc_type, exc, tb) -> bool:
        metrics.PROGRAM_READBACK_TRANSFERS.inc(self.transfers)
        metrics.PROGRAM_READBACK_BYTES.inc(self.bytes)
        metrics.PROGRAM_READBACK_LATE.inc(self.late)
        if self._sp is not None:
            self._sp.set("transfers", self.transfers)
            self._sp.set("bytes", self.bytes)
            self._sp.set("late", self.late)
        return self._span.__exit__(exc_type, exc, tb)


_sent = threading.local()  # .transfers: host-to-device calls of this thread's uploads


def count_transfer() -> None:
    """One host-to-device call of an upload: `COP_UPLOAD_TRANSFERS`, and
    this thread's count (`upload_transfers`)."""
    metrics.COP_UPLOAD_TRANSFERS.inc()
    _sent.transfers = getattr(_sent, "transfers", 0) + 1


def upload_transfers() -> int:
    """Host-to-device calls that uploads on this thread have made: read
    before and after a piece of work, the difference is its own."""
    return getattr(_sent, "transfers", 0)


UNPACKS = None  # the ProgramCache of the uploads' unpack functions, by layout: made at the first upload (the cache's module imports this one)
_UNPACKS_MU = threading.Lock()  # makes UNPACKS once


def upload(leaves: list) -> list:
    """Host arrays onto the default device in one host-to-device call,
    as device arrays of the same shapes and dtypes, uncommitted as
    `jnp.asarray` makes them.  The leaves that `rides_beside` keeps apart
    go as they are; the others are laid into one `uint8` buffer at 8-byte
    aligned offsets, in order, and the buffer comes apart on the device in
    the layout's `upload_unpack`."""
    beside = [rides_beside(a.shape, a.dtype) for a in leaves]
    packed = [a for a, b in zip(leaves, beside) if not b]
    layout, offset = [], 0
    for a in packed:
        layout.append((a.shape, a.dtype, offset))
        offset += -(-a.nbytes // 8) * 8
    buf = np.zeros(offset, np.uint8)
    for (_shape, _dtype, start), a in zip(layout, packed):
        buf[start: start + a.nbytes] = a.reshape(-1).view(np.uint8)
    count_transfer()
    buf, *own = jax.device_put([buf, *(a for a, b in zip(leaves, beside) if b)])
    layout = tuple(layout)
    (fn, gate), _hit, _ns = _unpacks().built(("upload_unpack", layout), lambda: _unpack_program(layout), traced=False)
    out = fn(buf) if gate.done else _call(fn, (buf,), gate, True, program=fn.__name__)[0]
    own, out = iter(own), iter(out)
    return [next(own) if b else next(out) for b in beside]


def _unpacks():
    global UNPACKS
    if UNPACKS is None:
        with _UNPACKS_MU:
            if UNPACKS is None:
                from .builder import ProgramCache

                UNPACKS = ProgramCache()
    return UNPACKS


def _unpack_program(layout: tuple) -> tuple:
    """(the jitted function that cuts an upload's buffer back into its
    leaves, per `layout`'s (shape, dtype, start); its `FirstCallGate`)."""
    metrics.COP_UPLOAD_PROGRAMS.inc()

    def upload_unpack(buf):
        # each leaf's slice behind a barrier: fused, the slices of a large
        # buffer take the TPU compiler minutes (a 65,536-row batch of sixteen
        # columns compiled for a v5e in 522 s, and in 1.2 s with barriers)
        return tuple(_from_bytes(jax.lax.optimization_barrier(buf[start: start + math.prod(shape) * dtype.itemsize]), shape, dtype)
                     for shape, dtype, start in layout)

    return jax.jit(upload_unpack), FirstCallGate()


def _from_bytes(b, shape, dtype):
    """Flat `uint8` in the host's byte order as an array of `shape` and
    `dtype`: `_as_bytes` undone."""
    if dtype == np.bool_:
        return b.astype(jnp.bool_).reshape(shape)
    if dtype.itemsize == 1:
        return jax.lax.bitcast_convert_type(b, dtype).reshape(shape)
    return jax.lax.bitcast_convert_type(b.reshape(-1, dtype.itemsize), dtype).reshape(shape)
