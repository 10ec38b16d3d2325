"""Compile expression trees to fused JAX computations.

The reference evaluates expressions either row-at-a-time or vectorized over
chunk columns (ref: pkg/expression/evaluator.go, builtin_*_vec.go). Here the
whole tree compiles into jnp operations over device columns, so XLA fuses the
entire predicate/projection into the surrounding kernel — the TPU-native
version of the "closure executor" fused fast path
(ref: unistore/cophandler/closure_exec.go:165).

Value model: every node yields a CompVal — (value, null) arrays plus the
FieldType. SQL three-valued logic is explicit: `null` is a bool array; the
`value` lane of a NULL slot is unspecified but harmless (kernels mask it).

Class-specific semantics (the tipb ScalarFuncSig split, e.g. GTInt vs GTReal)
are chosen from argument FieldTypes at trace time:

  int       int64 lanes; mixed signed/unsigned compares handled explicitly
  real      float64 lanes (MySQL DOUBLE)
  decimal   int64 lanes scaled by 10^ft.decimal — exact fixed-point
  time      int64 lanes holding the order-preserving packed layout
  string    int64 [N, W+1] packed big-endian words + length (device compare);
            raw bytes ride along for pass-through projection
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..chunk.device import DeviceColumn, pack_string_words
from ..types import FieldType, TypeCode
from .ir import ColumnRef, Const, Expr, Param, ScalarFunc, lane_value

# numpy (not jnp) scalar: created at import with no trace/x64-mode
# capture — the jit-purity vet pass enforces this for module constants
I64_MIN = np.int64(-0x8000000000000000)


@dataclass
class CompVal:
    value: jax.Array  # [N] lanes, or [N, W+1] packed words for strings
    null: jax.Array  # bool [N]
    ft: FieldType
    raw: tuple | None = None  # (data[N,W] uint8, length[N] int32) for strings
    const_bytes: bytes | None = None  # set for string CONSTANTS: trace-time
    # values are tracers, so CI guards read the python bytes here

    @property
    def eval_type(self) -> str:
        return self.ft.eval_type()


def _scale(ft: FieldType) -> int:
    return max(ft.decimal, 0)


def _pow10(k: int):
    return jnp.int64(10 ** k)


def _flip(v):
    """Map uint64-bitcast lanes to sign-flipped int64 so signed compare
    gives unsigned order."""
    return v ^ I64_MIN


def _round_div(num, den):
    """Integer divide rounding half away from zero (MySQL decimal/int rules)."""
    sign = jnp.where((num < 0) ^ (den < 0), jnp.int64(-1), jnp.int64(1))
    n, d = jnp.abs(num), jnp.abs(den)
    q = (2 * n + d) // (2 * d)
    return sign * q


def string_bytes(c: CompVal):
    """(data [N, W] uint8, length [N] int32) for a string CompVal — the raw
    bytes when they rode along, else unpacked from the packed compare words
    (which cover the first STRING_WORDS*8 bytes)."""
    if c.raw is not None:
        return c.raw
    words = c.value[:, :-1] ^ I64_MIN  # unflip the sign bit
    length = c.value[:, -1].astype(jnp.int32)
    shifts = jnp.array([56, 48, 40, 32, 24, 16, 8, 0], jnp.int64)
    b = (words[:, :, None] >> shifts[None, None, :]) & 0xFF
    data = b.reshape(words.shape[0], words.shape[1] * 8).astype(jnp.uint8)
    return data, length


def parse_f64_prefix(data, length):
    """MySQL string->double: value of the longest numeric prefix, 0.0 when
    none (ref: pkg/types/convert.go StrToFloat / getValidFloatPrefix —
    leading spaces skipped, trailing garbage ignored, no error here).

    Vectorized byte-at-a-time state machine over the static width W:
    stage 0 leading spaces/sign, 1 sign seen, 2 integer digits, 3 fraction,
    4 exponent sign, 5 exponent digits, 6 done.

    Bit-exact vs strtod on CPU/x64 (mantissa and scale stay exact, division
    is correctly rounded); under TPU f64 emulation the final divide can be
    ~2 ulp off — same deviation class as the double->decimal note below."""
    n, w = data.shape
    ch_all = data.astype(jnp.int32)
    stage = jnp.zeros(n, jnp.int32)
    mant = jnp.zeros(n, jnp.float64)
    frac = jnp.zeros(n, jnp.int32)
    exp = jnp.zeros(n, jnp.int32)
    neg = jnp.zeros(n, bool)
    eneg = jnp.zeros(n, bool)
    seen = jnp.zeros(n, bool)
    for i in range(w):
        ch = ch_all[:, i]
        act = (i < length) & (stage < 6)
        digit = act & (ch >= 48) & (ch <= 57)
        is_sign = (ch == 43) | (ch == 45)
        c_sp = act & (stage == 0) & (ch == 32)
        c_sign = act & (stage == 0) & is_sign
        c_int = digit & (stage <= 2)
        c_dot = act & (stage <= 2) & (ch == 46)
        c_frac = digit & (stage == 3)
        c_e = act & ((stage == 2) | (stage == 3)) & ((ch == 101) | (ch == 69)) & seen
        c_es = act & (stage == 4) & is_sign
        c_exp = digit & ((stage == 4) | (stage == 5))
        matched = c_sp | c_sign | c_int | c_dot | c_frac | c_e | c_es | c_exp
        dv = (ch - 48).astype(jnp.float64)
        mant = jnp.where(c_int | c_frac, mant * 10.0 + dv, mant)
        frac = jnp.where(c_frac, frac + 1, frac)
        exp = jnp.where(c_exp, jnp.minimum(exp * 10 + (ch - 48), 1000), exp)
        neg = neg | (c_sign & (ch == 45))
        eneg = eneg | (c_es & (ch == 45))
        seen = seen | c_int | c_frac
        stage = jnp.where(c_sign, 1, stage)
        stage = jnp.where(c_int, 2, stage)
        stage = jnp.where(c_dot, 3, stage)
        stage = jnp.where(c_e, 4, stage)
        stage = jnp.where(c_es | c_exp, 5, stage)
        stage = jnp.where(act & ~matched, 6, stage)
    e10 = jnp.clip(jnp.where(eneg, -exp, exp) - frac, -400, 400)
    # mant holds an exactly-representable integer (<= ~19 digits drift only
    # beyond 2^53); scale by an exact power of ten — dividing for negative
    # exponents keeps short decimals like "0.5" bit-exact vs strtod, and
    # jnp.power is NOT used (it loses ~1e-8 relative accuracy even in f64)
    p = _pow10_f64(jnp.abs(e10))
    out = jnp.where(e10 >= 0, mant * p, mant / p)
    # MySQL clamps range overflow to +/-DBL_MAX, not inf
    # (ref: pkg/types/convert.go StrToFloat ErrDataOutOfRange handling)
    out = jnp.clip(out, -1.7976931348623157e308, 1.7976931348623157e308)
    return jnp.where(seen, jnp.where(neg, -out, out), 0.0)


def _pow10_f64(ae):
    """Exact-where-possible 10**ae for non-negative int arrays: table lookup
    (10^k is exactly representable for k <= 22) plus exponentiation by
    squaring for the remainder (<= 400)."""
    table = jnp.array([10.0 ** k for k in range(23)], jnp.float64)
    small = jnp.minimum(ae, 22)
    out = table[small]
    r = ae - small
    b = jnp.float64(10.0)
    for _ in range(9):  # rem <= 378 < 2^9
        out = jnp.where((r & 1) == 1, out * b, out)
        b = b * b
        r = r >> 1
    return out


# the civil-calendar math is shared with the host path — branchless, so the
# same functions run on Python ints and int64 lanes (types/mytime.py)
from ..types.mytime import civil_from_days as _ymd_from_days
from ..types.mytime import days_from_civil as _days_from_ymd
from ..types.mytime import days_in_month as _days_in_month_vec


def _ci_ascii_guard(*vals):
    """The device CI kernels fold ASCII only. Column data is screened at
    to_device_batch; CONSTANTS are concrete at trace time and screened
    here — a non-ASCII constant routes the plan to the weight-based
    oracle (NotImplementedError -> the executor's documented fallback)."""
    for v in vals:
        if not isinstance(v, CompVal):
            continue
        b = v.const_bytes
        if b is not None and any(x >= 0x80 for x in b):
            raise NotImplementedError("non-ASCII constant under CI collation (oracle)")


def fold_words_ci(words):
    """ASCII-case-fold packed compare words (a-z -> A-Z), keeping the
    length word — general_ci collation compare on device (ref:
    pkg/util/collate generalCICollator, ASCII subset). Byte-local subtract
    of 0x20 never borrows (0x61-0x20 = 0x41 > 0)."""
    payload = words[..., :-1] ^ I64_MIN
    adj = jnp.zeros_like(payload)
    for b in range(8):
        sh = 56 - 8 * b
        byte = (payload >> sh) & 0xFF
        is_lower = (byte >= 0x61) & (byte <= 0x7A)
        adj = adj + jnp.where(is_lower, jnp.int64(0x20) << sh, jnp.int64(0))
    return jnp.concatenate([(payload - adj) ^ I64_MIN, words[..., -1:]], axis=-1)


def _words_cmp(a, b):
    """Lexicographic compare of [N, W] int64 word arrays -> (-1/0/1)[N]."""
    neq = a != b
    any_neq = neq.any(axis=-1)
    idx = jnp.argmax(neq, axis=-1)
    av = jnp.take_along_axis(a, idx[:, None], axis=-1)[:, 0]
    bv = jnp.take_along_axis(b, idx[:, None], axis=-1)[:, 0]
    lt = any_neq & (av < bv)
    gt = any_neq & (av > bv)
    return jnp.where(lt, -1, jnp.where(gt, 1, 0)).astype(jnp.int32)


def normalize_device_column(c: DeviceColumn) -> CompVal:
    """DeviceColumn -> CompVal (strings get packed compare words)."""
    if c.is_varlen():
        words = pack_string_words(c.data, c.length)
        return CompVal(words, c.null, c.ft, raw=(c.data, c.length))
    data = c.data
    if data.dtype != jnp.int64 and c.ft.eval_type() != "real":
        data = data.astype(jnp.int64)
    return CompVal(data, c.null, c.ft)


class ExprCompiler:
    """Compiles Expr trees against a fixed input schema.  `params` maps a
    lane (exec/dag.py `OPERAND_LANES`) to the program's operand array that
    `Param` nodes read their slot from (exec/builder.py passes the traced
    arguments)."""

    def __init__(self, input_fts: list[FieldType], params: dict | None = None):
        self.input_fts = input_fts
        self._params = params or {}

    # -- entry ---------------------------------------------------------------
    def run(self, exprs: list[Expr], cols: list[DeviceColumn]) -> list[CompVal]:
        """Trace `exprs` over device columns (called inside jit)."""
        self._cols = cols
        self._n = cols[0].null.shape[0] if cols else 1
        self._col_cache: dict[int, CompVal] = {}
        return [self._eval(e) for e in exprs]

    # -- dispatch ------------------------------------------------------------
    def _eval(self, e: Expr) -> CompVal:
        if isinstance(e, ColumnRef):
            return self._column(e)
        if isinstance(e, Const):
            return self._const(e)
        if isinstance(e, Param):
            if e.lane == "s":
                return self._string_const(self._params["s"][e.slot][None, :], self._params["n"][e.slot][None], e.ft)
            v = jnp.broadcast_to(self._params[e.lane][e.slot], (self._n,))
            return CompVal(v, jnp.zeros(self._n, bool), e.ft)
        if isinstance(e, ScalarFunc):
            fn = getattr(self, f"_op_{e.op}", None)
            if fn is None:
                raise NotImplementedError(f"scalar op {e.op} not implemented on device")
            return fn(e)
        raise TypeError(f"unknown expr node {e!r}")

    def _column(self, e: ColumnRef) -> CompVal:
        if e.index in self._col_cache:
            return self._col_cache[e.index]
        c = self._cols[e.index]
        if isinstance(c, CompVal):
            # pipeline stages (exec/builder.py) bind already-normalized values
            self._col_cache[e.index] = c
            return c
        v = normalize_device_column(c)
        self._col_cache[e.index] = v
        return v

    def _const(self, e: Const) -> CompVal:
        n = self._n
        d = e.datum
        if d.is_null():
            et = e.ft.eval_type()
            dt = jnp.float64 if et == "real" else jnp.int64
            return CompVal(jnp.zeros(n, dt), jnp.ones(n, bool), e.ft)
        et = e.ft.eval_type()
        if et == "string":
            b = d.val.encode() if isinstance(d.val, str) else bytes(d.val)
            data = np.zeros((1, max(1, len(b))), np.uint8)
            data[0, : len(b)] = np.frombuffer(b, np.uint8)
            return self._string_const(jnp.asarray(data), jnp.asarray(np.array([len(b)], np.int32)), e.ft, b)
        # a constant that stayed in the trace (`Const.operand()` is None, or
        # the caller did not parameterise): the same host value, baked
        v = jnp.full(n, lane_value(d, e.ft), jnp.float64 if et == "real" else jnp.int64)
        return CompVal(v, jnp.zeros(n, bool), e.ft)

    def _string_const(self, data, length, ft: FieldType, const_bytes: bytes | None = None) -> CompVal:
        """One string ([1, W] bytes, [1] length) in every lane: a baked
        constant's, with the bytes for the CI guards to read, or a `Param`
        seat's row of the string operand, which `Const.operand()` screened
        where it was bound."""
        n = self._n
        words = pack_string_words(data, length)
        return CompVal(jnp.broadcast_to(words, (n, words.shape[1])), jnp.zeros(n, bool), ft,
                       raw=(jnp.broadcast_to(data, (n, data.shape[1])), jnp.broadcast_to(length, (n,))),
                       const_bytes=const_bytes)

    # -- coercion ------------------------------------------------------------
    @staticmethod
    def _common_class(a: CompVal, b: CompVal) -> str:
        ea, eb = a.eval_type, b.eval_type
        if "string" in (ea, eb) and ea == eb:
            return "string"
        if "real" in (ea, eb):
            return "real"
        if "decimal" in (ea, eb):
            return "decimal"
        if "time" in (ea, eb):
            return "time"
        return "int"

    def _to_class(self, v: CompVal, cls: str, scale: int | None = None) -> CompVal:
        et = v.eval_type
        if cls == "real":
            if et == "real":
                return v
            if et == "string":
                data, length = string_bytes(v)
                return CompVal(parse_f64_prefix(data, length), v.null, FieldType(TypeCode.Double))
            if et == "decimal":
                return CompVal(v.value.astype(jnp.float64) / float(10 ** _scale(v.ft)), v.null, FieldType(TypeCode.Double))
            if v.ft.is_unsigned():
                # uint64 bit-pattern -> f64 without sign error
                val = v.value
                as_f = jnp.where(val >= 0, val.astype(jnp.float64), val.astype(jnp.float64) + 2.0**64)
                return CompVal(as_f, v.null, FieldType(TypeCode.Double))
            return CompVal(v.value.astype(jnp.float64), v.null, FieldType(TypeCode.Double))
        if cls == "decimal":
            s = _scale(v.ft) if scale is None else scale
            if et == "string":
                # via double (MySQL parses the numeric prefix first)
                v = self._to_class(v, "real")
                et = "real"
            if et == "decimal":
                return self._rescale_dec(v, s)
            if et == "int":
                from ..types import new_decimal

                ft = new_decimal(20, 0)
                vv = CompVal(v.value, v.null, ft)
                return self._rescale_dec(vv, s)
            if et == "real":
                ft = FieldType(TypeCode.NewDecimal, decimal=s)
                x = v.value * float(10 ** s)
                # MySQL rounds half away from zero, not half-to-even.
                # KNOWN DEVIATION: MySQL/TiDB convert double->decimal via the
                # shortest decimal repr (so the double nearest 16.405 rounds
                # like "16.405"); this kernel rounds the binary value, which
                # can differ by 1 ulp of the target scale on repr midpoints.
                scaled = jnp.where(x >= 0, jnp.floor(x + 0.5), jnp.ceil(x - 0.5)).astype(jnp.int64)
                return CompVal(scaled, v.null, ft)
        if cls in ("int", "time"):
            return v
        raise NotImplementedError(f"coerce {et} -> {cls}")

    @staticmethod
    def _rescale_dec(v: CompVal, s: int) -> CompVal:
        cur = _scale(v.ft)
        ft = v.ft.clone()
        ft.tp = TypeCode.NewDecimal
        ft.decimal = s
        if s == cur:
            return CompVal(v.value, v.null, ft)
        if s > cur:
            return CompVal(v.value * _pow10(s - cur), v.null, ft)
        return CompVal(_round_div(v.value, _pow10(cur - s)), v.null, ft)

    # -- arithmetic ----------------------------------------------------------
    def _arith(self, e: ScalarFunc, int_fn, real_fn, dec_fn):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        cls = self._common_class(a, b)
        null = a.null | b.null
        if cls == "real":
            a, b = self._to_class(a, "real"), self._to_class(b, "real")
            out = real_fn(a.value, b.value)
            return CompVal(out, null, e.ft)
        if cls == "decimal":
            return dec_fn(a, b, null, e.ft)
        out = int_fn(a.value, b.value)
        return CompVal(out, null, e.ft)

    def _dec_addsub(self, sign: int):
        def fn(a: CompVal, b: CompVal, null, ft):
            s = max(_scale(a.ft), _scale(b.ft))
            av = self._to_class(a, "decimal", s).value
            bv = self._to_class(b, "decimal", s).value
            out = av + sign * bv
            return self._rescale_dec(CompVal(out, null, FieldType(TypeCode.NewDecimal, decimal=s)), _scale(ft))

        return fn

    def _op_plus(self, e):
        return self._arith(e, lambda a, b: a + b, lambda a, b: a + b, self._dec_addsub(1))

    def _op_minus(self, e):
        return self._arith(e, lambda a, b: a - b, lambda a, b: a - b, self._dec_addsub(-1))

    def _op_mul(self, e):
        def dec(a: CompVal, b: CompVal, null, ft):
            av, bv = self._to_class(a, "decimal"), self._to_class(b, "decimal")
            s = _scale(av.ft) + _scale(bv.ft)
            out = av.value * bv.value
            return self._rescale_dec(CompVal(out, null, FieldType(TypeCode.NewDecimal, decimal=s)), _scale(ft))

        return self._arith(e, lambda a, b: a * b, lambda a, b: a * b, dec)

    def _op_div(self, e):
        """`/`: reals divide; ints & decimals use decimal division with the
        +4 scale increment (ref: cop_handler.go:350-354, mydecimal DivFracIncr).
        Division by zero yields NULL."""
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        if self._common_class(a, b) == "real":
            a, b = self._to_class(a, "real"), self._to_class(b, "real")
            zero = b.value == 0.0
            null = a.null | b.null | zero
            out = a.value / jnp.where(zero, 1.0, b.value)
            return CompVal(out, null, e.ft)
        av, bv = self._to_class(a, "decimal"), self._to_class(b, "decimal")
        sr = _scale(e.ft)
        k = sr - _scale(av.ft) + _scale(bv.ft)
        zero = bv.value == 0
        null = a.null | b.null | zero
        num = av.value * _pow10(max(k, 0))
        den = jnp.where(zero, jnp.int64(1), bv.value)
        out = _round_div(num, den)
        if k < 0:
            out = _round_div(out, _pow10(-k))
        return CompVal(out, null, e.ft)

    def _op_intdiv(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        if self._common_class(a, b) == "real":
            av, bv = self._to_class(a, "real"), self._to_class(b, "real")
            zero = bv.value == 0.0
            null = a.null | b.null | zero
            q = av.value / jnp.where(zero, 1.0, bv.value)
            out = jnp.trunc(q).astype(jnp.int64)
            return CompVal(out, null, e.ft)
        if self._common_class(a, b) == "decimal":
            av, bv = self._to_class(a, "decimal"), self._to_class(b, "decimal")
            zero = bv.value == 0
            null = a.null | b.null | zero
            sa, sb = _scale(av.ft), _scale(bv.ft)
            num, den = av.value * _pow10(sb), bv.value * _pow10(sa)
            den = jnp.where(zero, jnp.int64(1), den)
            q = jnp.abs(num) // jnp.abs(den)  # truncate toward zero
            out = jnp.where((num < 0) ^ (den < 0), -q, q)
            return CompVal(out, null, e.ft)
        zero = b.value == 0
        null = a.null | b.null | zero
        den = jnp.where(zero, jnp.int64(1), b.value)
        q = jnp.abs(a.value) // jnp.abs(den)
        out = jnp.where((a.value < 0) ^ (den < 0), -q, q)
        return CompVal(out, null, e.ft)

    def _op_mod(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        if self._common_class(a, b) == "real":
            a, b = self._to_class(a, "real"), self._to_class(b, "real")
            zero = b.value == 0.0
            null = a.null | b.null | zero
            out = jnp.fmod(a.value, jnp.where(zero, 1.0, b.value))
            return CompVal(out, null, e.ft)
        if self._common_class(a, b) == "decimal":
            s = max(_scale(a.ft), _scale(b.ft))
            av = self._to_class(a, "decimal", s).value
            bv = self._to_class(b, "decimal", s).value
            zero = bv == 0
            null = a.null | b.null | zero
            den = jnp.where(zero, jnp.int64(1), bv)
            r = jnp.abs(av) % jnp.abs(den)
            out = jnp.where(av < 0, -r, r)  # MySQL mod takes dividend sign
            return CompVal(out, null, e.ft)
        zero = b.value == 0
        null = a.null | b.null | zero
        den = jnp.where(zero, jnp.int64(1), b.value)
        r = jnp.abs(a.value) % jnp.abs(den)
        out = jnp.where(a.value < 0, -r, r)
        return CompVal(out, null, e.ft)

    def _op_unaryminus(self, e):
        a = self._eval(e.args[0])
        return CompVal(-a.value, a.null, e.ft)

    def _op_abs(self, e):
        a = self._eval(e.args[0])
        return CompVal(jnp.abs(a.value), a.null, e.ft)

    # -- comparison ----------------------------------------------------------
    def _cmp(self, a: CompVal, b: CompVal):
        """Return (-1/0/1)[N] semantic comparison of a vs b."""
        cls = self._common_class(a, b)
        if cls == "string":
            av, bv = a.value, b.value
            if a.ft.is_ci() or b.ft.is_ci():
                _ci_ascii_guard(a, b)
                av, bv = fold_words_ci(av), fold_words_ci(bv)
            return _words_cmp(av, bv)
        if cls == "real":
            av, bv = self._to_class(a, "real").value, self._to_class(b, "real").value
            return (jnp.sign(av - bv)).astype(jnp.int32)
        if cls == "decimal":
            s = max(_scale(a.ft), _scale(b.ft))
            av = self._to_class(a, "decimal", s).value
            bv = self._to_class(b, "decimal", s).value
            return jnp.sign(av - bv).astype(jnp.int32)
        # int class: handle signedness (ref: builtin_compare.go CompareInt)
        au, bu = a.ft.is_unsigned(), b.ft.is_unsigned()
        av, bv = a.value, b.value
        if au and bu:
            av, bv = _flip(av), _flip(bv)
            return jnp.where(av < bv, -1, jnp.where(av > bv, 1, 0)).astype(jnp.int32)
        if not au and not bu:
            return jnp.where(av < bv, -1, jnp.where(av > bv, 1, 0)).astype(jnp.int32)
        if au and not bu:
            # a unsigned vs b signed: b<0 => a>b; else unsigned compare
            c = jnp.where(_flip(av) < _flip(bv), -1, jnp.where(_flip(av) > _flip(bv), 1, 0))
            return jnp.where(bv < 0, 1, c).astype(jnp.int32)
        c = jnp.where(_flip(av) < _flip(bv), -1, jnp.where(_flip(av) > _flip(bv), 1, 0))
        return jnp.where(av < 0, -1, c).astype(jnp.int32)

    def _cmp_op(self, e: ScalarFunc, pred):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        c = self._cmp(a, b)
        out = pred(c).astype(jnp.int64)
        return CompVal(out, a.null | b.null, e.ft)

    def _op_eq(self, e):
        return self._cmp_op(e, lambda c: c == 0)

    def _op_ne(self, e):
        return self._cmp_op(e, lambda c: c != 0)

    def _op_lt(self, e):
        return self._cmp_op(e, lambda c: c < 0)

    def _op_le(self, e):
        return self._cmp_op(e, lambda c: c <= 0)

    def _op_gt(self, e):
        return self._cmp_op(e, lambda c: c > 0)

    def _op_ge(self, e):
        return self._cmp_op(e, lambda c: c >= 0)

    def _op_nulleq(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        c = self._cmp(a, b)
        both_null = a.null & b.null
        eq = (c == 0) & ~a.null & ~b.null
        return CompVal((both_null | eq).astype(jnp.int64), jnp.zeros_like(a.null), e.ft)

    def _op_in(self, e):
        a = self._eval(e.args[0])
        hit = jnp.zeros(self._n, bool)
        any_null = jnp.zeros(self._n, bool)
        for arg in e.args[1:]:
            b = self._eval(arg)
            c = self._cmp(a, b)
            hit = hit | ((c == 0) & ~b.null)
            any_null = any_null | b.null
        # a NULL lane's value is garbage — never let it match
        hit = hit & ~a.null
        # NULL if lhs null, or no hit with some NULL operand (MySQL IN)
        null = a.null | (~hit & any_null)
        return CompVal(hit.astype(jnp.int64), null, e.ft)

    def _op_between(self, e):
        a, lo, hi = (self._eval(x) for x in e.args)
        c1, c2 = self._cmp(a, lo), self._cmp(a, hi)
        out = ((c1 >= 0) & (c2 <= 0)).astype(jnp.int64)
        return CompVal(out, a.null | lo.null | hi.null, e.ft)

    # -- logical -------------------------------------------------------------
    @staticmethod
    def _truth(v: CompVal):
        """MySQL truthiness of a value lane (nonzero = true)."""
        if v.eval_type == "real":
            return v.value != 0.0
        if v.value.ndim == 2:
            # MySQL string truthiness parses a leading number ('0'→false,
            # 'abc'→false); no device parse yet, so refuse pushdown — the
            # whitelist gate routes these to the host path.
            raise NotImplementedError("logical op over string operand not on device")
        return v.value != 0

    @staticmethod
    def _sel(cond, a: CompVal, b: CompVal, av, bv):
        """jnp.where that handles 2-D string word lanes and carries raw."""
        if av.ndim == 2:
            out = jnp.where(cond[:, None], av, bv)
            raw = None
            if a.raw is not None and b.raw is not None:
                ad, al = a.raw
                bd, bl = b.raw
                w = max(ad.shape[1], bd.shape[1])
                if ad.shape[1] < w:
                    ad = jnp.pad(ad, ((0, 0), (0, w - ad.shape[1])))
                if bd.shape[1] < w:
                    bd = jnp.pad(bd, ((0, 0), (0, w - bd.shape[1])))
                raw = (jnp.where(cond[:, None], ad, bd), jnp.where(cond, al, bl))
            return out, raw
        return jnp.where(cond, av, bv), None

    def _op_and(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        ta, tb = self._truth(a), self._truth(b)
        f = (~ta & ~a.null) | (~tb & ~b.null)
        null = ~f & (a.null | b.null)
        return CompVal((~f & ~null).astype(jnp.int64), null, e.ft)

    def _op_or(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        ta, tb = self._truth(a), self._truth(b)
        t = (ta & ~a.null) | (tb & ~b.null)
        null = ~t & (a.null | b.null)
        return CompVal(t.astype(jnp.int64), null, e.ft)

    def _op_not(self, e):
        a = self._eval(e.args[0])
        return CompVal((~self._truth(a)).astype(jnp.int64), a.null, e.ft)

    def _op_xor(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        out = (self._truth(a) ^ self._truth(b)).astype(jnp.int64)
        return CompVal(out, a.null | b.null, e.ft)

    # -- null handling / control ---------------------------------------------
    def _op_isnull(self, e):
        a = self._eval(e.args[0])
        return CompVal(a.null.astype(jnp.int64), jnp.zeros_like(a.null), e.ft)

    def _op_ifnull(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        av = self._coerce_result(a, e.ft).value
        bv = self._coerce_result(b, e.ft).value
        out, raw = self._sel(~a.null, a, b, av, bv)
        return CompVal(out, a.null & b.null, e.ft, raw=raw)

    def _op_if(self, e):
        c, a, b = (self._eval(x) for x in e.args)
        cond = self._truth(c) & ~c.null
        av = self._coerce_result(a, e.ft).value
        bv = self._coerce_result(b, e.ft).value
        out, raw = self._sel(cond, a, b, av, bv)
        null = jnp.where(cond, a.null, b.null)
        return CompVal(out, null, e.ft, raw=raw)

    def _op_case(self, e):
        """case [when1, then1, when2, then2, ..., else?]."""
        args = e.args
        pairs = []
        i = 0
        while i + 1 < len(args):
            pairs.append((args[i], args[i + 1]))
            i += 2
        els = self._eval(args[i]) if i < len(args) else None
        if els is not None:
            out = self._coerce_result(els, e.ft).value
            null = els.null
        else:
            dt = jnp.float64 if e.ft.eval_type() == "real" else jnp.int64
            out = jnp.zeros(self._n, dt)
            null = jnp.ones(self._n, bool)
        for cond_e, then_e in reversed(pairs):
            c = self._eval(cond_e)
            t = self._eval(then_e)
            hit = self._truth(c) & ~c.null
            tv = self._coerce_result(t, e.ft).value
            cond2 = hit[:, None] if tv.ndim == 2 else hit
            out = jnp.where(cond2, tv, out)
            null = jnp.where(hit, t.null, null)
        return CompVal(out, null, e.ft)

    def _op_coalesce(self, e):
        vals = [self._eval(a) for a in e.args]
        out = self._coerce_result(vals[-1], e.ft).value
        null = vals[-1].null
        for v in reversed(vals[:-1]):
            vv = self._coerce_result(v, e.ft).value
            cond = v.null[:, None] if vv.ndim == 2 else v.null
            out = jnp.where(cond, out, vv)
            null = jnp.where(v.null, null, jnp.zeros_like(null))
        return CompVal(out, null, e.ft)

    def _coerce_result(self, v: CompVal, ft: FieldType) -> CompVal:
        cls = ft.eval_type()
        if cls == "decimal":
            return self._to_class(v, "decimal", _scale(ft))
        if cls == "real":
            return self._to_class(v, "real")
        return v

    # -- cast ----------------------------------------------------------------
    def _op_cast(self, e):
        a = self._eval(e.args[0])
        src, dst = a.eval_type, e.ft.eval_type()
        if dst == "real":
            return CompVal(self._to_class(a, "real").value, a.null, e.ft)
        if dst == "decimal":
            return CompVal(self._to_class(a, "decimal", _scale(e.ft)).value, a.null, e.ft)
        if dst == "int":
            if src == "string":
                a = self._to_class(a, "real")
                src = "real"
            if src == "real":
                out = jnp.round(a.value).astype(jnp.int64)  # MySQL rounds
                return CompVal(out, a.null, e.ft)
            if src == "decimal":
                out = _round_div(a.value, _pow10(_scale(a.ft)))
                return CompVal(out, a.null, e.ft)
            return CompVal(a.value, a.null, e.ft)
        if dst == "time" and src == "time":
            return CompVal(a.value, a.null, e.ft)
        if dst == "string" and src == "string":
            return CompVal(a.value, a.null, e.ft, raw=a.raw)
        raise NotImplementedError(f"cast {src} -> {dst} not on device")

    # -- math ----------------------------------------------------------------
    def _op_ceil(self, e):
        a = self._eval(e.args[0])
        if a.eval_type == "real":
            return CompVal(jnp.ceil(a.value), a.null, e.ft)
        if a.eval_type == "decimal":
            p = _pow10(_scale(a.ft))
            q = jnp.where(a.value >= 0, (a.value + p - 1) // p, -((-a.value) // p))
            return CompVal(q, a.null, e.ft)
        return CompVal(a.value, a.null, e.ft)

    def _op_floor(self, e):
        a = self._eval(e.args[0])
        if a.eval_type == "real":
            return CompVal(jnp.floor(a.value), a.null, e.ft)
        if a.eval_type == "decimal":
            p = _pow10(_scale(a.ft))
            q = jnp.where(a.value >= 0, a.value // p, -((-a.value + p - 1) // p))
            return CompVal(q, a.null, e.ft)
        return CompVal(a.value, a.null, e.ft)

    def _op_round(self, e):
        a = self._eval(e.args[0])
        nd = 0
        if len(e.args) > 1:
            c = e.args[1]
            if isinstance(c, Const) and not c.datum.is_null():
                nd = int(c.datum.val)
            else:
                raise NotImplementedError("round with non-constant digits")
        if a.eval_type == "real":
            p = float(10 ** nd)
            v = a.value * p
            out = jnp.where(v >= 0, jnp.floor(v + 0.5), jnp.ceil(v - 0.5)) / p
            return CompVal(out, a.null, e.ft)
        if a.eval_type == "decimal":
            tgt = min(max(nd, 0), _scale(a.ft))
            r = self._rescale_dec(a, tgt)
            return CompVal(self._rescale_dec(r, _scale(e.ft)).value, a.null, e.ft)
        if nd >= 0:
            return CompVal(a.value, a.null, e.ft)
        p = _pow10(-nd)
        return CompVal(_round_div(a.value, p) * p, a.null, e.ft)

    def _op_sqrt(self, e):
        a = self._to_class(self._eval(e.args[0]), "real")
        neg = a.value < 0
        out = jnp.sqrt(jnp.where(neg, 0.0, a.value))
        return CompVal(out, a.null | neg, e.ft)

    def _op_exp(self, e):
        a = self._to_class(self._eval(e.args[0]), "real")
        return CompVal(jnp.exp(a.value), a.null, e.ft)

    def _op_ln(self, e):
        a = self._to_class(self._eval(e.args[0]), "real")
        bad = a.value <= 0
        return CompVal(jnp.log(jnp.where(bad, 1.0, a.value)), a.null | bad, e.ft)

    _op_log = _op_ln

    def _op_pow(self, e):
        a = self._to_class(self._eval(e.args[0]), "real")
        b = self._to_class(self._eval(e.args[1]), "real")
        return CompVal(jnp.power(a.value, b.value), a.null | b.null, e.ft)

    def _op_sign(self, e):
        a = self._eval(e.args[0])
        out = jnp.sign(a.value).astype(jnp.int64)
        return CompVal(out, a.null, e.ft)

    # -- bit ops (int64 lanes) -----------------------------------------------
    def _bitop(self, e, fn):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        return CompVal(fn(a.value, b.value), a.null | b.null, e.ft)

    def _op_bitand(self, e):
        return self._bitop(e, lambda a, b: a & b)

    def _op_bitor(self, e):
        return self._bitop(e, lambda a, b: a | b)

    def _op_bitxor(self, e):
        return self._bitop(e, lambda a, b: a ^ b)

    def _op_bitneg(self, e):
        a = self._eval(e.args[0])
        return CompVal(~a.value, a.null, e.ft)

    def _op_shiftleft(self, e):
        return self._bitop(e, lambda a, b: jnp.where((b >= 64) | (b < 0), jnp.int64(0), a << jnp.clip(b, 0, 63)))

    def _op_shiftright(self, e):
        # logical (unsigned) shift, as MySQL >> on BIGINT UNSIGNED
        return self._bitop(
            e,
            lambda a, b: jnp.where(
                (b >= 64) | (b < 0),
                jnp.int64(0),
                (a.astype(jnp.uint64) >> jnp.clip(b, 0, 63).astype(jnp.uint64)).astype(jnp.int64),
            ),
        )

    # -- string --------------------------------------------------------------
    def _op_length(self, e):
        a = self._eval(e.args[0])
        if a.raw is None:
            raise NotImplementedError("length() needs raw string column")
        return CompVal(a.raw[1].astype(jnp.int64), a.null, e.ft)

    def _op_strcmp(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])
        av, bv = a.value, b.value
        if a.ft.is_ci() or b.ft.is_ci():
            _ci_ascii_guard(a, b)
            av, bv = fold_words_ci(av), fold_words_ci(bv)
        return CompVal(_words_cmp(av, bv).astype(jnp.int64), a.null | b.null, e.ft)

    def _op_like(self, e):
        """LIKE with constant pattern; device support for exact / 'prefix%' /
        '%suffix' is TODO — currently exact and prefix% patterns."""
        a = self._eval(e.args[0])
        pat = e.args[1]
        if not isinstance(pat, Const):
            raise NotImplementedError("LIKE with non-constant pattern")
        p = pat.datum.val
        p = p if isinstance(p, str) else p.decode()
        if a.raw is None:
            raise NotImplementedError("LIKE needs raw string column")
        data, length = a.raw
        if a.ft.is_ci() or pat.ft.is_ci():
            # general_ci LIKE: ASCII fold on BOTH sides (matching the
            # compare()/sort-key fold); a non-ASCII pattern goes to the
            # weight-based oracle
            from ..expr.eval_ref import _ascii_upper

            if any(ord(c) >= 0x80 for c in p):
                raise NotImplementedError("non-ASCII CI LIKE pattern (oracle)")
            hit = (data >= 0x61) & (data <= 0x7A)
            data = jnp.where(hit, data - 0x20, data)
            p = _ascii_upper(p)
        import numpy as np

        if p.endswith("%") and "%" not in p[:-1] and "_" not in p:
            prefix = p[:-1].encode()
            out = self._prefix_match(data, length, prefix)
        elif "%" not in p and "_" not in p:
            exact = p.encode()
            out = self._prefix_match(data, length, exact) & (length == len(exact))
        else:
            raise NotImplementedError(f"LIKE pattern {p!r} not on device yet")
        return CompVal(out.astype(jnp.int64), a.null, e.ft)

    @staticmethod
    def _prefix_match(data, length, prefix: bytes):
        import numpy as np

        k = len(prefix)
        if k == 0:
            return jnp.ones(data.shape[0], bool)
        w = data.shape[1]
        if k > w:
            return jnp.zeros(data.shape[0], bool)
        pref = jnp.asarray(np.frombuffer(prefix, np.uint8))
        eq = (data[:, :k] == pref[None, :]).all(axis=1)
        return eq & (length >= k)

    def _op_substr(self, e):
        """SUBSTR(s, pos[, len]) — per-row byte shift via gather."""
        a = self._eval(e.args[0])
        data, length = string_bytes(a)
        pos_cv = self._eval(e.args[1])
        pos = pos_cv.value.astype(jnp.int32)
        null = a.null | pos_cv.null
        # MySQL: 1-based; negative counts from the end; 0 -> ''
        start = jnp.where(pos > 0, pos - 1, length + pos)
        bad = (pos == 0) | (start < 0)
        start = jnp.clip(start, 0, length)
        avail = jnp.maximum(length - start, 0)
        if len(e.args) > 2:
            want_cv = self._eval(e.args[2])
            null = null | want_cv.null
            new_len = jnp.clip(want_cv.value.astype(jnp.int32), 0, avail)
        else:
            new_len = avail
        new_len = jnp.where(bad, 0, new_len)
        w = data.shape[1]
        idx = jnp.clip(jnp.arange(w)[None, :] + start[:, None], 0, w - 1)
        shifted = jnp.take_along_axis(data, idx, axis=1)
        shifted = jnp.where(jnp.arange(w)[None, :] < new_len[:, None], shifted, 0)
        return self._string_result(shifted, new_len, null, e.ft)

    def _string_result(self, data, length, null, ft):
        return CompVal(pack_string_words(data, length), null, ft, raw=(data, length))

    def _op_upper(self, e):
        return self._case_fold(e, upper=True)

    def _op_lower(self, e):
        return self._case_fold(e, upper=False)

    def _case_fold(self, e, upper: bool):
        a = self._eval(e.args[0])
        data, length = string_bytes(a)
        if upper:
            hit = (data >= 0x61) & (data <= 0x7A)
            out = jnp.where(hit, data - 0x20, data)
        else:
            hit = (data >= 0x41) & (data <= 0x5A)
            out = jnp.where(hit, data + 0x20, data)
        return self._string_result(out, length, a.null, e.ft)

    def _op_concat(self, e):
        """CONCAT(...) — pairwise fold; NULL if any arg NULL (MySQL)."""
        args = [self._as_string(self._eval(x)) for x in e.args]
        out = args[0]
        for b in args[1:]:
            out = self._concat2(out, b)
        d, ln = out.raw
        return self._string_result(d, ln, out.null, e.ft)

    def _as_string(self, a: CompVal) -> CompVal:
        if a.value.ndim == 2:
            data, length = string_bytes(a)
            return CompVal(a.value, a.null, a.ft, raw=(data, length))
        raise NotImplementedError("concat of non-string operands on device (cast first)")

    @staticmethod
    def _concat2(a: CompVal, b: CompVal) -> CompVal:
        da, la = a.raw
        db, lb = b.raw
        wa, wb = da.shape[1], db.shape[1]
        w = wa + wb
        pos = jnp.arange(w)[None, :]
        a_pad = jnp.pad(da, ((0, 0), (0, w - wa)))
        b_pad = jnp.pad(db, ((0, 0), (0, w - wb)))
        from_b_idx = jnp.clip(pos - la[:, None], 0, w - 1)
        b_shift = jnp.take_along_axis(b_pad, from_b_idx, axis=1)
        out = jnp.where(pos < la[:, None], a_pad, b_shift)
        ln = la + lb
        out = jnp.where(pos < ln[:, None], out, 0)
        return CompVal(a.value, a.null | b.null, a.ft, raw=(out, ln.astype(jnp.int32)))

    def _op_trim(self, e):
        return self._trim(e, left=True, right=True)

    def _op_ltrim(self, e):
        return self._trim(e, left=True, right=False)

    def _op_rtrim(self, e):
        return self._trim(e, left=False, right=True)

    def _trim(self, e, left: bool, right: bool):
        a = self._eval(e.args[0])
        data, length = string_bytes(a)
        w = data.shape[1]
        pos = jnp.arange(w)[None, :]
        in_str = pos < length[:, None]
        is_sp = (data == 0x20) & in_str
        lead = jnp.zeros(data.shape[0], jnp.int32)
        if left:
            # leading spaces: cumulative product of the space mask
            run = jnp.cumprod(jnp.where(in_str, is_sp, True).astype(jnp.int32), axis=1)
            lead = jnp.minimum((run * in_str.astype(jnp.int32)).sum(axis=1), length)
        trail = jnp.zeros(data.shape[0], jnp.int32)
        if right:
            # walk from the end: src index for the k-th-from-last byte
            src = length[:, None] - 1 - pos
            rev_bytes = jnp.take_along_axis(data, jnp.clip(src, 0, w - 1), axis=1)
            is_sp_end = jnp.where(src >= 0, rev_bytes == 0x20, False)
            run_t = jnp.cumprod(is_sp_end.astype(jnp.int32), axis=1)
            trail = jnp.minimum(run_t.sum(axis=1), length)
        new_len = jnp.maximum(length - lead - trail, 0)
        idx = jnp.clip(pos + lead[:, None], 0, w - 1)
        shifted = jnp.take_along_axis(data, idx, axis=1)
        shifted = jnp.where(pos < new_len[:, None], shifted, 0)
        return self._string_result(shifted, new_len.astype(jnp.int32), a.null, e.ft)

    def _op_replace(self, e):
        raise NotImplementedError("replace() is host-only (data-dependent lengths); planner keeps it at root")

    # -- date arithmetic (vectorized civil-calendar math) ---------------------
    def _op_date_add(self, e):
        return self._date_shift(e, +1)

    def _op_date_sub(self, e):
        return self._date_shift(e, -1)

    def _date_shift(self, e, sign: int):
        """packed datetime +/- INTERVAL n unit (ref: builtin_time date_add;
        semantics types/mytime.py datetime_add — Hinnant civil-from-days)."""
        d = self._eval(e.args[0])
        n = self._eval(e.args[1])
        unit = e.args[2].datum.val  # const string (planner contract)
        p = d.value
        micro = p & 0xFFFFFF
        rest = p >> 24
        hms = rest & ((1 << 17) - 1)
        ymd = rest >> 17
        day = ymd & 31
        ym = ymd >> 5
        y, m = ym // 13, ym % 13
        sec, minute, hour = hms & 63, (hms >> 6) & 63, hms >> 12
        nn = sign * n.value.astype(jnp.int64)
        from ..types.mytime import _UNIT_SECONDS, add_months

        if unit in _UNIT_SECONDS:
            total = _days_from_ymd(y, m, day) * 86400 + hour * 3600 + minute * 60 + sec + nn * _UNIT_SECONDS[unit]
            days, secs = total // 86400, total % 86400
            y, m, day = _ymd_from_days(days)
            hour, minute, sec = secs // 3600, (secs // 60) % 60, secs % 60
        elif unit in ("month", "quarter", "year"):
            months = nn * {"month": 1, "quarter": 3, "year": 12}[unit]
            y, m, day = add_months(y, m, day, months)
        else:
            raise NotImplementedError(f"interval unit {unit!r}")
        packed = (((y * 13 + m) << 5 | day) << 17 | (hour << 12 | minute << 6 | sec)) << 24 | micro
        return CompVal(packed, d.null | n.null, e.ft)

    def _op_datediff(self, e):
        a, b = self._eval(e.args[0]), self._eval(e.args[1])

        def days_of(v):
            ymd = v.value >> 41
            day = ymd & 31
            ym = ymd >> 5
            return _days_from_ymd(ym // 13, ym % 13, day)

        return CompVal(days_of(a) - days_of(b), a.null | b.null, e.ft)

    # -- time extraction (packed layout, types/mytime.py) ---------------------
    def _time_parts(self, a: CompVal):
        packed = a.value
        ymd = packed >> 41
        ym = ymd >> 5
        return packed, ymd, ym

    def _op_year(self, e):
        a = self._eval(e.args[0])
        _, _, ym = self._time_parts(a)
        return CompVal((ym // 13).astype(jnp.int64), a.null, e.ft)

    def _op_month(self, e):
        a = self._eval(e.args[0])
        _, _, ym = self._time_parts(a)
        return CompVal((ym % 13).astype(jnp.int64), a.null, e.ft)

    def _op_day(self, e):
        a = self._eval(e.args[0])
        _, ymd, _ = self._time_parts(a)
        return CompVal((ymd & 31).astype(jnp.int64), a.null, e.ft)

    def _op_hour(self, e):
        a = self._eval(e.args[0])
        hms = (a.value >> 24) & ((1 << 17) - 1)
        return CompVal((hms >> 12).astype(jnp.int64), a.null, e.ft)

    def _op_minute(self, e):
        a = self._eval(e.args[0])
        hms = (a.value >> 24) & ((1 << 17) - 1)
        return CompVal(((hms >> 6) & 63).astype(jnp.int64), a.null, e.ft)

    def _op_second(self, e):
        a = self._eval(e.args[0])
        hms = (a.value >> 24) & ((1 << 17) - 1)
        return CompVal((hms & 63).astype(jnp.int64), a.null, e.ft)

    def _op_to_days(self, e):
        """Days since year 0 (MySQL TO_DAYS) via civil-day arithmetic."""
        a = self._eval(e.args[0])
        _, ymd, ym = self._time_parts(a)
        y = ym // 13
        m = ym % 13
        d = ymd & 31
        # days from year 0: MySQL calcDaynr (ref: pkg/types/mytime.go calcDaynr)
        delsum = 365 * y + 31 * (m - 1) + d
        adj = jnp.where(m <= 2, 0, (0.4 * m.astype(jnp.float64) + 2.3).astype(jnp.int64))
        delsum = jnp.where(m <= 2, delsum, delsum - adj)
        yy = jnp.where(m <= 2, y - 1, y)
        out = delsum + yy // 4 - yy // 100 + yy // 400
        return CompVal(out.astype(jnp.int64), a.null, e.ft)

    def _op_weekday(self, e):
        a = self._eval(e.args[0])
        days = self._op_to_days(ScalarFunc("to_days", (e.args[0],), e.ft))
        return CompVal((days.value + 5) % 7, a.null, e.ft)

    def _op_extract(self, e):
        unit = e.args[0]
        if not isinstance(unit, Const):
            raise NotImplementedError
        u = str(unit.datum.val).lower()
        sub = ScalarFunc(u, (e.args[1],), e.ft)
        return self._eval(sub)


@dataclass
class CompiledExpr:
    """A jit-compiled projection over an input schema."""

    fn: Callable
    out_fts: list[FieldType]


def compile_exprs(input_fts: list[FieldType], exprs: list[Expr]) -> CompiledExpr:
    comp = ExprCompiler(input_fts)

    @jax.jit
    def run(cols):
        vals = comp.run(exprs, cols)
        return [(v.value, v.null) for v in vals]

    return CompiledExpr(run, [e.ft for e in exprs])
