"""jax-audit true positives: an integer program that leaks float64, a
builder whose closure captures a mutating Python scalar (every build
traces a different jaxpr — the ProgramCache multiplies silently), and a
program that bakes a statement's literal into its trace (every fresh
literal is a program of its own)."""

import itertools

import numpy as np

_counter = itertools.count(1)


def _args():
    return [np.arange(8, dtype=np.int64)]


def _f64_leak():
    import jax.numpy as jnp

    def fn(x):
        # BAD: int64 input promoted to float64 inside the program
        return (x.astype(jnp.float64) * 1.5).sum()

    return fn, _args()


def _closure_scalar():
    salt = next(_counter)  # BAD: baked into the trace, changes per build

    def fn(x):
        return x + salt

    return fn, _args()


def _baked_literal(literal):
    def make():
        def fn(x):
            # BAD: the statement's literal is a constant of the trace, not an operand
            return (x > literal).sum()

        return fn, _args()

    return make


JAX_AUDIT_CATALOG = [
    {"name": "f64-leak", "make": _f64_leak, "line": 17},
    {"name": "closure-scalar", "make": _closure_scalar, "line": 27},
    {"name": "baked-literal", "make": _baked_literal(2), "make_other": _baked_literal(7), "line": 37},
]
