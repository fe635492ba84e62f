"""Stable names for the device programs' operations.

Every staged program traces under a top ``jax.named_scope`` of its own
(``bls.gather``, ``bls.stage1``, ``bls.stage2``, ``bls.stage3``) and
its algorithmic pieces under inner ones (``sqrt``, ``miller_loop``,
...). A scope lands in each operation's ``op_name`` metadata, which the
profiler trace carries beside the device time of the operation, so a
reduction of the trace finds a piece of the program by name whatever
number XLA gives its loop (``while.24``) in a build. Scopes change
metadata only: the optimised programs keep their instructions.
"""

from __future__ import annotations

import functools

import jax


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``.
    A fresh scope object per call: one object keeps the name stack it
    replaced, so it cannot be shared between concurrent traces."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap
