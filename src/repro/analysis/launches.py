"""Launches-per-frame accounting: count Pallas kernel dispatches in a jaxpr.

The one-launch trunk (`kernels/frame_trunk`) is a launch-topology change —
O(stages x role-maps) Pallas dispatches per frame collapsing to ONE trunk
launch — so `tests/test_frame_trunk.py` pins the number.  Counting is
static: trace the program with `jax.make_jaxpr` and
walk every equation (recursing through pjit/scan/cond sub-jaxprs) for the
`pallas_call` primitive.  This counts launches in the PROGRAM, which under
jit is exactly launches-per-call; it is mode-independent (interpret vs
compiled lower the same jaxpr) and costs one trace, no execution.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
from jax.extend import core as jex_core


def _subjaxprs(params: dict):
    """Sub-jaxprs hiding in an eqn's params (pjit jaxpr=..., scan/cond
    branches=[...], custom_* call_jaxpr=...)."""
    for v in params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for item in v:
                if isinstance(item, jex_core.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, jex_core.Jaxpr):
                    yield item


def _count_in_jaxpr(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for sub in _subjaxprs(eqn.params):
            n += _count_in_jaxpr(sub)
    return n


def count_pallas_launches(fn: Callable, *args: Any, **kwargs: Any) -> int:
    """Number of `pallas_call` dispatches in one call of `fn(*args)`."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _count_in_jaxpr(closed.jaxpr)
