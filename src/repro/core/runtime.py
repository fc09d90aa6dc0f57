"""Process-wide kernel-execution switches and the compile cache location.

Every Pallas wrapper in kernels/*/ops.py takes `interpret: bool | None`
and resolves `None` against this module's default, so the whole stack
(backend registry -> FcnSweep -> StreamingPipeline -> engines) moves
between the CPU interpreter and compiled TPU kernels together.

The default comes from the platform: the Pallas interpreter on a CPU
backend (which cannot compile TPU kernels), compiled kernels everywhere
else.  For this repo's integer kernels interpret mode is bit-identical to
compiled mode (see kernels/fixed_conv/kernel.py), so CPU test batteries pin
the same words the chip computes.  `set_interpret` overrides the platform
choice for the rest of the process, e.g. to compile TPU kernels ahead of
time on a CPU host:

    from repro.core import runtime
    runtime.set_interpret(False)

Why a module-level flag instead of threading a kwarg through every layer:
the flag is resolved in each wrapper's THIN UN-JITTED entry point, before
`jax.jit` ever sees it, so a changed default cannot be baked stale into a
compiled executable.  `set_interpret` still clears jit caches (and any
registered model-level caches, e.g. the FCN sweep's per-geometry program
cache) so previously compiled programs from the old mode are dropped.

Every XLA backend compile in the process is counted in the metrics
registry (`jax_compiles`, `jax_compile_seconds`) by one `jax.monitoring`
listener, registered when this module is first imported: a compile inside
a serving window shows as a step of the counter.
"""
from __future__ import annotations

import os
import pathlib
from typing import Callable

import jax

from repro.obs import metrics as M

# None = follow the platform (interpret exactly when the backend is the CPU)
_INTERPRET: bool | None = None
_RESET_HOOKS: list[Callable[[], None]] = []

# the checkout root: src/repro/core/runtime.py -> three levels up
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def interpret_default() -> bool:
    """The current process-wide interpret default."""
    if _INTERPRET is None:
        return jax.default_backend() == "cpu"
    return _INTERPRET


def resolve_interpret(interpret: bool | None) -> bool:
    """What the ops wrappers call: explicit flag wins, None follows the
    process default."""
    return interpret_default() if interpret is None else bool(interpret)


def register_reset_hook(fn: Callable[[], None]) -> None:
    """Register a cache-clearing callback to run on `set_interpret` (for
    caches that close over compiled programs, like `fcn_sweep._sweep_fn`)."""
    if fn not in _RESET_HOOKS:
        _RESET_HOOKS.append(fn)


def set_interpret(flag: bool) -> None:
    """Pin the process to Pallas interpret (True) or compiled (False)
    execution.  Clears jit caches + registered model caches when the
    effective mode changes, so nothing compiled under the old mode
    survives."""
    global _INTERPRET
    flag = bool(flag)
    changed = flag != interpret_default()
    _INTERPRET = flag
    if not changed:
        return
    jax.clear_caches()
    for hook in _RESET_HOOKS:
        hook()


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the cache: JAX reads it
    itself and nothing here overrides it.  Otherwise the cache lives in
    `.jax_cache/` at the checkout root — a fixed path, because the path is
    part of the cache key.  Entry points call this before their first
    compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _count_compile(event: str, duration_secs: float, **_) -> None:
    if event == _COMPILE_EVENT:
        M.REGISTRY.counter("jax_compiles").inc()
        M.REGISTRY.counter("jax_compile_seconds").inc(duration_secs)


jax.monitoring.register_event_duration_secs_listener(_count_compile)
