"""Which device ops of a traced window are ResNet-8's conv kernel.

Matched by the names the program gives, never by XLA's numbering: the
engine's step is the module named after its jitted function `fwd`
(`jit_fwd`), and in it every launch of the multi-channel conv kernel is
an op named after the jitted function that holds nothing but its
`pallas_call`, `_fixed_conv_mc_jit` (`.N` appended per call site). A
program without that kernel gives no such op, and the readers then read
nothing.
"""
from __future__ import annotations

STEP_MODULE = "jit_fwd"
CONV_OP = "_fixed_conv_mc_jit"


def _is_conv(name: str) -> bool:
    return name.split(".", 1)[0] == CONV_OP


def conv_seconds(run) -> float:
    """Device seconds of the conv kernel's ops over the traced window."""
    ops = (run.trace or {}).get("ops", {})
    return sum(t for (module, name), (t, _) in ops.items()
               if module == STEP_MODULE and _is_conv(name))


def conv_per_step_s(run) -> float | None:
    """Mean device seconds of the conv kernel's ops per engine step (one
    run of the step module), or None where no step ran a conv kernel."""
    steps = [sum(t for name, t in ops if _is_conv(name))
             for module, ops in (run.trace or {}).get("runs", [])
             if module == STEP_MODULE]
    steps = [s for s in steps if s > 0]
    return sum(steps) / len(steps) if steps else None


def served(run) -> int:
    """Images the traced window served."""
    return len(run.window.outputs or ())
