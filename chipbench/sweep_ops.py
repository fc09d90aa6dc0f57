"""Which device ops of a traced window belong to which kernel of the sweep.

Matched by what the program names and by the order in which its per-frame
program runs them, never by XLA's numbering. The per-frame sweep program
is the module named after the jitted function `run` (`jit_run`). In each
of its runs the trunk megakernel is the op named after `_frame_trunk_jit`;
the ops before it prepare the frame (the float pixels' ingest to words and
the megakernel's halo'd window stack); every op after it is the window
head: the role-map window gathers, the role selects, `fixed_dense` and
the output sigmoid.
"""
from __future__ import annotations

SWEEP_MODULE = "jit_run"
TRUNK_OP = "frame_trunk"


def split_run(ops) -> dict[str, float] | None:
    """(op, seconds) pairs of one sweep run -> seconds by part, or None
    where the run holds no trunk kernel."""
    k = next((i for i, (n, _) in enumerate(ops) if TRUNK_OP in n), None)
    if k is None:
        return None
    return {"prep": sum(t for _, t in ops[:k]), "trunk": ops[k][1],
            "head": sum(t for _, t in ops[k + 1:])}


def parts(run) -> list[dict[str, float]]:
    out = []
    for module, ops in (run.trace or {}).get("runs", []):
        if module == SWEEP_MODULE:
            p = split_run(ops)
            if p is not None:
                out.append(p)
    return out


def per_frame_s(run, part: str) -> float | None:
    """Mean device seconds per frame of one part of the sweep program
    ("prep", "trunk" or "head"), or None where the trace holds no run."""
    ps = parts(run)
    return sum(p[part] for p in ps) / len(ps) if ps else None


def frames(run) -> int:
    return len(run.window.spans.get("score", []))


def windows(run) -> int:
    t = run.cell.traffic
    ny = len(range(0, t["height"] - 28, t["stride"])) + 1
    nx = len(range(0, t["width"] - 28, t["stride"])) + 1
    return ny * nx
