"""Wall time per frame of `FcnSweep.score`, from upload to the scores on
the host (its closing `np.asarray`), from the harness's span (ms)."""


def read(run):
    s = run.window.spans.get("score", [])
    return sum(s) / len(s) * 1e3 if s else None
