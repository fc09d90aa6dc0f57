"""Host time per frame in the pipeline's extract and aggregate stages,
from the harness's spans around the two calls (ms)."""


def read(run):
    s = run.window.spans
    n = len(s.get("aggregate", []))
    if not n:
        return None
    return (sum(s["extract"]) + sum(s["aggregate"])) / n * 1e3
