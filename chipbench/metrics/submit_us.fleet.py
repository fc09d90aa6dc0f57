"""Host time per `ReplicaRouter.submit` call, from the harness's span
around each call (us)."""


def read(run):
    s = run.window.spans.get("submit", [])
    return sum(s) / len(s) * 1e6 if s else None
