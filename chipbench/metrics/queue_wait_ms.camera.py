"""Time a frame waits between the pipeline's stages (ms): per served frame,
its waits in the three bounded queues, from `_admit` to the next stage's
`get` (`stream_queue_wait_seconds`), plus the hop of its wave onto the
executor thread (`stream_executor_hop_seconds`), from the metrics registry.
The window's pipeline is the newest in the registry: the runner builds one
for each window, after the one its warm-up used. It is read only if it
served exactly the frames the window served; a pipeline built after the
window makes the reading None rather than moving it to another source."""
from repro.obs import metrics as M

WAITS = ("stream_queue_wait_seconds", "stream_executor_hop_seconds")


def read(run):
    pipes: dict[str, list] = {}
    for inst in M.REGISTRY.instruments():
        pipe = inst.labels.get("pipe")
        if pipe is not None:
            pipes.setdefault(pipe, []).append(inst)
    if not pipes:
        return None
    newest = pipes[max(pipes, key=lambda p: int(p.rsplit("#", 1)[1]))]
    served = sum(i.value for i in newest if i.name == "stream_frames_served")
    waits = [i.sum for i in newest if i.name in WAITS]
    if not waits or not served or served != len(run.window.outputs or ()):
        return None
    return sum(waits) / served * 1e3
