"""Share of batch slots that carried a request over the window (%), from
the engine's step and padded-slot counters."""


def read(run):
    c = run.window.counters
    slots = c.get("batches", 0) * c.get("batch_size", 0)
    return (slots - c["padded_slots"]) / slots * 100.0 if slots else None
