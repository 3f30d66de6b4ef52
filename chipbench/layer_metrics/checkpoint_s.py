"""Mean host time of one RSSP checkpoint taken (``TrainWAL.maybe_checkpoint``
when it returns true: the dirty pages flushed), in s."""


def read(run):
    t = run.spans.get("wal.checkpoint")
    return sum(t) / len(t) if t else None
