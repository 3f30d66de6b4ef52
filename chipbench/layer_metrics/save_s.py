"""Mean host time of one state save (``TrainWAL.log_state``: the state
pulled to the host, chunked, one transaction of chunk updates), in s.  It
includes the wait for the steps queued before it."""


def read(run):
    t = run.spans.get("wal.save")
    return sum(t) / len(t) if t else None
