"""Mean host time of one per-step heartbeat (``TrainWAL.log_step_meta``:
one small transaction whose commit forces the log), in ms."""


def read(run):
    t = run.spans.get("wal.heartbeat")
    return 1e3 * sum(t) / len(t) if t else None
