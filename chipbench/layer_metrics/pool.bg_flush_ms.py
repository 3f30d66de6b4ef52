"""Mean host time of one background flush round of the buffer pool: the
program's ``pool.bg_flush`` spans (``BufferPool.flush_some``, after every
heartbeat and save), in ms."""
from chipbench import program_spans


def read(run):
    t = program_spans.named("pool.bg_flush")
    return 1e3 * sum(s.seconds for s in t) / len(t) if t else None
