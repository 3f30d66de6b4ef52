"""Host time per save spent copying the state to the host: the program's
``wal.save.pull`` spans (``np.asarray`` of each leaf, after
``wal.save.wait`` has waited for the queued steps) over its
``wal.log_state`` spans, in s."""
from chipbench import program_spans


def read(run):
    return program_spans.per_span("wal.save.pull", "wal.log_state")
