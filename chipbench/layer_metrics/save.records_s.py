"""Host time per save spent turning the state into log records: the
program's ``wal.save.records`` spans (each leaf's chunk loop: one copy of
each chunk into its record, then ``tc.update`` with one traversal to the
leaf, the before-image compare, the log append and the put; the trackers)
over its ``wal.log_state`` spans, in s."""
from chipbench import program_spans


def read(run):
    return program_spans.per_span("wal.save.records", "wal.log_state")
