"""Host time per save spent turning the state into log records: the
program's ``wal.save.records`` spans (each leaf's chunk loop: crc32,
encoding, one ``tc.update`` per chunk, trackers) over its
``wal.log_state`` spans, in s."""
from chipbench import program_spans


def read(run):
    return program_spans.per_span("wal.save.records", "wal.log_state")
