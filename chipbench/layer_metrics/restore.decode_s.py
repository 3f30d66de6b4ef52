"""Rebuilding the state's leaves on the host after the scan: the program's
``wal.restore.decode`` spans (each leaf's chunks decoded and joined), per
restore, in s."""
from chipbench import program_spans


def read(run):
    return program_spans.per_span("wal.restore.decode", "wal.restore")
