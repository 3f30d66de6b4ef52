"""Reading every state record back after recovery: the program's
``wal.restore.scan`` span (``scan_all`` and the prefix filter), per
restore, in s."""
from chipbench import program_spans


def read(run):
    return program_spans.per_span("wal.restore.scan", "wal.restore")
