"""Putting the restored leaves on the device: the program's
``wal.restore.put`` spans (``jnp.asarray`` and the reshape of each leaf),
per restore, in s."""
from chipbench import program_spans


def read(run):
    return program_spans.per_span("wal.restore.put", "wal.restore")
