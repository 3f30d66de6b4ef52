"""Host time in Python's garbage collections during the traced resume: the
program's ``gc`` spans from the start of ``wal.restore`` to the end of the
new step's heartbeat (the harness's own collection after the resume is
left out), per resume, in s."""
from chipbench import program_spans


def read(run):
    t = program_spans.gc_seconds(start="wal.restore")
    n = len(program_spans.named("wal.restore"))
    return t / n if t is not None else None
