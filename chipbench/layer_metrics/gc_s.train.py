"""Host time in Python's garbage collections during the traced training
period: the program's ``gc`` spans, every generation, in s."""
from chipbench import program_spans


def read(run):
    return program_spans.gc_seconds()
