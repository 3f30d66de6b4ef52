"""Recovery's end-of-recovery checkpoint, which flushes every page redo
dirtied: the program's ``checkpoint`` spans under a ``recover`` span, per
recovery, in s."""
from chipbench import program_spans


def read(run):
    found = program_spans.spans()
    recovers = {s.span for s in program_spans.named("recover", found)}
    t = [s.seconds for s in program_spans.named("checkpoint", found)
         if s.parent in recovers]
    return sum(t) / len(recovers) if t else None
