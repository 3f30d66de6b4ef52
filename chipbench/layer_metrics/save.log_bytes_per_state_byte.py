"""Log bytes appended per byte of state saved: the ``log_bytes`` attribute
of the program's ``wal.log_state`` spans (the ``log.bytes_appended``
counter, before- plus after-images, over the save) over their
``state_bytes`` (every chunk's record), in B/B."""
from chipbench import program_spans


def read(run):
    saves = program_spans.named("wal.log_state")
    state = sum(s.attrs.get("state_bytes", 0) for s in saves)
    if not state:
        return None
    return sum(s.attrs.get("log_bytes", 0) for s in saves) / state
