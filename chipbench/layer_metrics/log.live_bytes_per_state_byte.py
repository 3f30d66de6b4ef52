"""Image bytes the state log holds in memory per byte of state: the
largest ``log_live_bytes / state_bytes`` over the program's
``wal.log_state`` spans of the traced period, in B/B.  ``log_live_bytes``
is the log's count of the before- and after-image bytes of the update
records it holds at the save's commit; a program that does not record it
reads None."""
from chipbench import program_spans


def read(run):
    ratios = [s.attrs["log_live_bytes"] / s.attrs["state_bytes"]
              for s in program_spans.named("wal.log_state")
              if "log_live_bytes" in s.attrs and s.attrs.get("state_bytes")]
    return max(ratios) if ratios else None
