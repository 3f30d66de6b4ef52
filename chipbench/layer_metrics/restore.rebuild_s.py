"""What a resume spends outside recovery and replay: reading every state
record back and rebuilding the leaves on the device.  The harness span of
``resume_from_crash`` less its replay steps and less
``RecoveryStats.total_wall_ms``, in s."""


def read(run):
    resume = run.spans.get("restore")
    if not resume or not run.recovery:
        return None
    replay = sum(run.spans.get("replay", []))
    return resume[0] - replay - run.recovery["total_wall_ms"] / 1e3
