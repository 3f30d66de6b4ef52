"""Recovery's analysis pass (image clone, SMO replay, DPT build), as
``RecoveryStats.analysis_ms`` times it, in s."""


def read(run):
    return run.recovery["analysis_ms"] / 1e3 if run.recovery else None
