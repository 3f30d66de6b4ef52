"""Recovery's logical redo, as ``RecoveryStats.redo_wall_ms`` times it, in
s."""


def read(run):
    return run.recovery["redo_wall_ms"] / 1e3 if run.recovery else None
