"""The train step's share of the chip's bf16 peak, in %: model operations
of the steps traced (``chipbench/flops.py``) over the device time of the
step program in the trace, over the peak (``chipbench/peaks.py``)."""


def read(run):
    if run.trace is None or not run.flops_per_step:
        return None
    runs = [m for name, m in run.trace["modules"].items()
            if name.startswith(run.step_program)]
    n = sum(m["count"] for m in runs)
    secs = sum(m["seconds"] for m in runs)
    if not n or secs <= 0:
        return None
    return 100.0 * run.flops_per_step * n / secs / run.peak["bf16_flops"]
