"""On-chip benchmark of fault-tolerant training through ``TrainWAL``.

Run one cell once with ``python chipbench/run.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>``; see ``run.py``.
"""
