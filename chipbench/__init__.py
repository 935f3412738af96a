"""On-chip benchmark of the matcher: ``python3 chipbench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>`` (see ``run.py``)."""
