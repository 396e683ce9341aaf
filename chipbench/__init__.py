"""On-chip benchmark of the served model path.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` serves one cell of ``BENCHMARK.json`` through
``InferenceEngine`` -> ``JaxBackend`` on one TPU chip and prints one JSON
result line. Everything that belongs to one configuration, traffic mix or
per-layer metric lives in its own file, found by name:
``chipbench/configs/<config>.json``, ``chipbench/traffic/<mix>.json`` and
``chipbench/metrics/<metric>.py``.
"""
