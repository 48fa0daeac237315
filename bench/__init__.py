"""The chip benchmark: one cell (a configuration under a traffic mix) per run.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once, as ``BENCHMARK.json`` at the root of the checkout names
it. Everything a cell needs is found by name: its configuration in
``bench/configs/<file>``, its traffic mix in ``bench/traffic/<traffic>.json``
(whose ``driver`` key names the general window driver in
``bench/drivers/``), and each per-layer metric's reader in
``bench/metrics/<metric>.py``.
"""
