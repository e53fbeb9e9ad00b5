"""Chip benchmark of the federated boosting trainer.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name:

* ``bench/configs/<config>.json``: a deployment (sizes, source, cuts);
* ``bench/workloads/<cell>.json``: a cell: its config, driver, chips and
  traffic or job parameters;
* ``bench/drivers/<driver>.py``: one kind of cell (fleet training);
* ``bench/metrics/<metric>.py``: one per-layer metric's reader.
"""
