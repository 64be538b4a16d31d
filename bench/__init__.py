"""Chip benchmark of the uncertainty serving engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on and
prints one JSON line.  Everything that belongs to one configuration,
traffic mix or per-layer metric is a file of its own, found by name:

* ``bench/configs/<config>.json``: the model's sizes and engine settings;
* ``bench/references/<reference>.py``: the plain float32 forward a
  configuration names, which decides ``correct``;
* ``bench/traffic/<mix>.json``: parameters of the shared generator
  (``bench/traffic.py``);
* ``bench/limits/<cell>.json``: the limit of each number the correctness
  check compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``: a ``read(ctx)`` that returns the metric
  or None when the run gave it nothing to read.

The yardstick lives here too: the peaks table (``peaks.json``), the work
each kernel's shapes require (``work.py``), the trace reducer
(``trace.py``) and the weight generator (``weights.py``).
"""
