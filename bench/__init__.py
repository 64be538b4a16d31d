"""Chip benchmark of the uncertainty serving engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on and
prints one JSON line.  Everything that belongs to one configuration,
traffic mix or per-layer metric is a file of its own, found by name:

* ``bench/configs/<config>.json``: the model's sizes and engine
  settings; its ``"reference"`` names the architecture;
* ``bench/families/<reference>.py``: what the harness needs of that
  architecture: the program's ``ArchConfig``, the per-layer weights,
  the parameter tree and the body's FLOPs per token;
* ``bench/references/<reference>.py``: the architecture's plain float32
  forward, which decides ``correct``;
* ``bench/traffic/<mix>.json``: parameters of the shared generator
  (``bench/traffic.py``);
* ``bench/limits/<cell>.json``: the limit of each number the correctness
  check compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``: a ``read(ctx)`` that returns the metric
  or None when the run gave it nothing to read.

A configuration of a new architecture brings its own family and
reference beside its configuration file, limits and any new mix or
metric; no existing file changes.  The yardstick lives here too: the
peaks table (``peaks.json``), the work each kernel's shapes require
(``work.py``), the trace reducer (``trace.py``) and the weight generator
(``weights.py``).
"""
