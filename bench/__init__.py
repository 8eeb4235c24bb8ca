"""Chip benchmark of the serving path: ``python3 bench/run.py --workload <cell>``.

Driven by data: ``BENCHMARK.json`` at the repository root names every cell,
configuration and metric, and the harness finds each by that name:

* ``configs/<config>.json``   model configuration as it is run;
* ``models/<model>.py``       the model module a configuration names
                              (``decoder`` by default): its weights,
                              reference, operation counts and warm-up;
* ``traffic/<mix>.json``      traffic parameters read by ``traffic.py``;
* ``cells/<cell>.json``       engine settings and the correctness limit;
* ``metrics/<metric>.py``     the reader of one per-layer metric.
"""
