"""Chip benchmark of the distributed sparse-LDA fit.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything
that belongs to one configuration, cell or per-layer metric is a file
of its own, found by its name:

* ``bench/configs/<config>.json``: the deployment and its source;
* ``bench/workloads/<cell>.json``: the traffic of one cell;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

The rest of this package is the yardstick the cells share: the frozen
data generator (:mod:`bench.data`), the plain reference fit
(:mod:`bench.reference`), the trace reduction (:mod:`bench.tracing`),
the work and bytes of each layer (:mod:`bench.work`) and the table of
peaks (:mod:`bench.peaks`).
"""
