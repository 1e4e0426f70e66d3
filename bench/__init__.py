"""On-chip benchmark of the coded sparse matrix products.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once and prints one JSON result line.  Everything that
belongs to one configuration, traffic mix, metric or kernel sits in a
file of its own, found by the name that ``BENCHMARK.json`` gives:

  configs/<config>.json     the deployment: shapes, scheme, n, k, zeros
  cells/<workload>.json     its traffic: generator name and parameters
  traffic/<generator>.py    the generators (``mv_stream``, ``mm_stream``)
  metrics/<metric>.py       one reader per metric, ``read(run)``
  roofline/<kernel>.py      a kernel's operations and bytes, ``count(desc)``
  peaks.json                the chips' published peaks, by ``device_kind``

The rest is the yardstick: operand generation (``operands``), the
paper's support sets (``schemes``), the float64 reference and the
comparison (``reference``), and the reduction of a profiler trace to
busy time, kernel time and idle gaps (``trace``).  Two tools set the
limits and bounds and are never run by ``run.py``: ``control.py`` reads
the compared numbers of sound runs and of the lower-precision controls,
and ``spread.py`` runs sets of runs and prints each metric's spread.
"""
