"""On-chip serving benchmark for the continuous-batching engine.

Entry point: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.  Everything a
cell needs is found by name from ``BENCHMARK.json``:

  bench/configs/<config>.json   model sizes, precision, correctness limits
  bench/arch/<Architecture>.py  the configuration's ``architectures[0]``:
                                its weight layout, its sizes for the
                                operation counts and its plain reference
  bench/traffic/<mix>.json      arrival process, length distributions and
                                engine sizing
  bench/metrics/<metric>.py     one reader per per-layer metric

The yardstick lives here and imports nothing of the program: traffic
generation (``traffic``), percentile and rate arithmetic (``stats``), the
plain reference forward of each architecture (``arch``, with the
arithmetic they share in ``reference``), the comparison that decides
``correct`` (``check``), peaks and operation/byte counts (``roofline``) and
the reduction of profiler traces (``trace``).  Only ``run``, ``program``
and ``window`` touch the system under test.
"""
