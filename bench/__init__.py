"""The on-chip benchmark: one command runs one cell (a configuration
under a traffic mix) on the chip and prints one JSON result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration or per-layer metric
sits in a file of its own, found by name:

- ``bench/workloads/<cell>.json``  -- configuration, driver, traffic, why;
- ``bench/configs/<config>.json``  -- the deployment, its source and cuts;
- ``bench/drivers/<driver>.py``    -- one general generator per kind of
  traffic (``run(harness)``);
- ``bench/metrics/<metric>.py``    -- one reader per per-layer metric
  (``UNIT``, ``read(ctx)``);
- ``bench/reference/``             -- the plain references;
- ``bench/peaks.json``             -- the chip peaks, keyed by device kind.
"""
