"""One driver per kind of traffic; a workload file names its driver and
gives it its parameters.  Each driver module exposes ``run(h)`` (see
:mod:`bench.harness`)."""
