"""One reader a per-layer metric: ``bench/metrics/<metric>.py`` with
``read(ctx) -> float | None``.  ``ctx`` is what the cell's driver hands
over after a traced run (see each driver's ``context``): the reduced
trace, the configuration, the run's observations and the peaks of its
device.  A reader that finds nothing to read returns None and the
metric is left out of the result line."""
