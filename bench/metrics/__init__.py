"""Per-layer metrics: ``<metric>.py`` holds ``read(run)``, which takes the
metric from a finished run (``bench.run.RunRecord``) and returns a number,
or None where the run holds nothing to read."""
