"""One reader per metric, found by name: ``<metric>.py`` defines
``read(run)``, which returns the metric's value or ``None`` where the run
holds nothing to read."""
