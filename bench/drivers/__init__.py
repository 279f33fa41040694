"""Traffic drivers: ``<kind>.py`` is the general generator of every
traffic mix whose file names that ``kind``; it defines ``run(ctx)``."""
