"""Traffic drivers, one per kind of traffic mix: ``setup``, ``window``,
``check``."""
