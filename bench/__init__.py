"""GeoPlan's on-chip benchmark: ``python3 -m bench --workload <cell> ...``
(see :mod:`bench.run`)."""
