"""The chip benchmark's general code: manifest, traffic, drain loop, trace
reduction, yardsticks (FLOP/byte counts, chip peaks) and the comparison that
decides ``correct``. What belongs to one configuration, traffic mix or
per-layer metric lives in files of its own under ``bench/`` and is found by
its name in ``BENCHMARK.json``."""
