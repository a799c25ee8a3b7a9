"""The benchmark's yardstick: traffic generation, work counts, peaks, trace
reduction and the comparison that decides ``correct``."""
