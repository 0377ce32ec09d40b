"""The chip benchmark's harness: it finds every configuration, traffic mix,
per-layer metric, kernel adapter, plain reference, work count and limit by
name, from a file of its own under ``bench/``."""
