"""Benchmark harness: one module per paper table/figure, and the chip
benchmark under ``chip/``."""
