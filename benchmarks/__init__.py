"""Benchmarks: one module per paper table/figure + kernels."""
