"""Benchmark of the FlashR engine on the chip: see run.py and BENCHMARK.json."""
