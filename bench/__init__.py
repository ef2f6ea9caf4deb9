"""Benchmark of the FedVision system on the chip: see bench/README.md."""
