"""Chip benchmark of the FedGS round: cells, yardstick and harness."""
