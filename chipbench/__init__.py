"""The benchmark of accelerate_tpu on the chip: see README.md beside this file."""
