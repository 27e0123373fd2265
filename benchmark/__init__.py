"""The benchmark: TPC-H over the served path on the chip.  See README.md."""
