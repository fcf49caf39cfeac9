"""Cells returned per second of a wide-study cell: the reading of
``cells_per_s``, under a name and bound of its own."""

from chipbench import harness

read = harness.load_reader("cells_per_s").read
