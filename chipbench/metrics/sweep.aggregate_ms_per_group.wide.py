"""Host ms of aggregation per expansion group in a wide-study cell: the reading of
``sweep.aggregate_ms_per_group``, under a name and bound of its own."""

from chipbench import harness

read = harness.load_reader("sweep.aggregate_ms_per_group").read
