"""Host ms of trace build per trace family in a wide-study cell: the reading of
``sweep.trace_build_ms_per_family``, under a name and bound of its own."""

from chipbench import harness

read = harness.load_reader("sweep.trace_build_ms_per_family").read
