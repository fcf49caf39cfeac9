"""Mean host ms of packing a launch in a wide-study cell: the reading of
``engine.pack_ms_per_launch``, under a name of its own."""

from chipbench import harness

read = harness.load_reader("engine.pack_ms_per_launch").read
