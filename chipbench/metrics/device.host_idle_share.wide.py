"""Share of the window with no launch holding the device, in a
wide-study cell: the reading of ``device.host_idle_share``, under a name
of its own."""

from chipbench import harness

read = harness.load_reader("device.host_idle_share").read
