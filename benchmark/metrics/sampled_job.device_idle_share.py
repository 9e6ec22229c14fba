"""Share of the sampled (FracMinHash c=125) species job's time in which
no operation ran on the device: the reading of device.idle_share (1 -
the union of device-op intervals inside the jobs over the sum of the
job intervals, from the profiler trace). The job is host-bound by
nature; this says how far."""

from harness import load_reader

read = load_reader("device.idle_share")
