"""Share of the traced span in which no operation ran on the device (%):
the same reading as the training cells'."""

from chipbench.readers import load_reader

read = load_reader("device_idle_share.train").read
