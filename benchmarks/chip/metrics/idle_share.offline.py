"""Share of the traced stretch in which no operation ran on the device
(percent)."""
import runlib

read = runlib.idle_share
