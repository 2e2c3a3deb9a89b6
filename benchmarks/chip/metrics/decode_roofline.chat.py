"""Decode step's share of its roofline, from the device trace (percent)."""
import runlib

read = runlib.decode_roofline
