"""The whole step's counted work at the chip's roofline, over the measured
time per step, in %."""
from bench.rooflines import mfu as read  # noqa: F401
