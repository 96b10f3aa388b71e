"""The counted work's least time over the device's busy time per unit,
in %: the executors' and kernels' share of their roofline."""
from bench.rooflines import kernel_roofline as read  # noqa: F401
