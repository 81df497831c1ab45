"""Share of its roofline of the MAC kernel (``rtl/oplib.mac_int_pallas``),
all its calls in a dispatch summed, from its device time in the trace."""
from bench.harness.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "mac_int")
