"""Share of its roofline of the fused LSTM-window kernel
(``kernels/lstm_cell_int``), from its device time in the trace."""
from bench.harness.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "lstm_window_int")
