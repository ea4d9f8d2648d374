"""kernels: roofline least time of every f_matmul call over its device time
in the trace, %."""
import readings


def read(run):
    return readings.roofline(run, "f_matmul")
