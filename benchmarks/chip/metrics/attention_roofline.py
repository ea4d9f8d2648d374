"""kernels: roofline least time of every f_attn call (causal half counted)
over its device time in the trace, %."""
import readings


def read(run):
    return readings.roofline(run, "f_attn")
