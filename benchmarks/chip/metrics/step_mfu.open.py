"""model step: model FLOPs of the real prompt tokens over executed batch
time at the chip's peak, %, open-loop cells."""
import readings


def read(run):
    return readings.step_mfu(run)
