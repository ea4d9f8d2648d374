"""device: share of the engine's step time with no operation on the device, %."""
import readings


def read(run):
    return readings.idle_in_steps(run)
