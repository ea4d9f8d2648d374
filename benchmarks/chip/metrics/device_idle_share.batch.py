"""device: share of the traced window with no operation on the device, %."""
import readings


def read(run):
    return readings.idle_in_window(run)
