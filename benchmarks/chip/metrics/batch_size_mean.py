"""engine: mean requests per executed batch."""
import readings


def read(run):
    return readings.batch_size_mean(run)
