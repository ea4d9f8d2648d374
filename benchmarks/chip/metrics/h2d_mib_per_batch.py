"""weight pool: MiB moved host to device per batch (RunStats preloaded +
streamed bytes)."""
import readings


def read(run):
    return readings.h2d_mib_per_batch(run)
