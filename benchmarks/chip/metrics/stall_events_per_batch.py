"""executor + loader: ops that waited for a weight per batch (RunStats.stall_events)."""
import readings


def read(run):
    return readings.stall_events_per_batch(run)
