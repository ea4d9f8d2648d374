"""engine: mean wait from due time to the start of the request's batch
(Response.queue_s), ms."""
import readings


def read(run):
    return readings.queue_ms_mean(run)
