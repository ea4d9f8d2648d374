"""executor + loader: time the op loop waited for the loader per batch
(RunStats.stall_s, flashmem.exec.wait_weight), ms."""
import phases


def read(run):
    v = phases.per_batch(run, "stall_s")
    return None if v is None else v * 1e3
