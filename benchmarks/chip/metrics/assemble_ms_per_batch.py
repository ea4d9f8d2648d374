"""executor + loader: time the op loop spent assembling streamed chunks
into weights per batch (RunStats.assemble_s, flashmem.exec.assemble), ms."""
import phases


def read(run):
    v = phases.per_batch(run, "assemble_s")
    return None if v is None else v * 1e3
