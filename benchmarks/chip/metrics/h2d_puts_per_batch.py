"""executor + loader: device_puts the loader issued per batch
(RunStats.h2d_puts), one per slab of consecutive missing chunks or per
chunk that cannot join one."""
import phases


def read(run):
    return phases.per_batch(run, "h2d_puts")
