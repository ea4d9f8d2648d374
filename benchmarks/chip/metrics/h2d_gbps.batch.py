"""executor + loader: bytes the loader streamed over its time in
device_put and the pinned check-in (RunStats streamed_bytes over put_s),
1e9 B/s. This is the rate at which device_put hands chunks to the runtime:
a device_put that returns before its copy lands in HBM reads higher here
with no change in host-to-HBM bandwidth."""
import phases


def read(run):
    v = phases.ratio(run, "streamed_bytes", "put_s")
    return None if v is None else v / 1e9
