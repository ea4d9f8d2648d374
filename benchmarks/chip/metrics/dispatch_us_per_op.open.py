"""model step: host time of the op loop outside waits for weights, chunk
assembly and the final device sync, per op run (RunStats.dispatch_s over
ops_run), us."""
import phases


def read(run):
    v = phases.ratio(run, "dispatch_s", "ops_run")
    return None if v is None else v * 1e6
