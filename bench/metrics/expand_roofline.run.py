"""Percent of its roofline the exploration step reaches: the least bytes
it has to move (`bench.kernels.expand_call_bytes`, from the shapes of each
call in the traced window) over the chip's HBM bandwidth, divided by the
device time of its programs in the trace (`bench.xplane` names them).

A traced run whose calls and programs cannot be matched one to one is an
error, not a missing number: the program's cache keys or the trace's
naming have changed under the reader."""
from bench.kernels import expand_call_bytes, peaks_for

MODULE = "expand_stwig_shard"


def read(run):
    if run.trace is None:
        return None
    sizes = [b for c in run.calls
             if (b := expand_call_bytes(c.key, c.args)) is not None]
    n = run.trace.module_n.get(MODULE, 0)
    if not sizes or n != len(sizes):
        raise RuntimeError(
            f"expand_roofline: {len(sizes)} exploration calls recorded but "
            f"{n} {MODULE} programs in the trace")
    seconds = sum(sizes) / peaks_for(run.device_kind).hbm_bw
    return 100.0 * seconds / run.trace.module_s[MODULE]
