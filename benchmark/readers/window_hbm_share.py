"""The HBM window's share of the device's memory bandwidth, in per cent:
the bytes the window's DMAs moved in the traced jobs, over the device
time of the kernels in the slice, over the peak.

    bytes  = (counters[trace_window_fills] + counters[trace_window_writebacks])
             * counters[window_dma_bytes]
    time   = summed device time of the operations whose short name matches
             `match` (the Pallas kernels' custom calls), over the slice
    peak   = counters[hbm_bytes_per_s], peaks.json's number for the device

A fill moves one window of rows x lane block x 4 bytes from the memory
plane in HBM into a way in VMEM, a write-back the same the other way;
`window_dma_bytes` is that product, which the driver reads off the
engine's `mem_static`.  It is the one axis on which this interpreter
kernel has a count: it has no FLOP count.  The share is of the kernel's
time, not of the DMAs' own, so it says how far the kernel as a whole is
from being bound by the window's traffic.

None where there is no trace, no kernel in it, or the program or the
driver left a counter out (a kernel without the window; the parent).
"""


def read(obs, match):
    trace, c = obs["trace"], obs["counters"]
    names = ("trace_window_fills", "trace_window_writebacks",
             "window_dma_bytes", "hbm_bytes_per_s")
    if trace is None or any(c.get(n) is None for n in names):
        return None
    seconds = trace.op_seconds(match)
    if seconds <= 0 or not c["hbm_bytes_per_s"]:
        return None
    moved = (c["trace_window_fills"] + c["trace_window_writebacks"]) \
        * c["window_dma_bytes"]
    return 100.0 * moved / seconds / c["hbm_bytes_per_s"]
