"""A kernel's share of its roofline, in %, for the ``*_roofline`` readers.

The least time the chip could take for the kernel's work, the larger of
its operations over the peak operation rate and its bytes over the peak
memory bandwidth (``bench/work.py`` counts both; ``bench/peaks.json`` holds
the peaks), divided by the summed device time of the kernel's operations
in the trace. The kernel scores at float32 HIGHEST precision, which the
MXU runs as six bfloat16 passes, against the bfloat16 peak: the share's
practical ceiling is well below 100 %.
"""


def share(ctx, kernel: str):
    t = ctx.trace
    if t is None or ctx.peaks is None or kernel not in ctx.observed.work:
        return None
    seconds = t.kernel_seconds(ctx.observed.names[kernel])
    flops, nbytes = ctx.observed.work[kernel]
    if seconds <= 0 or flops <= 0:
        return None
    least = max(flops / ctx.peaks["bf16_flops_per_s"], nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
