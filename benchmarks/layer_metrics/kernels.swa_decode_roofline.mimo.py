"""The sliding layers' decode attend's share of its roofline, memory bound:
K and V of the last ``sliding_window`` tokens of each live slot, one sliding
layer's (``opcount_mimo.window_attend_bytes`` over the contexts of the
requests streaming in the traced tail: ``min(context, 128)`` rows of 8 x 192
+ 8 x 128 bfloat16), over the chip's HBM bytes/s, over the mean device time
of the trace's ops whose name ends in ``flash_decode_ring_sink`` (one call a
sliding layer and decode step). It counts the live rows, not the ring: a
kernel that reads the ring of 640 rows whole moves five times that, and the
share then reads a fifth of what the op's own traffic would. None when no
such op ran: a program that attends the ring densely."""

from benchmarks import common, opcount_mimo

KERNEL = "flash_decode_ring_sink"
kernel_share = common.load_file(
    "layer_metrics", "kernels.full_decode_roofline.mimo").kernel_share


def read(run):
    return kernel_share(run, KERNEL, opcount_mimo.window_attend_bytes)
