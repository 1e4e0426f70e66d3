"""Share of its roofline that the worker kernel reaches over the four
chips (layer: kernels): the least time the work of every chip launched
needs on one chip's peaks over the kernel's device time summed over
the chips' traces."""

KERNEL = "bcsr_matmul"
# HLO instruction names of its launches in the trace, without ".N"
MATCH = ("worker_kernel", "bcsr_matmul")


def read(run):
    return run.roofline_share(KERNEL, MATCH)
