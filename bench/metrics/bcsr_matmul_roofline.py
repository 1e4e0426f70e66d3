"""Share of its roofline that the worker kernel reaches (layer: kernels):
the least time its work needs on the chip's peaks over its device time
in the trace."""

KERNEL = "bcsr_matmul"
# HLO instruction names of its launches in the trace, without ".N": the
# executor's jit wrapper names the unnamed pallas_call
MATCH = ("worker_kernel", "bcsr_matmul")


def read(run):
    return run.roofline_share(KERNEL, MATCH)
