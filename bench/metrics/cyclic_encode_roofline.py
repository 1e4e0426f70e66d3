"""Share of its roofline that the encode kernel reaches in the calls
that encode B (layer: kernels)."""

KERNEL = "cyclic_encode"
# HLO instruction names of its launches in the trace, without ".N": the
# executor's jit wrapper names the unnamed pallas_call
MATCH = ("encode_kernel", "cyclic_encode")


def read(run):
    return run.roofline_share(KERNEL, MATCH)
