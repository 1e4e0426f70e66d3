"""Process start to the first timed call: operands, ``compile_plan``
and the warm-up of the cell's own shapes and masks."""


def read(run):
    return run.setup_s
