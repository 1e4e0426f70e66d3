"""Host seconds of the benchmark's ``compile_plan`` call (layer: plan)."""


def read(run):
    return run.phases.get("compile_plan")
