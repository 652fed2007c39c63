"""plan_device_ms (plan): mean time of the plan's device part per batch:
the dispatch of the plan program and the first host read that waits for
it, the ``repro.engine.plan.device`` span's stage histogram
(``stage="plan.device"`` in metrics_text())."""

from benchlib import program


def read(run):
    return program.stage_ms(run, "plan.device")
