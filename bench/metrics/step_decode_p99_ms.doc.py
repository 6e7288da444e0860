"""99th percentile over the window's decode steps of the host time of the
program's stage `step.decode` (the step's call through `DRService.flush`),
ms."""

from bench.metrics import readers


def read(ctx):
    return readers.stage_percentile(ctx, "step.decode", 99)
