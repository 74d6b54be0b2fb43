"""Trainer loop: median interval between entries into the trainer's step,
on the host's clock (the benchmark's hook around ``train_step``)."""
import statistics

SOURCE = "program_span"
UNIT = "ms"


def read(ctx):
    gaps = ctx["spans"].get("step_interval_s")
    return 1e3 * statistics.median(gaps) if gaps else None
