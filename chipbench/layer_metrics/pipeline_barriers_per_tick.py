"""Engine step loop: pipeline barriers that found a tick in flight and
drained it, per decode loop iteration, over the window: the sum over
reasons of ``serving_pipeline_barriers_total`` over
``serving_decode_iterations_total`` (metricsz, after less before). At 1
every tick waits for the one before it and the depth-1 overlap is lost.
The split by reason goes to standard error."""
import sys

SOURCE = "program_counter"
UNIT = "barriers"
TOTAL = "serving_pipeline_barriers_total"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    ticks = delta.get("serving_decode_iterations_total")
    barriers = {k[len(TOTAL):].strip("{}"): v for k, v in delta.items()
                if k.startswith(TOTAL)}
    if not ticks or not barriers:
        return None
    split = ", ".join(f"{k} {v:.0f}" for k, v in sorted(
        barriers.items(), key=lambda kv: -kv[1]) if v)
    print(f"chipbench: {sum(barriers.values()):.0f} pipeline barriers in "
          f"{ticks:.0f} ticks: {split or 'none'}", file=sys.stderr)
    return sum(barriers.values()) / ticks
