"""Engine step loop: how long the engine task waited for the event loop
per decode loop iteration, over the window: the sum over ``at`` of
``serving_loop_wait_seconds_total`` (``harvest``: from a tick's read
returning on the executor thread to the task resuming; ``yield``: the
end-of-iteration turn the front door runs in) over
``serving_decode_iterations_total`` (metricsz, after less before). The
split by ``at`` goes to standard error. A program without the counter
gives nothing."""
import sys

SOURCE = "program_counter"
UNIT = "ms"
TOTAL = "serving_loop_wait_seconds_total"


def read(ctx):
    delta = ctx["counters"].get("metricsz_delta") or {}
    ticks = delta.get("serving_decode_iterations_total")
    waits = {k[len(TOTAL):].strip("{}"): v for k, v in delta.items()
             if k.startswith(TOTAL)}
    if not ticks or not waits:
        return None
    split = ", ".join(f"{k} {1e3 * v / ticks:.3f} ms"
                      for k, v in sorted(waits.items()))
    print(f"chipbench: loop wait per decode iteration over {ticks:.0f} "
          f"iterations: {split}", file=sys.stderr)
    return 1e3 * sum(waits.values()) / ticks
