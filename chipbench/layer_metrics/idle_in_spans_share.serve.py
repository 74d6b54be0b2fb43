"""Device: of all the time the device ran no operation in the traced
window (every gap between ``XLA Ops`` from the first to the last, not the
ten longest), the share that fell inside some span of the program
(``dk:*`` in the profiler trace): how much of the chip's idling the
program can put a name to. The split by span, innermost first where they
nest, goes to standard error."""
import sys

SOURCE = "program_span"
UNIT = "%"


def read(ctx):
    from harness import host_spans

    parsed = host_spans.load() if ctx["trace"] is not None else None
    found = host_spans.idle_by_span(parsed) if parsed else None
    if found is None:
        return None
    idle, inside = found
    split = ", ".join(f"{name} {100 * s / idle:.1f} %" for name, s in
                      sorted(inside.items(), key=lambda kv: -kv[1]))
    print(f"chipbench: device idle {1e3 * idle:.2f} ms between operations; "
          f"by span: {split or 'none'}; in no span "
          f"{100 * (1 - sum(inside.values()) / idle):.1f} %", file=sys.stderr)
    return 100.0 * sum(inside.values()) / idle
