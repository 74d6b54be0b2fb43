"""Trainer loop: compilations of the step counted by the RecompileAuditor
after the window opened. Should read 0."""
SOURCE = "program_counter"
UNIT = "compiles"


def read(ctx):
    return ctx["counters"].get("compiles_in_window")
