"""Recompiles the program's telemetry counted between the window's open and
close (``accelerator.telemetry.recompiles_total``).  Expected 0."""


def read(ctx):
    return ctx["counters"].get("recompiles_in_window")
