"""GB one device holds for the step by XLA's ``memory_analysis()``:
arguments + temporaries + outputs - aliased."""


def read(ctx):
    return ctx["memory_bytes"] / 1e9
