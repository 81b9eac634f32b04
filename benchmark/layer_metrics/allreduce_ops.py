"""All-reduce and reduce-scatter operations in the compiled step (a
count from the HLO text; async pairs count once, by their start)."""

import re


def read(ctx):
    if ctx["cell"]["chips"] < 2:
        return None
    return len(re.findall(r"\s(?:all-reduce|reduce-scatter)(?:-start)?\(",
                          ctx["hlo"]))
