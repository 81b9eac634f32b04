"""Seconds from ``hvd.init()`` to the mesh (host clock)."""


def read(ctx):
    return sum(ctx["spans"]["init"])
