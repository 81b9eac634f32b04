import time as _time
_T0 = _time.perf_counter()      # first line: the start-up log's span

from .reduce_ops import Sum, Average, Adasum, Min, Max, Product  # noqa: F401

from ..utils import compile_cache as _startup
_startup.imported(__name__, _T0)
