"""Median latency of every request due in the window (see p95_ms)."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location("p95_ms", Path(__file__).with_name("p95_ms.py"))
_p95 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_p95)


def read(ctx):
    return _p95.read(ctx, 50)
