"""The benchmark's own tests: small shapes on the CPU.

    python -m pytest bench/tests
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# each traffic kind's mix cut to a CPU test's length
TINY_TRAFFIC = {
    "closed": {"clients": 4, "check_requests": 4, "check_cur_requests": 12},
    "poisson": {"rate_qps": 20.0, "check_requests": 4, "check_cur_requests": 12},
    "index_build": {"queries": 8, "items_per_call": 4},
}


def tiny(kind: str, config: str) -> dict:
    """Overrides that run a cell of ``config`` under a traffic ``kind`` at
    a CPU test's size: the cut its architecture family states (``TINY``)
    and a short mix."""
    import cell

    return {"config": cell.family(cell.load_json("configs", config)).TINY,
            "traffic": TINY_TRAFFIC[kind]}
