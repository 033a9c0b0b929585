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

# every cell's configuration cut to a size a CPU test can run: the widths
# and lengths shrink, the engine, service and traffic kinds stay
TINY_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
    "intermediate_size": 128,
    "deployment": {"n_items": 600, "query_len": 13, "item_len": 16,
                   "pair_len": 32, "n_queries": 64},
    "service": {"max_batch": 4, "batch_buckets": [1, 2, 4]},
}
TINY_TRAFFIC = {
    "closed": {"clients": 4, "check_requests": 4, "check_cur_requests": 12},
    "poisson": {"rate_qps": 20.0, "check_requests": 4, "check_cur_requests": 12},
    "index_build": {"queries": 8, "items_per_call": 4},
}


def tiny(kind: str) -> dict:
    return {"config": TINY_CONFIG, "traffic": TINY_TRAFFIC[kind]}
