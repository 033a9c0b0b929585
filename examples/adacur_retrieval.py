"""Budget-sweep study (paper Fig. 2 shape): recall vs CE-call budget for
every method — all expressed as Retriever-engine configurations — on a
paper-scale synthetic domain (10K items, 500 anchors).

    PYTHONPATH=src python examples/adacur_retrieval.py
"""

import jax

from benchmarks import recall_budget
from benchmarks.common import make_domain
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    dom = make_domain()
    print("domain: 10,000 items, 500 anchor queries, 200 test queries")
    print("name,us_per_call,derived")
    rows = recall_budget.run(dom)

    print("\n=== recall@100 by budget ===")
    budgets = sorted({b for _, b, _ in rows})
    methods = sorted({m for m, _, _ in rows})
    header = "method".ljust(26) + "".join(f"B={b:>5} " for b in budgets)
    print(header)
    table = {(m, b): r for m, b, r in rows}
    for m in methods:
        cells = "".join(
            f"{table[(m, b)][100]:>7.3f}" if (m, b) in table else "      -"
            for b in budgets
        )
        print(m.ljust(26) + cells)


if __name__ == "__main__":
    main()
