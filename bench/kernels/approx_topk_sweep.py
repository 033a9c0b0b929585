"""``approx_topk_sweep``: one fused pass of S_hat = e_q @ R_anc plus a
masked per-tile top-k over the item axis.

Bytes are the payload (k_q x N at ``payload_bytes`` each), the int8
suppression mask (B x N) and the query block, read once, plus the per-tile
top-k lists written once.  FLOPs are the estimate GEMM (B k_q N MACs).
"""


def cost(batch: int, k_q: int, n_items: int, k: int, tile: int,
         payload_bytes: int) -> tuple:
    n_tiles = -(-n_items // tile)
    flops = 2.0 * batch * k_q * n_items
    nbytes = (k_q * n_items * payload_bytes + batch * n_items
              + batch * k_q * 4 + 2 * n_tiles * batch * k * 4)
    return flops, float(nbytes)
