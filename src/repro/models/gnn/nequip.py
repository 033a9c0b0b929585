"""NequIP: E(3)-equivariant interatomic potential  [arXiv:2101.03164].

Implementation notes (hardware adaptation, DESIGN.md):

- Features are irreps l=0..2 with channel multiplicity ``d_hidden``, stored
  in Cartesian form: scalars (N,h), vectors (N,h,3), symmetric-traceless
  rank-2 tensors (N,h,3,3).  The Cartesian form makes every tensor-product
  path an elementary einsum — dot, cross, symmetric outer, matrix-vector —
  which maps directly onto the TPU MXU instead of irregular CG contractions.
- Message passing is ``jax.ops.segment_sum`` over an edge index (JAX is
  BCOO-only — scatter-based message passing IS part of this system).
- Radial dependence: Bessel basis (n_rbf) with a polynomial cutoff envelope;
  per-path per-channel radial weights from a small MLP, as in the paper.
- Equivariance is property-tested: rotations of the input positions rotate
  vector features, leave energies invariant (tests/test_gnn.py).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ...configs.base import GNNConfig
from .. import layers

# tensor-product paths computed in each interaction block
_PATHS = (
    "ss", "vv_s",            # -> scalars
    "sv", "vs", "vv_v", "tv_v", "vt_v",   # -> vectors
    "st", "vv_t", "ts", "tt_t",           # -> tensors
)


def _sym_traceless(m):
    m = 0.5 * (m + jnp.swapaxes(m, -1, -2))
    tr = jnp.trace(m, axis1=-2, axis2=-1)[..., None, None]
    eye = jnp.eye(3, dtype=m.dtype)
    return m - tr * eye / 3.0


def bessel_basis(r: jax.Array, n_rbf: int, cutoff: float) -> jax.Array:
    """Radial Bessel basis with smooth polynomial cutoff envelope (paper)."""
    r = jnp.maximum(r, 1e-6)
    n = jnp.arange(1, n_rbf + 1, dtype=r.dtype)
    basis = jnp.sqrt(2.0 / cutoff) * jnp.sin(n * jnp.pi * r[..., None] / cutoff) / r[..., None]
    x = jnp.clip(r / cutoff, 0.0, 1.0)
    # p=6 polynomial envelope: 1 - 28x^6 + 48x^7 - 21x^8 (C^2-smooth at cutoff)
    env = 1.0 - 28.0 * x**6 + 48.0 * x**7 - 21.0 * x**8
    return basis * env[..., None]


def _radial_mlp_init(key, n_rbf: int, n_out: int, hidden: int = 16):
    k1, k2 = jax.random.split(key)
    return {
        "w1": layers.dense_init(k1, (n_rbf, hidden), ("rbf", "mlp")),
        "w2": layers.dense_init(k2, (hidden, n_out), ("mlp", "radial_out")),
    }


def _radial_mlp(p, rbf):
    return jax.nn.silu(rbf @ p["w1"]) @ p["w2"]


def _layer_init(key, cfg: GNNConfig):
    h = cfg.d_hidden
    ks = jax.random.split(key, 8)
    n_weights = len(_PATHS) * h       # one radial weight per path per channel
    lin = {
        # post-aggregation linear mixing per irrep (channel mixing only —
        # equivariance forbids mixing across irrep components)
        "w_s": layers.dense_init(ks[0], (2 * h, h), ("ch_in", "ch")),
        "w_v": layers.dense_init(ks[1], (2 * h, h), ("ch_in", "ch")),
        "w_t": layers.dense_init(ks[2], (2 * h, h), ("ch_in", "ch")),
        # gates: scalars produced to gate vector/tensor channels
        "w_gate": layers.dense_init(ks[3], (2 * h, 2 * h), ("ch_in", "ch")),
    }
    radial = _radial_mlp_init(ks[4], cfg.n_rbf, n_weights)
    p, s = layers.split_tree({"lin": lin, "radial": radial})
    return p, s


def init_nequip(key, cfg: GNNConfig, d_feat: int = 0):
    """d_feat>0: raw node features projected in; else species embedding."""
    h = cfg.d_hidden
    ks = jax.random.split(key, cfg.n_layers + 3)
    params: Dict = {}
    specs: Dict = {}
    if d_feat > 0:
        params["embed"], specs["embed"] = layers.dense_init(
            ks[0], (d_feat, h), ("feat", "ch")
        )
    else:
        params["embed"], specs["embed"] = layers.dense_init(
            ks[0], (cfg.n_species, h), ("species", "ch"), scale=1.0
        )
    lp = [_layer_init(ks[1 + i], cfg) for i in range(cfg.n_layers)]
    params["layers"] = [p for p, _ in lp]
    specs["layers"] = [s for _, s in lp]
    params["readout1"], specs["readout1"] = layers.dense_init(
        ks[-2], (h, h), ("ch_in", "ch")
    )
    params["readout2"], specs["readout2"] = layers.dense_init(
        ks[-1], (h, 1), ("ch_in", "unit")
    )
    return params, specs


def _edge_geometry(positions, senders, receivers, cfg: GNNConfig):
    rel = positions[receivers] - positions[senders]          # (E, 3)
    r = jnp.sqrt(jnp.sum(rel * rel, axis=-1) + 1e-12)
    rhat = rel / r[:, None]
    rbf = bessel_basis(r, cfg.n_rbf, cfg.cutoff)             # (E, n_rbf)
    # Y1 = rhat ; Y2 = sym-traceless(rhat rhat^T)
    y2 = _sym_traceless(rhat[:, :, None] * rhat[:, None, :])
    return rhat, y2, rbf


def _interact(lp, feats, senders, receivers, rhat, y2, rbf, n_nodes: int, h: int):
    """One interaction block: TP messages -> segment_sum -> linear + gate."""
    s, v, t = feats["s"], feats["v"], feats["t"]
    w = _radial_mlp(lp["radial"], rbf).reshape(-1, len(_PATHS), h)  # (E, P, h)
    wp = {name: w[:, i] for i, name in enumerate(_PATHS)}

    se, ve, te = s[senders], v[senders], t[senders]          # sender feats
    y1 = rhat[:, None, :]                                    # (E, 1, 3)
    y2e = y2[:, None, :, :]                                  # (E, 1, 3, 3)

    # --- scalar messages ---------------------------------------------------
    m_s = wp["ss"] * se                                           # s ⊗ Y0 -> s
    m_s += wp["vv_s"] * jnp.einsum("ehc,ec->eh", ve, rhat)        # v ⊗ Y1 -> s
    # --- vector messages ---------------------------------------------------
    m_v = wp["sv"][..., None] * (se[..., None] * y1)              # s ⊗ Y1 -> v
    m_v += wp["vs"][..., None] * ve                               # v ⊗ Y0 -> v
    m_v += wp["vv_v"][..., None] * jnp.cross(ve, jnp.broadcast_to(y1, ve.shape))
    m_v += wp["tv_v"][..., None] * jnp.einsum("ehij,ej->ehi", te, rhat)
    m_v += wp["vt_v"][..., None] * jnp.einsum("eij,ehj->ehi", y2, ve)
    # --- tensor messages ---------------------------------------------------
    m_t = wp["st"][..., None, None] * (se[..., None, None] * y2e)
    m_t += wp["ts"][..., None, None] * te                         # t ⊗ Y0 -> t
    outer = _sym_traceless(ve[..., :, None] * jnp.broadcast_to(y1, ve.shape)[..., None, :])
    m_t += wp["vv_t"][..., None, None] * outer                    # v ⊗ Y1 -> t
    anti = _sym_traceless(jnp.einsum("ehij,ejk->ehik", te, y2))
    m_t += wp["tt_t"][..., None, None] * anti                     # t ⊗ Y2 -> t

    agg_s = jax.ops.segment_sum(m_s, receivers, num_segments=n_nodes)
    agg_v = jax.ops.segment_sum(m_v, receivers, num_segments=n_nodes)
    agg_t = jax.ops.segment_sum(m_t, receivers, num_segments=n_nodes)

    # self-interaction: concat(old, aggregated) -> channel-mix per irrep
    cs = jnp.concatenate([s, agg_s], axis=-1)                     # (N, 2h)
    cv = jnp.concatenate([v, agg_v], axis=1)                      # (N, 2h, 3)
    ct = jnp.concatenate([t, agg_t], axis=1)                      # (N, 2h, 3, 3)
    new_s = cs @ lp["lin"]["w_s"]
    new_v = jnp.einsum("ehi,hc->eci", cv, lp["lin"]["w_v"])
    new_t = jnp.einsum("ehij,hc->ecij", ct, lp["lin"]["w_t"])
    gates = jax.nn.sigmoid(cs @ lp["lin"]["w_gate"])              # (N, 2h)
    g_v, g_t = gates[:, :new_v.shape[1]], gates[:, new_v.shape[1]:]
    return {
        "s": s + jax.nn.silu(new_s),
        "v": v + g_v[..., None] * new_v,
        "t": t + g_t[..., None, None] * new_t,
    }


def make_sharded_interact(mesh, node_axis: str = "data",
                          channel_axis: Optional[str] = "model"):
    """Receiver-partitioned, channel-TP message passing (pod-scale graphs).

    Two-axis decomposition of one interaction block:

    - ``node_axis``: edges are partitioned by RECEIVER shard (the standard
      graph-partitioning contract), so every scatter-add is shard-local;
      the only node-axis collective is one all_gather of sender features.
      Without this, XLA's scatter partitioner replicates the (N, h, 9)
      feature tensors — 83.7 GB/device on ogb_products.
    - ``channel_axis``: the irrep channel (multiplicity) dim is tensor-
      parallel — every equivariant tensor-product path is channelwise, so
      each model shard gathers/computes only its h/tp channels; only the
      channel-MIXING linears contract across shards (one psum_scatter each).
      This divides the gathered sender table (the dominant resident after
      edge chunking) by the model-axis size.

    Returns interact(lp, feats, senders, receivers, rhat, y2, rbf, n, h)
    with feats sharded (node_axis, channel_axis, ...), edges on node_axis.
    """
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[channel_axis] if channel_axis else 1

    def body(lp, feats, senders, receivers, rhat, y2, rbf):
        n_local, h_local = feats["s"].shape
        h_full = h_local * tp
        offset = jax.lax.axis_index(node_axis) * n_local
        crank = jax.lax.axis_index(channel_axis) if channel_axis else 0
        # sender features: gather full node table for MY channels only
        full = jax.tree.map(
            lambda x: jax.lax.all_gather(x, node_axis, axis=0, tiled=True), feats
        )
        local_recv = receivers - offset   # receiver-partitioned: in [0, n_local)

        def mix(cs_local, w_full, out_dim):
            """Channel-TP linear: rows of w for my channels, psum_scatter out."""
            if channel_axis is None:
                return cs_local @ w_full
            w_top = jax.lax.dynamic_slice_in_dim(w_full, crank * h_local, h_local, 0)
            w_bot = jax.lax.dynamic_slice_in_dim(
                w_full, h_full + crank * h_local, h_local, 0
            )
            partial = cs_local @ jnp.concatenate([w_top, w_bot], axis=0)
            return jax.lax.psum_scatter(
                partial, channel_axis, scatter_dimension=1, tiled=True
            )

        def radial_slice(rb):
            w = _radial_mlp(lp["radial"], rb).reshape(-1, len(_PATHS), h_full)
            if channel_axis is None:
                return w
            return jax.lax.dynamic_slice_in_dim(w, crank * h_local, h_local, 2)

        return _interact_inner_tp(
            lp, feats, full, senders, local_recv, rhat, y2, rbf,
            n_local, radial_slice, mix,
        )

    def interact(lp, feats, senders, receivers, rhat, y2, rbf, n, h):
        ch = channel_axis
        e_spec = P(node_axis)
        f_specs = {
            "s": P(node_axis, ch),
            "v": P(node_axis, ch, None),
            "t": P(node_axis, ch, None, None),
        }
        lp_spec = jax.tree.map(lambda _: P(), lp)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(lp_spec, f_specs, e_spec, e_spec, P(node_axis, None),
                      P(node_axis, None, None), P(node_axis, None)),
            out_specs=f_specs,
            check_vma=False,
        )(lp, feats, senders, receivers, rhat, y2, rbf)

    return interact


def _interact_inner_tp(lp, feats, full_feats, senders, receivers, rhat, y2,
                       rbf, n_nodes, radial_slice, mix,
                       edge_chunk: int = 262144):
    """Edge-blocked interact with pluggable radial-weight slicing and
    channel-mixing (the channel-TP hooks from make_sharded_interact)."""
    s, v, t = feats["s"], feats["v"], feats["t"]
    ne = senders.shape[0]

    def messages(sd, rh, y2c, rb):
        wfull = radial_slice(rb)                            # (E, P, h_local)
        wp = {name: wfull[:, i] for i, name in enumerate(_PATHS)}
        se, ve, te = full_feats["s"][sd], full_feats["v"][sd], full_feats["t"][sd]
        y1 = rh[:, None, :]
        y2e = y2c[:, None, :, :]
        m_s = wp["ss"] * se + wp["vv_s"] * jnp.einsum("ehc,ec->eh", ve, rh)
        m_v = wp["sv"][..., None] * (se[..., None] * y1)
        m_v += wp["vs"][..., None] * ve
        m_v += wp["vv_v"][..., None] * jnp.cross(ve, jnp.broadcast_to(y1, ve.shape))
        m_v += wp["tv_v"][..., None] * jnp.einsum("ehij,ej->ehi", te, rh)
        m_v += wp["vt_v"][..., None] * jnp.einsum("eij,ehj->ehi", y2c, ve)
        m_t = wp["st"][..., None, None] * (se[..., None, None] * y2e)
        m_t += wp["ts"][..., None, None] * te
        outer = _sym_traceless(
            ve[..., :, None] * jnp.broadcast_to(y1, ve.shape)[..., None, :]
        )
        m_t += wp["vv_t"][..., None, None] * outer
        m_t += wp["tt_t"][..., None, None] * _sym_traceless(
            jnp.einsum("ehij,ejk->ehik", te, y2c)
        )
        return m_s, m_v, m_t

    if ne > edge_chunk:
        n_chunks = -(-ne // edge_chunk)
        pad = n_chunks * edge_chunk - ne
        if pad:
            zpad = lambda x: jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0
            )
            senders, receivers = zpad(senders), zpad(receivers)
            rhat, y2, rbf = zpad(rhat), zpad(y2), zpad(rbf)
        rs = lambda x: x.reshape((n_chunks, edge_chunk) + x.shape[1:])
        xs = (rs(senders), rs(receivers), rs(rhat), rs(y2), rs(rbf))

        @jax.checkpoint
        def chunk_body(carry, x):
            a_s, a_v, a_t = carry
            sd, rc, rh, y2c, rb = x
            m_s, m_v, m_t = messages(sd, rh, y2c, rb)
            a_s = a_s + jax.ops.segment_sum(m_s, rc, num_segments=n_nodes)
            a_v = a_v + jax.ops.segment_sum(m_v, rc, num_segments=n_nodes)
            a_t = a_t + jax.ops.segment_sum(m_t, rc, num_segments=n_nodes)
            return (a_s, a_v, a_t), None

        init = (jnp.zeros_like(s), jnp.zeros_like(v), jnp.zeros_like(t))
        (agg_s, agg_v, agg_t), _ = jax.lax.scan(chunk_body, init, xs)
    else:
        m_s, m_v, m_t = messages(senders, rhat, y2, rbf)
        agg_s = jax.ops.segment_sum(m_s, receivers, num_segments=n_nodes)
        agg_v = jax.ops.segment_sum(m_v, receivers, num_segments=n_nodes)
        agg_t = jax.ops.segment_sum(m_t, receivers, num_segments=n_nodes)

    cs = jnp.concatenate([s, agg_s], axis=-1)
    cv = jnp.concatenate([v, agg_v], axis=1)
    ct = jnp.concatenate([t, agg_t], axis=1)
    new_s = mix(cs, lp["lin"]["w_s"], None)
    new_v = jnp.moveaxis(mix(jnp.moveaxis(cv, 1, -1).reshape(n_nodes * 3, -1),
                             lp["lin"]["w_v"], None).reshape(n_nodes, 3, -1), -1, 1)
    new_t = jnp.moveaxis(mix(jnp.moveaxis(ct, 1, -1).reshape(n_nodes * 9, -1),
                             lp["lin"]["w_t"], None).reshape(n_nodes, 3, 3, -1), -1, 1)
    # gate halves mixed separately: psum_scatter hands each shard a
    # CONTIGUOUS output slice, so the [v-gates | t-gates] layout must be
    # scattered per half to land on the right channel block
    h_full_out = lp["lin"]["w_gate"].shape[1] // 2
    g_v = jax.nn.sigmoid(mix(cs, lp["lin"]["w_gate"][:, :h_full_out], None))
    g_t = jax.nn.sigmoid(mix(cs, lp["lin"]["w_gate"][:, h_full_out:], None))
    return {
        "s": s + jax.nn.silu(new_s),
        "v": v + g_v[..., None] * new_v,
        "t": t + g_t[..., None, None] * new_t,
    }


def forward(
    params,
    cfg: GNNConfig,
    positions: jax.Array,        # (N, 3)
    node_attr: jax.Array,        # (N,) species int OR (N, d_feat) float
    senders: jax.Array,          # (E,)
    receivers: jax.Array,        # (E,)
    edge_mask: Optional[jax.Array] = None,   # (E,) padding mask
    node_mask: Optional[jax.Array] = None,   # (N,) padding mask
    graph_ids: Optional[jax.Array] = None,   # (N,) for batched small graphs
    n_graphs: int = 1,
    feat_spec=None,                          # PartitionSpec for (N, ...) feats
    remat: bool = False,                     # checkpoint each interaction block
    interact_fn=None,                        # e.g. make_sharded_interact(mesh)
) -> jax.Array:
    """Per-graph potential energies (n_graphs,)."""
    n_nodes = positions.shape[0]
    h = cfg.d_hidden
    if node_attr.ndim == 1:
        s = jnp.take(params["embed"], node_attr % params["embed"].shape[0], axis=0)
    else:
        s = node_attr @ params["embed"]
    feats = {
        "s": s,
        "v": jnp.zeros((n_nodes, h, 3), s.dtype),
        "t": jnp.zeros((n_nodes, h, 3, 3), s.dtype),
    }

    def _constrain(f):
        if feat_spec is None:
            return f
        import jax.sharding as shd
        # feat_spec is the (possibly multi-axis) sharding of the NODE dim
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, shd.PartitionSpec(feat_spec, *((None,) * (x.ndim - 1)))
            ),
            f,
        )

    feats = _constrain(feats)
    rhat, y2, rbf = _edge_geometry(positions, senders, receivers, cfg)
    if edge_mask is not None:
        rbf = rbf * edge_mask[:, None]
    block = interact_fn if interact_fn is not None else _interact
    if remat:
        block = jax.checkpoint(block, static_argnums=(7, 8))
    for lp in params["layers"]:
        feats = _constrain(
            block(lp, feats, senders, receivers, rhat, y2, rbf, n_nodes, h)
        )
    node_e = jax.nn.silu(feats["s"] @ params["readout1"]) @ params["readout2"]
    node_e = node_e[:, 0]
    if node_mask is not None:
        node_e = node_e * node_mask
    if graph_ids is None:
        return jnp.sum(node_e, keepdims=True)
    return jax.ops.segment_sum(node_e, graph_ids, num_segments=n_graphs)


def energy_and_forces(params, cfg: GNNConfig, positions, node_attr, senders, receivers, **kw):
    """Forces = -dE/dpositions (autodiff through the whole network)."""
    def e_total(pos):
        return forward(params, cfg, pos, node_attr, senders, receivers, **kw).sum()

    e, grad = jax.value_and_grad(e_total)(positions)
    return e, -grad


def energy_mse_loss(params, cfg: GNNConfig, batch, n_graphs: int = 1,
                    feat_spec=None, remat: bool = False,
                    interact_fn=None) -> jax.Array:
    """MSE on per-graph energies. ``n_graphs`` is static (segment count)."""
    e = forward(
        params, cfg,
        batch["positions"], batch["node_attr"],
        batch["senders"], batch["receivers"],
        edge_mask=batch.get("edge_mask"),
        node_mask=batch.get("node_mask"),
        graph_ids=batch.get("graph_ids"),
        n_graphs=n_graphs,
        feat_spec=feat_spec,
        remat=remat,
        interact_fn=interact_fn,
    )
    target = batch["energy"]
    return jnp.mean((e - target) ** 2)
