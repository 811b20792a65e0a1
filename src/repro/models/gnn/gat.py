"""GAT — the paper's heaviest BR user (Table 2, row 8):

    e_copy_add_v, e_copy_max_v, u_add_v_copy_e, e_sub_v_copy_e,
    e_div_v_copy_e, u_mul_e_add_v, v_mul_e_copy_e

Attention logits per edge via the planned gSDDMM (``u_add_v_copy_e``);
normalization via edge-softmax; aggregation via ``u_mul_e_add_v`` with
per-head scalars. ``attn`` selects how much of that pipeline fuses:

    'multipass'     — gsddmm logits + composed 5-primitive softmax +
                      separate weighted aggregate (the paper's layering),
    'softmax-fused' — single-pass softmax, separate logits/aggregate,
    'fused'/'pallas'/'auto'
                    — the whole pipeline as ONE planned pass
                      (:func:`repro.core.fused_attention`, DESIGN.md §9).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ...core.binary_reduce import gsddmm, gspmm
from ...core.blocks import block_gspmm
from ...core.edge_softmax import (block_edge_softmax,
                                  block_fused_attention, edge_softmax,
                                  edge_softmax_fused, fused_attention,
                                  fused_attention_partitioned)
from ...substrate.nn import glorot, dropout, leaky_relu
from .common import GraphBundle, PartitionedBundle, run_blocks

_ATTN_MODES = ("multipass", "softmax-fused", "fused", "pallas", "auto")


def init(key, d_in: int, d_hidden: int, n_classes: int, n_heads: int = 4,
         n_layers: int = 2) -> Dict:
    layers = []
    d = d_in
    for i in range(n_layers):
        out = n_classes if i == n_layers - 1 else d_hidden
        heads = 1 if i == n_layers - 1 else n_heads
        key, k1, k2, k3 = jax.random.split(key, 4)
        layers.append({
            "w": glorot(k1, (d, heads * out)),
            "attn_l": glorot(k2, (heads, out)),
            "attn_r": glorot(k3, (heads, out)),
        })
        d = heads * out
    return {"layers": layers}


def _resolve_attn(attn: Optional[str], fused_softmax: bool) -> str:
    """Back-compat: ``fused_softmax`` predates ``attn`` and keeps its
    meaning when ``attn`` is not given."""
    if attn is None:
        return "softmax-fused" if fused_softmax else "multipass"
    if attn not in _ATTN_MODES:
        raise ValueError(f"unknown attn mode {attn!r}; expected one of "
                         f"{_ATTN_MODES}")
    return attn


def _gat_layer(lyr, bundle: GraphBundle, h, heads: int, out: int, *,
               strategy: str, attn: str):
    g = bundle.g
    z = (h @ lyr["w"]).reshape(-1, heads, out)           # (n, H, F)
    el = jnp.sum(z * lyr["attn_l"], axis=-1)             # (n, H)
    er = jnp.sum(z * lyr["attn_r"], axis=-1)
    if attn in ("fused", "pallas", "auto"):
        out_feat = fused_attention(g, el, er, z, strategy=attn)
        return out_feat.reshape(-1, heads * out)
    # u_add_v_copy_e: per-edge logits on the planned gSDDMM path; a
    # pinned gspmm strategy maps onto the sddmm lattice like gspmm's own
    # edge-output delegation (baselines pin the caller-order gather)
    sddmm_req = {"auto": "auto", "pallas": "pallas", "push": "gather",
                 "segment": "gather"}.get(strategy, "canonical")
    logits = gsddmm(g, "u_add_v_copy_e", u=el, v=er, strategy=sddmm_req)
    logits = leaky_relu(logits)
    if attn == "softmax-fused":
        alpha = edge_softmax_fused(g, logits)            # (nnz, H)
    else:
        alpha = edge_softmax(g, logits, strategy=strategy,
                             cache=bundle.cache)
    # u_mul_e_add_v with per-head scalar α is a 3-D broadcast: the
    # planner keeps it on segment/ell (pallas/onehot are rank-2 only)
    out_feat = gspmm(g, "u_mul_e_add_v", u=z, e=alpha[:, :, None],
                     strategy=strategy, cache=bundle.cache)
    return out_feat.reshape(-1, heads * out)


def forward(params: Dict, bundle: GraphBundle, x: jnp.ndarray, *,
            strategy: str = "auto", train: bool = False, rng=None,
            drop: float = 0.4, fused_softmax: bool = False,
            attn: Optional[str] = None) -> jnp.ndarray:
    attn = _resolve_attn(attn, fused_softmax)
    h = x
    n_layers = len(params["layers"])
    for i, lyr in enumerate(params["layers"]):
        hd = lyr["attn_l"].shape[0]     # heads encoded in param shapes
        out = lyr["attn_l"].shape[-1]
        with jax.named_scope(f"gat.layer{i}"):
            if train and rng is not None:
                rng, sub = jax.random.split(rng)
                h = dropout(sub, h, drop, train)
            h = _gat_layer(lyr, bundle, h, hd, out, strategy=strategy,
                           attn=attn)
            if i < n_layers - 1:
                h = jax.nn.elu(h)
    return h


def block_layer(lyr, blk, h: jnp.ndarray, *, strategy: str = "auto",
                bwd_strategy: str = "auto",
                attn: str = "multipass") -> jnp.ndarray:
    """One GAT layer on a sampled block.

    Attention logits are per-edge over the block's sampled edges; the
    destination-side term uses ``z[:n_dst_real]`` (dst-first numbering)
    padded with one dummy row, and the softmax normalizes over each
    destination's REAL in-edges only (pads live in the dummy row)."""
    bg = blk.bg
    heads, out = lyr["attn_l"].shape
    z = (h @ lyr["w"]).reshape(-1, heads, out)           # (n_src_pad, H, F)
    el = jnp.sum(z * lyr["attn_l"], axis=-1)             # (n_src_pad, H)
    er = jnp.sum(z[: bg.n_dst_real] * lyr["attn_r"], axis=-1)
    er = jnp.concatenate([er, jnp.zeros((1, heads), er.dtype)], axis=0)
    if attn in ("fused", "pallas", "auto"):
        out_feat = block_fused_attention(bg, el, er, z, strategy=attn)
        return out_feat.reshape(bg.n_dst_real, heads * out)
    logits = gsddmm(bg.g, "u_add_v_copy_e", u=el, v=er)
    logits = leaky_relu(logits)
    alpha = block_edge_softmax(bg, logits, strategy=strategy,
                               bwd_strategy=bwd_strategy)  # (nnz, H)
    out_feat = block_gspmm(bg, "u_mul_e_add_v", u=z, e=alpha[:, :, None],
                           strategy=strategy,
                           bwd_strategy=bwd_strategy)    # (nd, H, F)
    return out_feat.reshape(bg.n_dst_real, heads * out)


def forward_blocks(params: Dict, blocks, x: jnp.ndarray, *,
                   strategy: str = "auto", bwd_strategy: str = "auto",
                   train: bool = False, rng=None, drop: float = 0.4,
                   attn: Optional[str] = None) -> jnp.ndarray:
    """Sampled mini-batch forward on the shared block path."""
    attn = _resolve_attn(attn, False) if attn is not None else "multipass"

    def layer(lyr, blk, h, **kw):
        return block_layer(lyr, blk, h, attn=attn, **kw)

    return run_blocks(layer, params["layers"], blocks, x,
                      strategy=strategy, bwd_strategy=bwd_strategy,
                      activation=jax.nn.elu,
                      train=train, rng=rng, drop=drop, scope="gat")


def forward_partitioned(params: Dict, pb: PartitionedBundle,
                        x: jnp.ndarray, *, halo=None, refresh: bool = True,
                        comm_state=None, train: bool = False, rng=None,
                        drop: float = 0.4):
    """Partitioned full-graph GAT (always exact — attention weights are
    parameter-dependent, so a stale remote partial has no DistGNN-style
    formulation; delayed halos are a GCN/SAGE knob).

    Each layer is one :func:`fused_attention_partitioned` call: a ring
    pass assembles bucketed logits, the softmax normalizes each
    destination locally (every dst bucket is owner-resident), and a
    second ring pass does the α-weighted aggregation with per-head
    weights.

    int8-compressed exchanges (``comm_state``) are a GCN/SAGE knob too:
    GAT's exchanges carry pre-softmax logits whose quantization error
    amplifies through exp(), and the two-ring fused pass has no single
    payload for error feedback to track (DESIGN.md §12). Train GAT in
    bf16 with uncompressed rings instead.
    """
    if halo is not None:
        raise ValueError("GAT has no delayed-halo mode (attention "
                         "weights are parameter-dependent)")
    if comm_state is not None:
        raise ValueError("GAT has no compressed-comm mode (the fused "
                         "attention rings exchange pre-softmax logits; "
                         "see DESIGN.md §12)")
    pg = pb.pg
    h = x
    n_layers = len(params["layers"])
    for i, lyr in enumerate(params["layers"]):
        heads, out = lyr["attn_l"].shape
        if train and rng is not None:
            rng, sub = jax.random.split(rng)
            h = dropout(sub, h, drop, train)
        z = (h @ lyr["w"]).reshape(-1, heads, out)       # (n_pad, H, F)
        el = jnp.sum(z * lyr["attn_l"], axis=-1)         # (n_pad, H)
        er = jnp.sum(z * lyr["attn_r"], axis=-1)
        out_feat = fused_attention_partitioned(pg, el, er, z,
                                               mesh=pb.mesh, axis=pb.axis)
        h = out_feat.reshape(-1, heads * out)
        if i < n_layers - 1:
            h = jax.nn.elu(h)
    return h, None


def infer(params: Dict, bundle: GraphBundle, x: jnp.ndarray, *,
          strategy: str = "auto",
          attn: Optional[str] = None) -> jnp.ndarray:
    """Inference-mode forward — the serving tier's layer-wise refresh
    entry point (dropout off, no rng threading)."""
    return forward(params, bundle, x, strategy=strategy, train=False,
                   attn=attn)


def infer_blocks(params: Dict, blocks, x: jnp.ndarray, *,
                 strategy: str = "auto",
                 attn: Optional[str] = None) -> jnp.ndarray:
    """Inference-mode block forward — the serving tier's fan-out path.

    Defaults to the same multipass softmax family as the full forward
    so the two serve modes agree to float tolerance."""
    return forward_blocks(params, blocks, x, strategy=strategy,
                          train=False, attn=attn)
