"""Shared transformer building blocks: norms, rope, attention, MLP, MoE.

Attention is *q-chunked* everywhere (lax.map over query chunks): peak score
memory is bounded by (B, H, chunk, S_kv) regardless of sequence length, which
is what lets prefill_32k lower without materializing 32k x 32k score tensors.
The KV cache is a ring buffer over ``capacity`` slots with per-slot absolute
positions, which unifies full attention (capacity = max_len) and sliding
window (capacity = window) under one code path.

MoE uses expert parallelism via shard_map: activations are replicated over
the 'model' axis (megatron convention), so each model shard gathers the
tokens routed to *its* experts locally and one psum combines expert outputs
— the same collective shape as a row-parallel MLP, no all-to-all and no
GShard dispatch-einsum fake FLOPs.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

Params = dict[str, Any]


# ------------------------------------------------------------------- basics
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    # NB: keep the f32 upcast as an explicit astype: the astype boundary is
    # what casts the backward cotangent back to bf16. (An einsum with
    # preferred_element_type=f32 computes the same variance but leaks f32
    # cotangents into every residual all-reduce — observed 2x collective
    # bytes on granite train_4k.)
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if ang.ndim == 2:  # (S, half) -> broadcast over batch and heads
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:  # (B, S, half)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


# ---------------------------------------------------------------- attention
def _attend(
    q: jax.Array,  # (B, Sq, H, hd) — already rope'd
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,  # (B, Sk, KV, hd)
    q_pos: jax.Array,  # (B, Sq) absolute positions of queries
    k_pos: jax.Array,  # (Sk,) absolute positions of keys (-1 = empty slot)
    window: int,  # attend iff 0 <= qpos - kpos < window (causal SWA)
    causal: bool,
    q_seg: jax.Array | None = None,  # (B, Sq) packing segment ids (0 = pad)
    k_seg: jax.Array | None = None,  # (B, Sk)
) -> jax.Array:
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / jnp.sqrt(hd).astype(q.dtype)
    scores = scores.astype(jnp.float32)
    dist = q_pos[:, None, None, :, None] - k_pos[None, None, None, None, :]
    valid = k_pos[None, None, None, None, :] >= 0
    if causal:
        valid &= (dist >= 0) & (dist < window)
    if q_seg is not None and k_seg is not None:
        # packed sequences: attend only within the same document segment
        same = (
            q_seg[:, None, None, :, None] == k_seg[:, None, None, None, :]
        ) & (q_seg[:, None, None, :, None] > 0)
        valid &= same
    scores = jnp.where(valid, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(jnp.isfinite(scores).any(-1, keepdims=True), p, 0.0)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return out.reshape(b, sq, h, hd)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,  # (Sq,) absolute query positions (shared across batch)
    k_pos: jax.Array,  # (Sk,)
    window: int,
    causal: bool,
    chunk: int,
    segments: jax.Array | None = None,  # (B, S) packing segment ids
) -> jax.Array:
    """lax.map over query chunks — bounded score memory for long sequences."""
    b, sq, h, hd = q.shape
    chunk = min(chunk, sq)
    k_seg = segments
    if sq % chunk != 0:  # pad queries; padded rows discarded after
        pad = (-sq) % chunk
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-(10**9))
        if segments is not None:
            segments = jnp.pad(segments, ((0, 0), (0, pad)))
    nc = q.shape[1] // chunk
    qc = q.reshape(b, nc, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(nc, chunk)
    sc = (
        segments.reshape(b, nc, chunk).transpose(1, 0, 2)
        if segments is not None else None
    )

    def one(args):
        if segments is not None:
            qi, pi, si = args
        else:
            qi, pi = args
            si = None
        return _attend(
            qi, k, v, jnp.broadcast_to(pi, (b, chunk)), k_pos, window, causal,
            q_seg=si, k_seg=k_seg,
        )

    xs = (qc, pc, sc) if segments is not None else (qc, pc)
    out = jax.lax.map(one, xs)  # (nc, B, chunk, H, hd)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nc * chunk, h, hd)
    return out[:, :sq]


def self_attention_train(
    p: Params, x: jax.Array, cfg: ModelConfig, window: int,
    return_kv: bool = False, segments: jax.Array | None = None,
):
    """Training / scoring path: full sequence, causal (or SWA) mask.
    ``segments`` (B, S) enables packed-sequence isolation (0 = padding)."""
    b, s, d = x.shape
    pos = jnp.arange(s)
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    if cfg.attn_impl == "flash" and window >= s and segments is None:
        from repro.kernels import ops as _kops

        out = _kops.flash_attention(q, k, v, causal=True, backend="pallas")
    else:
        out = chunked_attention(
            q, k, v, pos, pos, window, True, cfg.attn_chunk, segments=segments
        )
    out = out.reshape(b, s, cfg.q_dim) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def ring_cache_from_prefill(k: jax.Array, v: jax.Array, cap: int):
    """Fold full-sequence (B, S, KV, hd) K/V into a ring cache of ``cap``
    slots. Requires cap | S so slot s holds absolute position S - cap + s."""
    s = k.shape[1]
    assert s % cap == 0, "ring capacity must divide prefill length"
    slot_pos = jnp.arange(cap, dtype=jnp.int32) + (s - cap)
    return k[:, s - cap :], v[:, s - cap :], slot_pos


def encoder_attention(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Bidirectional (whisper encoder)."""
    b, s, d = x.shape
    pos = jnp.arange(s)
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    out = chunked_attention(q, k, v, pos, pos, s, False, cfg.attn_chunk)
    return out.reshape(b, s, cfg.q_dim) @ p["wo"]


def cross_attention(
    p: Params, x: jax.Array, kv_src: jax.Array, cfg: ModelConfig
) -> jax.Array:
    """x attends to media/encoder states. kv_src: (B, M, D) or precomputed
    (k, v) tuple of (B, M, KV, hd) when serving from cache."""
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    if isinstance(kv_src, tuple):
        k, v = kv_src
    else:
        m = kv_src.shape[1]
        k = (kv_src @ p["wk"]).reshape(b, m, cfg.n_kv_heads, cfg.head_dim)
        v = (kv_src @ p["wv"]).reshape(b, m, cfg.n_kv_heads, cfg.head_dim)
    m = k.shape[1]
    pos_q = jnp.arange(s)
    pos_k = jnp.arange(m)
    out = chunked_attention(q, k, v, pos_q, pos_k, m + s + 1, False, cfg.attn_chunk)
    return out.reshape(b, s, cfg.q_dim) @ p["wo"]


def self_attention_decode(
    p: Params,
    x: jax.Array,  # (B, 1, D) current token
    cache_k: jax.Array,  # (B, C, KV, hd) ring buffer
    cache_v: jax.Array,
    slot_pos: jax.Array,  # (C,) absolute position stored in each slot (-1 empty)
    pos: jax.Array,  # () current absolute position
    cfg: ModelConfig,
    window: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One decode step against the ring cache. Returns (out, k', v', slot')."""
    b = x.shape[0]
    cap = cache_k.shape[1]
    q = (x @ p["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    posb = jnp.broadcast_to(pos[None], (1,))
    q = rope(q, posb, cfg.rope_theta)
    k = rope(k, posb, cfg.rope_theta)
    slot = pos % cap
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)
    slot_pos = jax.lax.dynamic_update_slice_in_dim(
        slot_pos, posb.astype(slot_pos.dtype), slot, axis=0
    )
    out = _attend(
        q, cache_k, cache_v,
        jnp.broadcast_to(pos[None, None], (b, 1)), slot_pos, window, True,
    )
    return out.reshape(b, 1, cfg.q_dim) @ p["wo"], cache_k, cache_v, slot_pos


# ---------------------------------------------------------------------- MLP
def mlp(p: Params, x: jax.Array) -> jax.Array:
    return swiglu(x, p["wg"], p["wu"], p["wd"])


# ---------------------------------------------------------------------- MoE
def _router(p: Params, xf: jax.Array, cfg: ModelConfig):
    """Top-k routing + switch-style load-balance aux loss."""
    logits = (xf.astype(jnp.float32)) @ p["wr"].astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)  # (T, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # aux: E * sum_e mean(one_hot tokens_e) * mean(probs_e)
    onehot = jax.nn.one_hot(ids[:, 0], cfg.n_experts)  # top-1 load
    aux = cfg.n_experts * jnp.mean(
        jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0)
    )
    return weights.astype(xf.dtype), ids, aux


def _expert_block(xf, ids, weights, wg, wu, wd, e_offset, capacity):
    """Compute the experts owned locally (wg/wu/wd: (E_loc, ...)) and return
    the weighted partial output (T, D). Tokens over capacity are dropped."""
    t = xf.shape[0]
    e_loc = wg.shape[0]
    out = jnp.zeros_like(xf)
    for j in range(e_loc):  # E_loc is tiny (1 on the production mesh)
        e = e_offset + j
        m = ids == e  # (T, k)
        tok_w = jnp.sum(jnp.where(m, weights, 0.0), axis=-1)  # (T,)
        routed = jnp.any(m, axis=-1)
        rank = jnp.cumsum(routed.astype(jnp.int32)) - 1
        slot = jnp.where(routed & (rank < capacity), rank, capacity)
        dispatch = jnp.full((capacity + 1,), t, jnp.int32)
        dispatch = dispatch.at[slot].set(jnp.arange(t, dtype=jnp.int32), mode="drop")
        dispatch = dispatch[:capacity]
        xe = jnp.concatenate([xf, jnp.zeros_like(xf[:1])], 0)[dispatch]  # (C, D)
        he = (jax.nn.silu(xe @ wg[j]) * (xe @ wu[j])) @ wd[j]  # (C, D)
        we = jnp.concatenate([tok_w, jnp.zeros_like(tok_w[:1])], 0)[dispatch]
        out = out.at[dispatch].add(he * we[:, None], mode="drop")
    return out


def moe_ffn(
    p: Params,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    mesh: jax.sharding.Mesh | None = None,
    batch_axes: tuple[str, ...] = ("data",),
    model_axis: str = "model",
    capacity: int | None = None,  # None -> capacity_factor rule; -1 -> all
                                   # local tokens (lossless; decode uses this)
) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE FFN. Returns (out, aux_loss).

    With a mesh: shard_map over (batch_axes + model_axis); activations are
    replicated over 'model', each model shard computes its E/tp experts on
    locally-gathered tokens, and one psum over 'model' combines outputs —
    identical collective shape to a row-parallel dense MLP.
    """
    b, s, d = x.shape

    # Tiny batches (long-context decode has global_batch = 1) cannot shard
    # over the data axes — fall back to replicated tokens, keeping the
    # expert-parallel split over 'model'.
    if mesh is not None and batch_axes:
        dp_check = 1
        for a in batch_axes:
            dp_check *= dict(mesh.shape).get(a, 1)
        if b % dp_check != 0:
            batch_axes = ()

    if mesh is None or model_axis not in mesh.shape or mesh.shape[model_axis] == 1:
        xf = x.reshape(b * s, d)
        weights, ids, aux = _router(p, xf, cfg)
        if capacity == -1:
            cap = xf.shape[0]
        elif capacity is not None:
            cap = capacity
        else:
            cap = max(
                1, int(cfg.top_k * xf.shape[0] / cfg.n_experts * cfg.capacity_factor)
            )
        out = _expert_block(xf, ids, weights, p["wg"], p["wu"], p["wd"], 0, cap)
        return out.reshape(b, s, d), aux

    tp = mesh.shape[model_axis]
    e_loc = cfg.n_experts // tp
    dp = 1
    for a in batch_axes:
        dp *= dict(mesh.shape).get(a, 1)
    t_loc = (b // dp) * s
    if capacity == -1:
        cap = t_loc
    elif capacity is not None:
        cap = capacity
    else:
        cap = max(1, int(cfg.top_k * t_loc / cfg.n_experts * cfg.capacity_factor))

    # When the batch cannot use the 'data' axis (long-context decode,
    # global_batch = 1), shard each expert's d_ff over 'data' instead: the
    # weights arrive already 2D-sharded (experts x ff), so no expert-weight
    # all-gather is needed — one extra psum over 'data' combines the
    # ff-partial outputs (beyond-paper optimization, §Perf).
    ff_axis = None
    names = dict(mesh.shape)
    if (
        not batch_axes
        and names.get("data", 1) > 1
        and cfg.d_ff % names["data"] == 0
    ):
        ff_axis = "data"

    def body(xb, wr, wg, wu, wd):
        xf = xb.reshape(-1, d)
        weights, ids, aux = _router({"wr": wr}, xf, cfg)
        e_offset = jax.lax.axis_index(model_axis) * e_loc
        out = _expert_block(xf, ids, weights, wg, wu, wd, e_offset, cap)
        axes = (model_axis,) if ff_axis is None else (model_axis, ff_axis)
        out = jax.lax.psum(out, axes)
        aux = jax.lax.pmean(aux, tuple(batch_axes) + (model_axis,))
        return out.reshape(xb.shape), aux

    bspec = P(batch_axes or None, None, None)
    # check_vma=False: the replication checker rejects the body's
    # axis_index-dependent routing.
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(bspec, P(None, None),
                  P(model_axis, None, ff_axis),
                  P(model_axis, None, ff_axis),
                  P(model_axis, ff_axis, None)),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, p["wr"], p["wg"], p["wu"], p["wd"])
    return out, aux
