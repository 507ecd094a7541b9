"""Shared model building blocks: norms, RoPE/M-RoPE, GQA attention (einsum +
query-chunked memory-efficient variants), MLPs, embeddings.

Conventions:
 * activations  (B, S, D);  queries (B, S, KV, G, hd);  keys/values
   (B, T, KV, hd) — GQA is a grouped einsum, repeated KV is never
   materialized;
 * masks are built on the fly from position vectors (never a materialized
   (S, S) array at long context);
 * softmax/normalization in float32, matmuls in the model dtype with float32
   accumulation via ``preferred_element_type``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constraint


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE / M-RoPE
# --------------------------------------------------------------------------
def rope_inv_freq(head_dim: int, theta: float) -> np.ndarray:
    """Default RoPE frequencies ``theta ** (-2i / head_dim)``, float32."""
    half = head_dim // 2
    return (1.0 / theta ** (np.arange(half, dtype=np.float64) * 2.0
                            / head_dim)).astype(np.float32)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_positions: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN frequencies (arXiv:2309.00071) as Hugging Face's ``rope_type:
    yarn`` defines them (``truncate`` on): slot i keeps its default
    frequency below the ``beta_fast`` correction dimension, is divided by
    ``factor`` above the ``beta_slow`` one, and is blended linearly in
    between.  The attention factor scales cos and sin, not these."""
    half = head_dim // 2
    pos_freqs = theta ** (np.arange(half, dtype=np.float64) * 2.0
                          / head_dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(original_max_positions
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp                   # share of the default frequency
    return (interpolation * (1.0 - keep) + extrapolation * keep
            ).astype(np.float32)


def rope_cos_sin_freqs(positions: jax.Array, inv_freq,
                       scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """positions (..., S) int32 -> cos, sin (..., S, hd/2) of the given
    frequencies, each multiplied by ``scale`` (YaRN's attention factor)."""
    angles = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float,
                 sections: Optional[tuple] = None
                 ) -> tuple[jax.Array, jax.Array]:
    """positions: (B, S) int32, or (C, B, S) for M-RoPE with C position
    channels (temporal/height/width). Returns cos/sin of shape (B, S, hd/2).

    M-RoPE (Qwen2-VL): frequency slot i draws its position from channel
    section_id(i), with ``sections`` giving the per-channel slot counts.
    """
    half = head_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    pos = positions if positions.ndim == 3 else positions[None]
    if sections is None:
        sec_ids = np.zeros((half,), dtype=np.int32)
    else:
        assert sum(sections) == half, (sections, half)
        sec_ids = np.repeat(np.arange(len(sections)), sections).astype(np.int32)
    pos_sel = pos[sec_ids]                      # (half, B, S)
    angles = jnp.einsum("hbs,h->bsh", pos_sel.astype(jnp.float32), freq)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, ..., hd); cos/sin: (B, S, hd/2) broadcast over head dims."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    shape = cos.shape[:2] + (1,) * (x.ndim - 3) + cos.shape[2:]
    c, s = cos.reshape(shape), sin.reshape(shape)
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s],
                           axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
NEG_INF = -1e30


def _band_bias(q_pos: jax.Array, kv_pos: jax.Array, causal: bool,
               window) -> jax.Array:
    """Additive bias (..., Sq, Tk) computed from positions; ``window`` may be
    a traced scalar (0 = unwindowed) so local/global layers share one scan
    body."""
    q = q_pos[..., :, None].astype(jnp.int32)
    k = kv_pos[..., None, :].astype(jnp.int32)
    ok = jnp.ones(q.shape[:-1] + (k.shape[-1],), dtype=bool)
    if causal:
        ok = ok & (k <= q)
    w = jnp.asarray(window, jnp.int32)
    ok = ok & ((w <= 0) | (q - k < w))
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def repeat_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(B,T,KV,hd) -> (B,T,H,hd). Flat-head layout keeps every tensor sharded
    on the H axis — GSPMD propagates it cleanly, whereas a (KV,G) grouped
    reshape of an H-sharded tensor forces involuntary rematerialization
    (observed; see DESIGN.md §6). XLA fuses the broadcast into the dot."""
    B, T, KV, hd = k.shape
    if KV == num_heads:
        return k
    G = num_heads // KV
    return jnp.broadcast_to(k[:, :, :, None, :], (B, T, KV, G, hd)) \
        .reshape(B, T, num_heads, hd)


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  q_pos: jax.Array, kv_pos: jax.Array,
                  causal: bool = True, window=0,
                  kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """Einsum attention. q: (B,S,H,hd), k/v: (B,T,KV,hd) -> (B,S,H,hd).

    ``kv_valid``: optional (B, T) bool marking populated cache slots
    (decode). Softmax in f32.
    """
    H = q.shape[2]
    k = repeat_kv(k, H)
    v = repeat_kv(v, H)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * scale
    bias = _band_bias(q_pos, kv_pos, causal, window)      # (S, T) or (B,S,T)
    while bias.ndim < scores.ndim:
        bias = bias[..., None, :, :] if bias.ndim >= 3 else bias[None]
    scores = scores + bias
    if kv_valid is not None:
        scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      q_pos: jax.Array, kv_pos: jax.Array,
                      causal: bool = True, window=0,
                      chunk: int = 512, band: int = 0) -> jax.Array:
    """Memory-efficient attention: map over query chunks so peak live memory
    is O(S * chunk) instead of O(S^2). The XLA analogue of flash attention —
    the Pallas kernel (`repro.kernels.flash_attention`) is the TPU hot path
    for inference; this is the portable default for 32k+ prefill, and the
    training path (the Pallas kernel has no backward pass).

    Each chunk is rematerialised in the backward pass (``jax.checkpoint``),
    so a gradient keeps O(S * chunk) live as well.  With a static ``band``
    > 0 (self-attention over one run of positions, ``kv_pos == q_pos``,
    windowed to ``band``), chunk i reads only the keys from ``band`` before
    its first query to its last: O(S * (chunk + band)) work, not O(S^2)."""
    B, S, H, hd = q.shape
    assert S % chunk == 0, (S, chunk)
    nq = S // chunk
    qc = q.reshape(B, nq, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(nq, chunk)
    if band:
        window = band
        pad = ((0, 0), (band, 0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        # Padding keys sit more than ``band`` before every query: masked.
        kv_pos = jnp.pad(kv_pos, (band, 0), constant_values=-(1 << 30))
    starts = jnp.arange(nq, dtype=jnp.int32) * chunk

    @jax.checkpoint
    def one_chunk(args):
        qi, pi, start = args
        ki, vi, ti = k, v, kv_pos
        if band:
            ki = jax.lax.dynamic_slice_in_dim(k, start, chunk + band, 1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, chunk + band, 1)
            ti = jax.lax.dynamic_slice_in_dim(kv_pos, start, chunk + band)
        return gqa_attention(qi, ki, vi, q_pos=pi, kv_pos=ti,
                             causal=causal, window=window)

    out = jax.lax.map(one_chunk, (qc, pc, starts))  # (nq, B, chunk, H, hd)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def causal_prefix_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            chunk: int, groups: int) -> jax.Array:
    """Causal self-attention over positions 0..S-1 in ``groups`` runs of
    queries, each reading only the keys up to its own last position (its
    causal prefix), through :func:`chunked_attention`: the key work is
    (groups + 1) / (2 * groups) of attending every query to all S keys."""
    S = q.shape[1]
    step = S // groups
    assert S % groups == 0 and step % chunk == 0, (S, groups, chunk)
    pos = jnp.arange(S, dtype=jnp.int32)
    outs = []
    for g in range(groups):
        lo, hi = g * step, (g + 1) * step
        outs.append(chunked_attention(q[:, lo:hi], k[:, :hi], v[:, :hi],
                                      q_pos=pos[lo:hi], kv_pos=pos[:hi],
                                      chunk=chunk))
    return jnp.concatenate(outs, axis=1)


def attention(q, k, v, *, q_pos, kv_pos, causal=True, window=0,
              kv_valid=None, impl: str = "einsum", chunk: int = 512):
    if impl == "chunked" and q.shape[1] > chunk and kv_valid is None:
        return chunked_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                                 causal=causal, window=window, chunk=chunk)
    return gqa_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
                         window=window, kv_valid=kv_valid)


# --------------------------------------------------------------------------
# Projections / MLP
# --------------------------------------------------------------------------
def qkv_proj(x, wq, wk, wv, num_kv: int, groups: int):
    """x: (B,S,D) -> q (B,S,H,hd), k/v (B,S,KV,hd). Flat-head layout (no
    grouped reshape of sharded weights — see repeat_kv)."""
    q = jnp.einsum("bsd,dnh->bsnh", x, wq)
    k = jnp.einsum("bsd,dkh->bskh", x, wk)
    v = jnp.einsum("bsd,dkh->bskh", x, wv)
    return q, k, v


def out_proj(o, wo):
    """o: (B,S,H,hd), wo: (H, hd, D) -> (B,S,D)."""
    return jnp.einsum("bsnh,nhd->bsd", o, wo)


def mlp(x, params: dict, mlp_type: str):
    if mlp_type == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
        up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
        h = jax.nn.silu(gate) * up
    else:
        up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
        h = jax.nn.gelu(up)
    h = constraint(h, "batch", None, "d_ff")
    return jnp.einsum("bsf,fd->bsd", h, params["w_down"])


# --------------------------------------------------------------------------
# Embedding / logits / loss
# --------------------------------------------------------------------------
def embed_tokens(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    """Under a mesh: one-hot matmul — a dot partitions cleanly when the table
    is (vocab x embed_d)-sharded (a row gather would all-gather the table).
    The one-hot carries explicit vocab sharding so the embed GRADIENT
    (oh^T @ dx) comes out vocab-sharded instead of replicated.
    Off-mesh (CPU tests): plain gather."""
    from repro.distributed.sharding import active_mesh
    if active_mesh() is not None:
        oh = jax.nn.one_hot(tokens, embed.shape[0], dtype=embed.dtype)
        oh = constraint(oh, "batch", None, "vocab")
        return jnp.einsum("...sv,vd->...sd", oh, embed)
    return jnp.take(embed, tokens, axis=0)


def logits_from_hidden(x, params, tie: bool):
    # Exit any sequence-parallel region before the LM head and pin the
    # vocab-parallel sharding of the logits: without this the unembed
    # GRADIENT materializes replicated (d x V in f32) on every device.
    x = constraint(x, "batch", None, None)
    if tie:
        out = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bsd,dv->bsv", x, params["unembed"],
                         preferred_element_type=jnp.float32)
    return constraint(out, "batch", None, "vocab")


def _gold_logit(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits[b,s,labels[b,s]] — as a one-hot contraction under a mesh (a
    gather along a vocab-sharded axis forces SPMD to replicate the logits
    and wrecks the unembed-gradient sharding; a dot partitions cleanly)."""
    from repro.distributed.sharding import active_mesh
    lab = jnp.maximum(labels, 0)
    if active_mesh() is not None:
        oh = jax.nn.one_hot(lab, logits.shape[-1], dtype=logits.dtype)
        return jnp.einsum("...v,...v->...", logits, oh)
    return jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  chunk: int = 0) -> jax.Array:
    """Mean token NLL; labels < 0 are masked. ``chunk`` > 0 computes the
    loss over sequence chunks (never materializing full (B,S,V) f32 logits
    at once when the caller fuses it — see model.loss_fn chunked path)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = lse - _gold_logit(logits, labels)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------------------------------------------------------
# Init helpers
# --------------------------------------------------------------------------
def dense_init(rng, shape, dtype, fan_in: Optional[int] = None):
    fan = fan_in if fan_in is not None else shape[0]
    std = fan ** -0.5
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


def split_tree(rng, n: int):
    return list(jax.random.split(rng, n))
