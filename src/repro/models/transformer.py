"""Decoder-only transformer family: dense (granite/starcoder2/yi/gemma3),
MoE (qwen3-moe/olmoe), and VLM (qwen2-vl text backbone + patch-embed prefix).

Structure: stacked per-layer parameters + ``jax.lax.scan`` over layers (one
layer body in the HLO regardless of depth — compact compiles at 512 fake
devices and production-idiomatic). Heterogeneous attention patterns (gemma3's
5 local : 1 global, hymba-style explicit full layers) are a per-layer scalar
flag consumed inside the scan body as a traced window select — no parameter
or compute duplication.

MoE baseline (``moe_impl="scan"``) is a scan over experts with top-k
combine weights (clean GSPMD sharding; computes every expert — E/k times
the active work, a deliberate waste kept as the plain baseline the tests
compare against).  ``moe_impl="ragged"`` is the dropless top-k path
(:func:`moe_route` + :func:`moe_dispatch`: sort by expert, then
:func:`grouped_matmul`), the one the federated ``lm`` client
(``repro.models.lm_client``) trains through.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constraint
from repro.models import layers as L

def padded_vocab(cfg: ModelConfig) -> int:
    return cfg.padded_vocab


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def is_global_flags(cfg: ModelConfig) -> np.ndarray:
    """Per-layer bool: True = full/global attention, False = windowed."""
    flags = np.zeros((cfg.num_layers,), dtype=bool)
    if cfg.sliding_window == 0:
        flags[:] = True
    else:
        if cfg.global_every:
            flags[cfg.global_every - 1::cfg.global_every] = True
        for i in cfg.full_attn_layers:
            flags[i] = True
    return flags


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def init_decoder(cfg: ModelConfig, rng: jax.Array) -> dict:
    dt = _dtype(cfg)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV, F, Lr = cfg.num_heads, cfg.num_kv_heads, cfg.d_ff, cfg.num_layers
    V = padded_vocab(cfg)
    ks = iter(jax.random.split(rng, 16))

    layer: dict[str, jax.Array] = {
        "attn_norm": jnp.ones((Lr, d), dt),
        "mlp_norm": jnp.ones((Lr, d), dt),
        "wq": L.dense_init(next(ks), (Lr, d, H, hd), dt, d),
        "wk": L.dense_init(next(ks), (Lr, d, KV, hd), dt, d),
        "wv": L.dense_init(next(ks), (Lr, d, KV, hd), dt, d),
        "wo": L.dense_init(next(ks), (Lr, H, hd, d), dt, H * hd),
    }
    if cfg.num_experts:
        E = cfg.num_experts
        layer["router"] = L.dense_init(next(ks), (Lr, d, E), dt, d)
        layer["we_gate"] = L.dense_init(next(ks), (Lr, E, d, F), dt, d)
        layer["we_up"] = L.dense_init(next(ks), (Lr, E, d, F), dt, d)
        layer["we_down"] = L.dense_init(next(ks), (Lr, E, F, d), dt, F)
    else:
        if cfg.mlp_type == "swiglu":
            layer["w_gate"] = L.dense_init(next(ks), (Lr, d, F), dt, d)
        layer["w_up"] = L.dense_init(next(ks), (Lr, d, F), dt, d)
        layer["w_down"] = L.dense_init(next(ks), (Lr, F, d), dt, F)

    params = {
        "embed": L.dense_init(next(ks), (V, d), dt, d),
        "final_norm": jnp.ones((d,), dt),
        "layers": layer,
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(next(ks), (d, V), dt, d)
    return params


def decoder_param_specs(cfg: ModelConfig) -> dict:
    """Logical-axis tree mirroring ``init_decoder`` output."""
    layer = {
        "attn_norm": ("layers", None),
        "mlp_norm": ("layers", None),
        "wq": ("layers", "w_data", "heads", "head_dim"),
        "wk": ("layers", "w_data", "kv_heads", "head_dim"),
        "wv": ("layers", "w_data", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "w_data"),
    }
    if cfg.num_experts:
        layer.update({
            "router": ("layers", "w_data", None),
            "we_gate": ("layers", None, "w_data", "d_ff"),
            "we_up": ("layers", None, "w_data", "d_ff"),
            "we_down": ("layers", None, "d_ff", "w_data"),
        })
    else:
        if cfg.mlp_type == "swiglu":
            layer["w_gate"] = ("layers", "w_data", "d_ff")
        layer["w_up"] = ("layers", "w_data", "d_ff")
        layer["w_down"] = ("layers", "d_ff", "w_data")
    specs = {
        "embed": ("vocab", "embed_d"),
        "final_norm": (None,),
        "layers": layer,
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ("embed_d", "vocab")
    return specs


# --------------------------------------------------------------------------
# Blocks
# --------------------------------------------------------------------------
def _moe_block(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Top-k MoE, baseline: scan over ALL experts with combine weights.
    FLOPs = E/k x the active compute — see module docstring."""
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    router_logits = jnp.einsum("bsd,de->bse", x, p["router"],
                               preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)                       # (B,S,K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    combine = jnp.sum(
        jax.nn.one_hot(top_i, E, dtype=x.dtype) * top_w[..., None].astype(x.dtype),
        axis=-2)                                                  # (B,S,E)

    def expert_body(acc, xs):
        wg, wu, wd, w_tok = xs            # (d,F) (d,F) (F,d) (B,S)
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, wg)) \
            * jnp.einsum("bsd,df->bsf", x, wu)
        h = h * w_tok[..., None]
        return acc + jnp.einsum("bsf,fd->bsd", h, wd), None

    acc0 = jnp.zeros_like(x)
    combine_e = jnp.moveaxis(combine, -1, 0)                      # (E,B,S)
    out, _ = jax.lax.scan(
        expert_body, acc0,
        (p["we_gate"], p["we_up"], p["we_down"], combine_e))
    return out


def moe_route(x: jax.Array, router: jax.Array, k: int
              ) -> tuple[jax.Array, jax.Array]:
    """Softmax router, top-``k``, renormalised (``norm_topk_prob``).
    x (T, d) -> weights (T, k) float32 and expert ids (T, k) int32."""
    logits = jnp.einsum("td,de->te", x, router,
                        preferred_element_type=jnp.float32)
    top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return top_w / jnp.sum(top_w, axis=-1, keepdims=True), top_i


def _ragged_dims(lhs_contract: int, rhs_contract: int, rhs_group):
    return jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((lhs_contract,), (rhs_contract,)), ((), ())),
        lhs_ragged_dimensions=[0],
        rhs_group_dimensions=[] if rhs_group is None else [rhs_group])


def _megablox(*dims: int) -> bool:
    """Whether the grouped products run as the Pallas megablox kernels: on
    a TPU, for dimensions on the 128 tiling."""
    return jax.default_backend() == "tpu" and all(d % 128 == 0 for d in dims)


def _tiles(*pairs: tuple[int, int]) -> tuple[int, ...]:
    """The largest multiple of 128 up to ``want`` dividing each ``dim``."""
    out = []
    for dim, want in pairs:
        t = min(want, dim) // 128 * 128
        while dim % t:
            t -= 128
        out.append(t)
    return tuple(out)


def _gmm(lhs, rhs, group_sizes, transposed: bool):
    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    if _megablox(m, k, n):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
        return gmm(lhs, rhs, group_sizes, jnp.float32,
                      _tiles((m, 512), (k, 1024), (n, 1024)),
                      transpose_rhs=transposed)
    return jax.lax.ragged_dot_general(
        lhs, rhs, group_sizes, _ragged_dims(1, 2 if transposed else 1, 0),
        preferred_element_type=jnp.float32)


def _tgmm(lhs, dout, group_sizes):
    """Per group g: ``lhs_g^T @ dout_g`` -> (G, K, N) float32."""
    (m, k), n = lhs.shape, dout.shape[1]
    if _megablox(m, k, n):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
        return tgmm(lhs.T, dout, group_sizes, jnp.float32,
                       _tiles((m, 512), (k, 1024), (n, 1024)),
                       num_actual_groups=group_sizes.shape[0])
    return jax.lax.ragged_dot_general(
        lhs, dout, group_sizes, _ragged_dims(0, 0, None),
        preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   transposed: bool = False) -> jax.Array:
    """(T,K) x (G,K,N) -> (T,N) float32, rows grouped by ``group_sizes``
    (``sum(group_sizes) == T``).  With ``transposed`` the weights are
    (G,N,K).  ``lhs`` and ``rhs`` share a dtype; the products accumulate
    in float32.  On a TPU, at dimensions on the 128 tiling, the products
    are JAX's Pallas megablox kernels (``gmm``, ``tgmm``); elsewhere
    ``ragged_dot``, which the TPU compiler runs at a fraction of the
    kernels' rate.

    jax's built-in VJP for ragged_dot falls back to dense per-group masks
    ((T,T) and (G,T,K) f32 monsters — observed 4 GiB buffers in the qwen3
    cell). Both transposes are themselves grouped products, so this custom
    VJP keeps the backward grouped:
      dlhs = gmm(dout, rhs^T)                   (grouped rows)
      drhs = tgmm(lhs, dout)                    (grouped CONTRACTING -> per
                                                 group lhs_g^T @ dout_g)
    When ``rhs`` is not differentiated (frozen expert weights), ``lhs`` is
    not kept for the backward pass and ``drhs`` is never computed.
    """
    return _gmm(lhs, rhs, group_sizes, transposed)


def _gmm_fwd(lhs, rhs, group_sizes, transposed):
    out = _gmm(lhs.value, rhs.value, group_sizes.value, transposed)
    kept = lhs.value if rhs.perturbed else None
    return out, (kept, rhs.value, group_sizes.value)


def _gmm_bwd(transposed, res, dout):
    lhs, rhs, gs = res
    dout = dout.astype(rhs.dtype)
    dlhs = _gmm(dout, rhs, gs, not transposed)
    drhs = None
    if lhs is not None:
        drhs = (_tgmm(dout, lhs, gs) if transposed
                else _tgmm(lhs, dout, gs)).astype(rhs.dtype)
    return dlhs.astype(rhs.dtype), drhs, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd, symbolic_zeros=True)


def moe_dispatch(x: jax.Array, top_w: jax.Array, top_i: jax.Array,
                 wg: jax.Array, wu: jax.Array, wd: jax.Array,
                 reduce=None, transposed: bool = False) -> jax.Array:
    """Dropless top-k SwiGLU experts: x (T, d) -> (T, d).

    The T*k token-expert rows are sorted by expert and each expert's run
    goes through :func:`grouped_matmul`: every row is computed, none is
    dropped, and only top-k work is done.  The weights are (E, d, F),
    (E, d, F), (E, F, d), or each (out, in) with ``transposed``.
    ``reduce`` sums partial products over a mesh axis (the sharded shelf
    path); None off-mesh."""
    E, K = wg.shape[0], top_i.shape[-1]
    T = x.shape[0]
    flat_e = top_i.reshape(-1)                                   # (T*K,)
    # Each row's place in expert order: its expert's start plus the rows
    # of that expert before it (a cumulative count, not a sort).
    onehot = (flat_e[:, None] == jnp.arange(E, dtype=flat_e.dtype)
              ).astype(jnp.int32)                                # (TK, E)
    group_sizes = jnp.sum(onehot, axis=0)
    before = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                                 flat_e[:, None], axis=1)[:, 0]
    place = jnp.cumsum(group_sizes)[flat_e] - group_sizes[flat_e] + before
    order = jnp.zeros_like(flat_e).at[place].set(
        jnp.arange(T * K, dtype=flat_e.dtype))                   # place->row
    x_sorted = jnp.take(x, order // K, axis=0)                   # (TK, d)
    g = grouped_matmul(x_sorted, wg, group_sizes, transposed)
    u = grouped_matmul(x_sorted, wu, group_sizes, transposed)
    if reduce is not None:
        g, u = reduce(g, "data"), reduce(u, "data")
    h = (jax.nn.silu(g) * u).astype(x.dtype)                     # (TK, F)
    o = grouped_matmul(h, wd, group_sizes, transposed)           # (TK, d)
    if reduce is not None:
        o = reduce(o, "model")
    o_rows = jnp.take(o.astype(x.dtype), place, axis=0)
    return jnp.einsum("tkd,tk->td", o_rows.reshape(T, K, -1),
                      top_w.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _moe_block_ragged(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Dropless top-k MoE: :func:`moe_route`, then :func:`moe_dispatch`
    (sort by expert + :func:`grouped_matmul`; no capacity, no row dropped,
    top-k FLOPs only).

    Token-parallel under a mesh: every device keeps its own tokens,
    contracts against its (d/dp, F/tp) weight shards, and the partial sums
    meet in psums over ``data`` and ``model`` + one d-axis all-gather — no
    per-expert weight/activation collectives.  Off-mesh it runs the same
    math on one device (the federated ``lm`` client and the equivalence
    tests)."""
    from repro.distributed.sharding import active_mesh
    K = cfg.num_experts_per_tok
    B, S, d = x.shape
    mesh = active_mesh()

    def local_moe(x_l, router_l, wg_l, wu_l, wd_l):
        data_ax = mesh is not None and "data" in mesh.axis_names
        model_ax = mesh is not None and "model" in mesh.axis_names
        Bl, Sl, _ = x_l.shape
        xf = x_l.reshape(Bl * Sl, d)
        if data_ax:
            dp = jax.lax.axis_size("data")
            d_loc = d // dp
            di = jax.lax.axis_index("data")
            xf = jax.lax.dynamic_slice_in_dim(xf, di * d_loc, d_loc, 1)
        logits = jnp.einsum("td,de->te", xf, router_l,
                            preferred_element_type=jnp.float32)
        if data_ax:
            logits = jax.lax.psum(logits, "data")
        top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

        def reduce(t, axis):
            on = data_ax if axis == "data" else model_ax
            return jax.lax.psum(t, axis) if on else t
        o_tok = moe_dispatch(xf, top_w, top_i, wg_l, wu_l, wd_l,
                             reduce=reduce if mesh is not None else None)
        if data_ax:
            o_tok = jax.lax.all_gather(o_tok, "data", axis=1, tiled=True)
        return o_tok.reshape(Bl, Sl, d)

    if mesh is None:
        return local_moe(x, p["router"], p["we_gate"], p["we_up"],
                         p["we_down"])

    from jax.sharding import PartitionSpec as P
    x = constraint(x, "batch", None, None)   # exit SP once per block
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fspec = "model" if "model" in mesh.axis_names else None
    dspec = "data" if "data" in mesh.axis_names else None
    out = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(batch_axes or None, None, None),
                  P(dspec, None),
                  P(None, dspec, fspec),
                  P(None, dspec, fspec),
                  P(None, fspec, dspec)),
        out_specs=P(batch_axes or None, None, None),
        check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])
    return out


def _attn_block(x, p, cfg, cos, sin, q_pos, kv_pos, window, *,
                k_ext=None, v_ext=None, kv_valid=None, impl="einsum"):
    """Self-attention with optional external KV (decode cache)."""
    KV, G = cfg.num_kv_heads, cfg.q_groups
    q, k, v = L.qkv_proj(x, p["wq"], p["wk"], p["wv"], KV, G)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    if k_ext is not None:
        k_all, v_all = k_ext, v_ext
    else:
        k_all, v_all = k, v
    o = L.attention(q, k_all, v_all, q_pos=q_pos, kv_pos=kv_pos, causal=True,
                    window=window, kv_valid=kv_valid, impl=impl)
    return L.out_proj(o, p["wo"]), k, v


def _ffn(x, p, cfg, moe_impl: str = "scan"):
    if cfg.num_experts:
        if moe_impl == "ragged":
            return _moe_block_ragged(x, p, cfg)
        return _moe_block(x, p, cfg)
    return L.mlp(x, p, cfg.mlp_type)


# --------------------------------------------------------------------------
# Forward (training / prefill hidden states)
# --------------------------------------------------------------------------
def decoder_hidden(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
                   positions: Optional[jax.Array] = None,
                   vision_embeds: Optional[jax.Array] = None,
                   attn_impl: str = "einsum",
                   remat_policy: str = "dots",
                   moe_impl: str = "scan",
                   collect_kv: bool = False):
    """tokens (B,S) -> hidden (B,S,D); optionally per-layer (k, v) stacks."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    sections = cfg.mrope_sections if cfg.mrope else None
    cos, sin = L.rope_cos_sin(positions, cfg.resolved_head_dim,
                              cfg.rope_theta, sections)
    x = L.embed_tokens(params["embed"], tokens)
    if vision_embeds is not None:
        x = jax.lax.dynamic_update_slice(
            x, vision_embeds.astype(x.dtype), (0, 0, 0))
    x = constraint(x, "batch", "act_seq", None)
    q_pos = (positions[0] if positions.ndim == 2 else positions[0, 0])

    flags = jnp.asarray(is_global_flags(cfg))
    win = cfg.sliding_window

    def body(h, xs):
        p, flag = xs
        window = jnp.where(flag, jnp.int32(0), jnp.int32(win))
        # Megatron-SP block boundary: all-gather the sequence BEFORE the
        # projections (so heads/d_ff TP applies inside), reduce-scatter the
        # projection outputs back to sequence shards. Both constraints are
        # no-ops when act_seq is unmapped.
        attn_in = constraint(L.rmsnorm(h, p["attn_norm"]),
                             "batch", None, None)
        attn_out, k, v = _attn_block(attn_in, p, cfg, cos, sin, q_pos, q_pos,
                                     window, impl=attn_impl)
        h = h + constraint(attn_out, "batch", "act_seq", None)
        mlp_in = constraint(L.rmsnorm(h, p["mlp_norm"]),
                            "batch", None, None)
        h = h + constraint(_ffn(mlp_in, p, cfg, moe_impl),
                           "batch", "act_seq", None)
        return h, ((k, v) if collect_kv else None)

    if remat_policy == "full":
        body = jax.checkpoint(body)
    elif remat_policy == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    x, kv = jax.lax.scan(body, x, (params["layers"], flags))
    x = L.rmsnorm(x, params["final_norm"])
    return (x, kv) if collect_kv else x


def decoder_logits(cfg, params, hidden):
    V = padded_vocab(cfg)
    logits = L.logits_from_hidden(hidden, params, cfg.tie_embeddings)
    return logits  # (B,S,Vpad) f32


def decoder_loss(cfg: ModelConfig, params: dict, batch: dict, *,
                 attn_impl: str = "einsum", remat_policy: str = "dots",
                 loss_chunk: int = 0, moe_impl: str = "scan") -> jax.Array:
    hidden = decoder_hidden(
        cfg, params, batch["tokens"], positions=batch.get("positions"),
        vision_embeds=batch.get("vision_embeds"), attn_impl=attn_impl,
        remat_policy=remat_policy, moe_impl=moe_impl)
    labels = batch["labels"]
    if loss_chunk and hidden.shape[1] % loss_chunk == 0:
        # Stream the (B,chunk,V) logits: never materialize (B,S,V).
        n = hidden.shape[1] // loss_chunk
        hc = hidden.reshape(hidden.shape[0], n, loss_chunk, -1)
        lc = labels.reshape(labels.shape[0], n, loss_chunk)

        def chunk_loss(carry, xs):
            h, lab = xs
            logits = L.logits_from_hidden(h, params, cfg.tie_embeddings)
            logits = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = L._gold_logit(logits, lab)
            mask = (lab >= 0).astype(jnp.float32)
            return (carry[0] + jnp.sum((lse - gold) * mask),
                    carry[1] + jnp.sum(mask)), None

        (tot, cnt), _ = jax.lax.scan(
            chunk_loss, (jnp.float32(0), jnp.float32(0)),
            (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0)))
        return tot / jnp.maximum(cnt, 1.0)
    logits = decoder_logits(cfg, params, hidden)
    return L.cross_entropy(logits, labels)


# --------------------------------------------------------------------------
# KV cache: prefill + decode
# --------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = _dtype(cfg)
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_len, KV, hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "pos": jnp.zeros((), jnp.int32)}


def cache_specs(cfg: ModelConfig) -> dict:
    return {"k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
            "v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
            "pos": ()}


def decoder_prefill(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
                    positions=None, vision_embeds=None,
                    attn_impl: str = "chunked"):
    """Full-sequence forward that also returns the populated KV cache and the
    last-position logits (the realistic serve entry point)."""
    hidden, kv = decoder_hidden(
        cfg, params, tokens, positions=positions,
        vision_embeds=vision_embeds, attn_impl=attn_impl,
        remat_policy="none", collect_kv=True)
    k, v = kv                                   # (L, B, S, KV, hd)
    cache = {"k": k, "v": v,
             "pos": jnp.asarray(tokens.shape[1], jnp.int32)}
    last = hidden[:, -1]
    logits = L.logits_from_hidden(last[:, None], params, cfg.tie_embeddings)
    return logits[:, 0], cache


def decoder_decode(cfg: ModelConfig, params: dict, cache: dict,
                   tokens: jax.Array, *, positions=None):
    """One decode step. tokens (B,1); cache KV (L,B,T,KV,hd); returns
    (logits (B,Vpad), new cache)."""
    B, S1 = tokens.shape
    T = cache["k"].shape[2]
    pos = cache["pos"]
    if positions is None:
        positions = jnp.full((B, S1), pos, jnp.int32)
    sections = cfg.mrope_sections if cfg.mrope else None
    cos, sin = L.rope_cos_sin(positions, cfg.resolved_head_dim,
                              cfg.rope_theta, sections)
    x = L.embed_tokens(params["embed"], tokens)
    q_pos = jnp.full((S1,), pos, jnp.int32)
    kv_pos = jnp.arange(T, dtype=jnp.int32)
    kv_valid = jnp.broadcast_to((kv_pos <= pos)[None], (B, T))
    flags = jnp.asarray(is_global_flags(cfg))
    win = cfg.sliding_window

    def body(h, xs):
        p, flag, k_l, v_l = xs
        window = jnp.where(flag, jnp.int32(0), jnp.int32(win))
        attn_in = L.rmsnorm(h, p["attn_norm"])
        KV, G = cfg.num_kv_heads, cfg.q_groups
        q, k_new, v_new = L.qkv_proj(attn_in, p["wq"], p["wk"], p["wv"],
                                     KV, G)
        q = L.apply_rope(q, cos, sin)
        k_new = L.apply_rope(k_new, cos, sin)
        k_l = jax.lax.dynamic_update_slice(k_l, k_new.astype(k_l.dtype),
                                           (0, pos, 0, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, v_new.astype(v_l.dtype),
                                           (0, pos, 0, 0))
        o = L.attention(q, k_l, v_l, q_pos=q_pos, kv_pos=kv_pos, causal=True,
                        window=window, kv_valid=kv_valid)
        h = h + L.out_proj(o, p["wo"])
        h = h + _ffn(L.rmsnorm(h, p["mlp_norm"]), p, cfg)
        return h, (k_l, v_l)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["layers"], flags, cache["k"], cache["v"]))
    x = L.rmsnorm(x, params["final_norm"])
    logits = L.logits_from_hidden(x, params, cfg.tie_embeddings)
    new_cache = {"k": k_new, "v": v_new, "pos": pos + 1}
    return logits[:, 0], new_cache
