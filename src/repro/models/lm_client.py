"""MoE decoders as federated clients: LoRA fine-tuning of a frozen base,
one silo per client.

The deployment is cross-silo federated instruction tuning (FedIT,
arXiv:2305.05644; OpenFedLLM, arXiv:2402.06954): every silo holds the same
frozen base model, trains low-rank adapters on the attention projections
on its own token stream, and sends only the adapters; the server averages
them.  The flat parameter vector the fleet moves is therefore the adapters
alone, and the base reaches the jitted step as an argument shared by the
whole batch (:meth:`LMClientModel.frozen`), never as a constant.

The architecture is a shelf :class:`~repro.configs.base.ModelConfig`, cut
in depth or shrunk for tests through ``num_layers`` and ``overrides``; one
:class:`LMStep` picks each layer's attention and FFN block from it.
``lm`` is :data:`ARCH` (Mellum2-12B-A2.5B)::

    h = x + Attn(RMSNorm(x))      GQA, RoPE; windowed or full by layer type
    y = h + MoE(RMSNorm(h))       softmax router, top-k renormalised,
                                  dropless SwiGLU experts

Windowed layers use the default RoPE table, full layers YaRN's, with cos
and sin scaled by the attention factor; LoRA on ``wq``, ``wk``, ``wv`` and
``wo``.  ``lm_mla`` is :data:`MLA_ARCH` (Moonlight-16B-A3B, the
DeepSeek-V3 block)::

    h = x + MLA(RMSNorm(x))       q = x Wq (nope | rope dims a head);
                                  c | k_pe = x Wkv_a, c RMS-normed;
                                  k_nope | v = c Wkv_b; k_pe one RoPE key
                                  every head shares; causal, scale
                                  (nope + rope)**-0.5
    y = h + FFN(RMSNorm(h))       the first ``first_k_dense_replace``
                                  layers a dense SwiGLU; then sigmoid-
                                  routed top-k experts (chosen on score +
                                  bias, weighted by the unbiased scores,
                                  renormalised, times the routed scale)
                                  plus the shared experts' one SwiGLU

with LoRA on ``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj`` and
``o_proj``.  Its RoPE rotates adjacent pairs (``rope_interleave``): the
base's RoPE columns of ``q_proj`` and ``kv_a_proj_with_mqa`` are
de-interleaved once, when the base is drawn (:func:`rope_columns`; the B
adapters' columns at use), so that the half-split rotation computes the
same scores.  The leading dense layers run one by one, before one
``lax.scan`` over the MoE layers.  LoRA (rank ``lora_rank``, scale
``lora_alpha / lora_rank``) is ``x W + s * (x A) B``.

Precision: base weights and activations bfloat16 with float32
accumulation; a sigmoid router and its bias float32 at full precision;
adapters, their products (``precision="highest"``), their gradients and
the SGD step float32.

Everything is drawn from the seed, on the device:

* base leaf j of :func:`base_layout` (in its order), layer ``l`` (the
  model's own layer number):
  ``normal(fold_in(fold_in(PRNGKey(seed), j), l), shape) * fan_in**-0.5``
  in float32, then bfloat16 (layer-less leaves use ``fold_in(PRNGKey(seed),
  j)``); the norms' scales are ones; ``e_score_correction_bias`` is
  ``normal(...) * BIAS_STD``, kept float32;
* the token stream of silo ``c`` at round ``r``, local step ``s``:
  ``u = uniform(fold_in(fold_in(fold_in(fold_in(PRNGKey(seed), STREAM), c),
  r), s), (seq_len + 1,))``, token ``(c * stride + floor(u**4 * 2 *
  stride)) % vocab`` with ``stride = vocab // n_clients``: each silo
  favours its own slice of the vocabulary (non-IID), Zipf-like within it;
  the sequence is the first ``seq_len`` tokens, the labels the last;
* the adapters' starting values (host, numpy): ``A`` uniform in
  ``±fan_in**-0.5`` from ``default_rng(seed)``, ``B`` zero.

The plain references of the same step are ``bench/reference/lm.py`` and
``bench/reference/lm_mla.py``; they import nothing of this package.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, get_config
from repro.core import tracing
from repro.core.client_compute import ClientModel
from repro.core.packetizer import flatten_to_vector, unflatten_from_vector
from repro.models import layers as L
from repro.models import transformer as T

#: The shelf configurations the ``lm`` and ``lm_mla`` clients train.
ARCH = "mellum2-12b-a2.5b"
MLA_ARCH = "moonlight-16b-a3b"
#: ``fold_in`` tag of the token streams' key.
STREAM = 1 << 20
#: The selection bias leaf, and its draw's standard deviation: a trained
#: buffer no checkpoint gives here, small beside the sigmoid scores' spread
#: yet enough to change the chosen experts of some tokens.
BIAS = "e_score_correction_bias"
BIAS_STD = 0.02
#: Queries per attention chunk and positions per chunk of the head's
#: logits: at 8 silos x 4,096 positions a full layer's chunk of scores is
#: 0.5 GB in float32, and the step fits one v5e beside its 7.6 GB base.
ATTN_CHUNK = 128
LOSS_CHUNK = 256
#: Queries per causal-prefix run of a full layer (its keys stop at the
#: run's last query: 56% of the all-keys work at 4,096 positions).
FULL_GROUP = 512


def base_layout(cfg: ModelConfig) -> dict[str, tuple[tuple, int,
                                                     Optional[range]]]:
    """``{leaf: (shape of one layer or of the whole leaf, fan_in, the
    model layers it is stacked over or None)}``, in key order; a fan-in
    of 0 marks a norm scale (ones)."""
    d, V, n = cfg.d_model, cfg.vocab_size, cfg.num_layers
    every, k0 = range(n), cfg.first_k_dense_replace
    dense, sparse = range(k0), range(k0, n)
    out = {"embed": ((V, d), d, None), "unembed": ((d, V), d, None),
           "final_norm": ((d,), 0, None), "attn_norm": ((d,), 0, every),
           "mlp_norm": ((d,), 0, every)}
    if cfg.kv_lora_rank:
        H, R = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        out.update({
            "q_proj": ((d, H * (nope + rope)), d, every),
            "kv_a_proj_with_mqa": ((d, R + rope), d, every),
            "kv_a_layernorm": ((R,), 0, every),
            "kv_b_proj": ((R, H * (nope + vd)), R, every),
            "o_proj": ((H * vd, d), H * vd, every)})
    else:
        hd = cfg.resolved_head_dim
        q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
        out.update({"wq": ((d, q), d, every), "wk": ((d, kv), d, every),
                    "wv": ((d, kv), d, every), "wo": ((q, d), q, every)})
    if k0:
        F = cfg.dense_d_ff
        out.update({"dense_gate": ((d, F), d, dense),
                    "dense_up": ((d, F), d, dense),
                    "dense_down": ((F, d), F, dense)})
    E, F = cfg.num_experts, cfg.d_ff
    out["router"] = ((d, E), d, sparse)
    if cfg.router_bias:
        out[BIAS] = ((E,), 1, sparse)
    out.update({"we_gate": ((E, F, d), d, sparse),      # experts (out, in)
                "we_up": ((E, F, d), d, sparse),
                "we_down": ((E, d, F), F, sparse)})
    if cfg.n_shared_experts:
        F = cfg.n_shared_experts * cfg.d_ff
        out.update({"shared_gate": ((d, F), d, sparse),
                    "shared_up": ((d, F), d, sparse),
                    "shared_down": ((F, d), F, sparse)})
    return out


def lora_targets(cfg: ModelConfig) -> tuple[str, ...]:
    """The projections that carry an adapter."""
    if cfg.kv_lora_rank:
        return ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
    return ("wq", "wk", "wv", "wo")


def adapter_layout(cfg: ModelConfig, rank: int) -> list[tuple[str, tuple]]:
    """Flat-vector layout of the adapters: ``(name, shape)`` in sorted
    name order (``tree_leaves`` of the dict), each stacked over layers."""
    layout, out = base_layout(cfg), {}
    for t in lora_targets(cfg):
        fan_in, fan_out = layout[t][0]
        out[f"a_{t}"] = (cfg.num_layers, fan_in, rank)
        out[f"b_{t}"] = (cfg.num_layers, rank, fan_out)
    return sorted(out.items())


def rope_columns(cfg: ModelConfig) -> dict[str, np.ndarray]:
    """For RoPE on adjacent pairs: the output-column order of each
    projection that makes RoPE dims, in which every head's RoPE dims
    ``(0, 1), (2, 3), ...`` become ``(0, r/2), (1, r/2 + 1), ...``, the
    half-split pairs :func:`~repro.models.layers.apply_rope` rotates.  Both
    sides of a score are permuted alike, so the scores are unchanged."""
    if not cfg.rope_interleave:
        return {}
    r, nope, R = cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    pairs = np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])
    head = np.concatenate([np.arange(nope), nope + pairs])
    return {"q_proj": np.concatenate([h * (nope + r) + head
                                      for h in range(cfg.num_heads)]),
            "kv_a_proj_with_mqa": np.concatenate([np.arange(R), R + pairs])}


def init_base(cfg: ModelConfig, key: jax.Array) -> dict:
    """The frozen base, drawn on the device leaf by leaf and layer by
    layer (module docstring); RoPE columns de-interleaved
    (:func:`rope_columns`)."""
    out, cols = {}, rope_columns(cfg)
    for j, (name, (shape, fan_in, span)) in enumerate(
            base_layout(cfg).items()):
        full = shape if span is None else (len(span),) + shape
        if not fan_in:
            out[name] = jnp.ones(full, jnp.bfloat16)
            continue
        leaf_key = jax.random.fold_in(key, j)
        if span is None:
            out[name] = _draw(leaf_key, shape, fan_in)
            continue
        keys = jax.vmap(lambda i: jax.random.fold_in(leaf_key, i))(
            jnp.arange(span.start, span.stop))
        if name == BIAS:
            out[name] = _draw_bias(keys, shape)
        else:
            out[name] = _draw_layers(keys, shape, fan_in)
        if name in cols:
            out[name] = jnp.take(out[name], cols[name], axis=-1)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            * np.float32(fan_in ** -0.5)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_layers(keys, shape, fan_in):
    return jax.lax.map(lambda k: (jax.random.normal(k, shape, jnp.float32)
                                  * np.float32(fan_in ** -0.5)
                                  ).astype(jnp.bfloat16), keys)


@functools.partial(jax.jit, static_argnums=(1,))
def _draw_bias(keys, shape):
    return jax.lax.map(lambda k: jax.random.normal(k, shape, jnp.float32)
                       * np.float32(BIAS_STD), keys)


def stream_tokens(key, client, round_idx, step, seq_len: int, vocab: int,
                  n_clients: int) -> jax.Array:
    """``seq_len + 1`` tokens of one silo's stream (module docstring)."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, STREAM), client), round_idx), step)
    u = jax.random.uniform(k, (seq_len + 1,), jnp.float32)
    stride = vocab // n_clients
    rank = jnp.floor(u ** 4 * np.float32(2 * stride)).astype(jnp.int32)
    return (client * stride + rank) % vocab


def rope_tables(cfg: ModelConfig, seq_len: int):
    """(cos, sin) of the windowed layers and of the full layers, each
    (seq_len, rotated dims / 2) float32."""
    hd = cfg.qk_rope_head_dim or cfg.resolved_head_dim
    pos = jnp.arange(seq_len, dtype=jnp.int32)
    window = L.rope_cos_sin_freqs(pos, L.rope_inv_freq(hd, cfg.rope_theta))
    if not cfg.rope_yarn_factor:
        return window, window
    full = L.rope_cos_sin_freqs(pos, L.yarn_inv_freq(
        hd, cfg.rope_theta, cfg.rope_yarn_factor, cfg.rope_yarn_original_max,
        cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow),
        cfg.rope_yarn_attention_factor)
    return window, full


def _swiglu(x, wg, wu, wd):
    """A dense SwiGLU, x (T, d) with weights (d, F), (d, F), (F, d): the
    products in x's dtype with float32 accumulation, as the experts'."""
    f32 = jnp.float32
    g = jnp.einsum("td,df->tf", x, wg, preferred_element_type=f32)
    u = jnp.einsum("td,df->tf", x, wu, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.einsum("tf,fd->td", h, wd,
                      preferred_element_type=f32).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class LMStep:
    """The batched local round over a leading client axis, as a pure
    function of ``(stack, client_idx, round_idx, frozen)``.  It holds only
    static sizes, so one compile serves every base of the same shape."""

    cfg: ModelConfig
    n_clients: int
    seq_len: int
    local_steps: int
    lr: float
    rank: int
    alpha: float

    def tokens(self, key, client, round_idx, step) -> jax.Array:
        return stream_tokens(key, client, round_idx, step, self.seq_len,
                             self.cfg.vocab_size, self.n_clients)

    @property
    def layout(self) -> list[tuple[str, tuple]]:
        return adapter_layout(self.cfg, self.rank)

    def unflatten(self, stack: jax.Array) -> dict:
        out, off = {}, 0
        for name, shape in self.layout:
            size = int(np.prod(shape))
            out[name] = stack[:, off:off + size].reshape(
                (stack.shape[0],) + shape)
            off += size
        return out

    def flatten(self, ad: dict) -> jax.Array:
        return jnp.concatenate([ad[name].reshape(ad[name].shape[0], -1)
                                for name, _ in self.layout], axis=1)

    # -- the local round ---------------------------------------------------
    def __call__(self, stack, client_idx, round_idx, frozen):
        ad = self.unflatten(stack.astype(jnp.float32))
        tokens = jax.vmap(self.tokens, in_axes=(None, 0, 0, None))

        def sgd_step(ad, s):
            toks = tokens(frozen["key"], client_idx, round_idx, s)
            grads, aux = jax.grad(self.loss, has_aux=True)(
                ad, frozen["base"], toks)
            ad = {n: ad[n] - jnp.float32(self.lr) * grads[n] for n in ad}
            return ad, aux

        ad, aux = jax.lax.scan(sgd_step, ad,
                               jnp.arange(self.local_steps, dtype=jnp.int32))
        k = stack.shape[0]
        return self.flatten(ad), {
            "train_loss": aux["loss"][-1],
            "train.tokens": jnp.full((k,), self.seq_len * self.local_steps,
                                     jnp.int32),
            "moe.rows": aux["rows"].sum(0),
            "moe.rows_max": aux["rows_max"].sum(0),
            "moe.dropped_rows": aux["dropped"].sum(0)}

    def loss(self, ad: dict, base: dict, toks: jax.Array):
        """Σ over the batch of each silo's mean next-token loss (so each
        row's gradient is its own silo's), and per-silo ``aux``.  The
        leading dense layers run one by one, the MoE layers as one scan."""
        cfg = self.cfg
        x = jnp.take(base["embed"], toks[:, :-1], axis=0)       # (K, T, d)
        tables = rope_tables(cfg, self.seq_len)
        flags = jnp.asarray(T.is_global_flags(cfg))
        spans = {n: span for n, (_, _, span) in base_layout(cfg).items()
                 if span is not None}
        lora = {n: jnp.swapaxes(a, 0, 1) for n, a in ad.items()}  # (L,K,..)
        k0, n = cfg.first_k_dense_replace, cfg.num_layers

        def at(lo, hi):
            """The base leaves stacked over layers lo..hi-1, cut to them."""
            return {m: base[m] if (lo, hi) == (s.start, s.stop)
                    else base[m][lo - s.start:hi - s.start]
                    for m, s in spans.items()
                    if s.start <= lo and hi <= s.stop}

        @jax.checkpoint
        def dense_layer(x, p, a, full):
            x = x + self._attention(self._norm(x, p["attn_norm"]), p, a,
                                    full, tables)
            return x + self._dense(self._norm(x, p["mlp_norm"]), p)

        for i in range(k0):
            x = dense_layer(x, {m: v[0] for m, v in at(i, i + 1).items()},
                            {m: v[i] for m, v in lora.items()}, flags[i])

        @jax.checkpoint
        def layer(x, xs):
            p, a, full = xs
            x = x + self._attention(self._norm(x, p["attn_norm"]), p, a,
                                    full, tables)
            y, counts = self._moe(self._norm(x, p["mlp_norm"]), p)
            rows = counts.sum(-1)
            return x + y, (rows, counts.max(-1),
                           self.seq_len * cfg.num_experts_per_tok - rows)

        if k0:
            lora, flags = {m: v[k0:] for m, v in lora.items()}, flags[k0:]
        x, (rows, rows_max, dropped) = jax.lax.scan(
            layer, x, (at(k0, n), lora, flags))
        nll = self._head(self._norm(x, base["final_norm"]), base["unembed"],
                         toks[:, 1:])
        return jnp.sum(nll), {"loss": nll, "rows": rows.sum(0),
                              "rows_max": rows_max.sum(0),
                              "dropped": dropped.sum(0)}

    # -- blocks ------------------------------------------------------------
    def _norm(self, x, scale):
        return L.rmsnorm(x, scale, self.cfg.rms_norm_eps)

    def _proj(self, h, w, a, b, cols=None):
        """``h W + s * (h A) B``: the base product in bfloat16 with float32
        accumulation, the adapter's in float32.  ``cols`` puts B's columns
        in the order of W's (:func:`rope_columns`)."""
        base = jnp.einsum("ktd,do->kto", h, w,
                          preferred_element_type=jnp.float32)
        hp = jax.lax.Precision.HIGHEST
        if cols is not None:
            b = jnp.take(b, cols, axis=-1)
        low = jnp.einsum("ktd,kdr->ktr", h.astype(jnp.float32), a,
                         precision=hp)
        lora = jnp.einsum("ktr,kro->kto", low, b, precision=hp)
        return (base + jnp.float32(self.alpha / self.rank) * lora
                ).astype(h.dtype)

    def _attention(self, h, p, a, full, tables):
        cfg = self.cfg
        if cfg.kv_lora_rank:
            return self._latent_attention(h, p, a, tables[1])
        K, S, _ = h.shape
        hd = cfg.resolved_head_dim
        q = self._proj(h, p["wq"], a["a_wq"], a["b_wq"]).reshape(
            K, S, cfg.num_heads, hd)
        k = self._proj(h, p["wk"], a["a_wk"], a["b_wk"]).reshape(
            K, S, cfg.num_kv_heads, hd)
        v = self._proj(h, p["wv"], a["a_wv"], a["b_wv"]).reshape(
            K, S, cfg.num_kv_heads, hd)
        pos = jnp.arange(S, dtype=jnp.int32)
        chunk = min(ATTN_CHUNK, S)

        def windowed(q, k, v):
            with jax.named_scope("lm.attn_window"):
                cos, sin = (t[None] for t in tables[0])
                return L.chunked_attention(
                    L.apply_rope(q, cos, sin), L.apply_rope(k, cos, sin), v,
                    q_pos=pos, kv_pos=pos, chunk=chunk,
                    band=cfg.sliding_window)

        def whole(q, k, v):
            with jax.named_scope("lm.attn_full"):
                cos, sin = (t[None] for t in tables[1])
                return L.causal_prefix_attention(
                    L.apply_rope(q, cos, sin), L.apply_rope(k, cos, sin), v,
                    chunk=chunk, groups=max(1, S // FULL_GROUP))

        if cfg.sliding_window:
            o = jax.lax.cond(full, whole, windowed, q, k, v)
        else:
            o = whole(q, k, v)
        return self._proj(o.reshape(K, S, -1), p["wo"], a["a_wo"],
                          a["b_wo"])

    def _latent_attention(self, h, p, a, table):
        """MLA: queries from the input; k_nope and v decompressed from
        the RMS-normed latent; the one RoPE key broadcast over the heads;
        causal attention through the chunked causal-prefix runs."""
        cfg = self.cfg
        K, S, _ = h.shape
        H, R = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        cols = rope_columns(cfg)

        def proj(x, t):
            return self._proj(x, p[t], a[f"a_{t}"], a[f"b_{t}"], cols.get(t))

        q = proj(h, "q_proj").reshape(K, S, H, nope + rope)
        ckv = proj(h, "kv_a_proj_with_mqa")
        with jax.named_scope("lm.attn_mla"):
            kv = proj(self._norm(ckv[..., :R], p["kv_a_layernorm"]),
                      "kv_b_proj").reshape(K, S, H, nope + vd)
            cos, sin = (t[None] for t in table)
            q = jnp.concatenate(
                [q[..., :nope], L.apply_rope(q[..., nope:], cos, sin)], -1)
            k_pe = L.apply_rope(ckv[..., None, R:], cos, sin)  # (K,S,1,r)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_pe, (K, S, H, rope))], -1)
            o = L.causal_prefix_attention(
                q, k, kv[..., nope:], chunk=min(ATTN_CHUNK, S),
                groups=max(1, S // FULL_GROUP))
        return proj(o.reshape(K, S, H * vd), "o_proj")

    def _dense(self, h, p):
        """The leading layers' dense SwiGLU, one silo's sequence at a time
        (its float32 (T, F) products would not fit for every silo at
        once beside the base)."""
        @jax.checkpoint
        def silo(x):
            with jax.named_scope("lm.ffn_dense"):
                return _swiglu(x, p["dense_gate"], p["dense_up"],
                               p["dense_down"])

        return jax.lax.map(silo, h)

    def _moe(self, h, p):
        """Dropless top-k experts, one silo's sequence at a time, as a silo
        computes them on its own chip (each rematerialised in the backward
        pass: all 8 silos' token-expert rows at once would not fit beside
        the base), plus the shared experts where the model has them;
        returns the output and each silo's rows per routed expert (K, E)."""
        cfg = self.cfg
        E, topk = cfg.num_experts, cfg.num_experts_per_tok
        route = {}
        if cfg.router_scoring != "softmax":
            route = dict(scoring=cfg.router_scoring, bias=p.get(BIAS),
                         scale=cfg.routed_scaling_factor)

        @jax.checkpoint
        def silo(x):
            with jax.named_scope("lm.moe"):
                top_w, top_i = T.moe_route(x, p["router"], topk, **route)
                y = T.moe_dispatch(x, top_w, top_i, p["we_gate"],
                                   p["we_up"], p["we_down"],
                                   transposed=True)
                counts = jnp.zeros((E,), jnp.int32).at[top_i].add(1)
            if cfg.n_shared_experts:
                with jax.named_scope("lm.moe_shared"):
                    y = y + _swiglu(x, p["shared_gate"], p["shared_up"],
                                    p["shared_down"])
            return y, counts

        return jax.lax.map(silo, h)

    def _head(self, x, unembed, labels):
        """Each silo's mean next-token loss, over chunks of
        :data:`LOSS_CHUNK` positions: the (K, S, vocab) logits are never
        whole."""
        K, S, d = x.shape
        c = min(LOSS_CHUNK, S)
        xc = jnp.swapaxes(x.reshape(K, S // c, c, d), 0, 1)
        lc = jnp.swapaxes(labels.reshape(K, S // c, c), 0, 1)

        @jax.checkpoint
        def chunk(args):
            xi, li = args
            with jax.named_scope("lm.head"):
                logits = jnp.einsum("kcd,dv->kcv", xi, unembed,
                                    preferred_element_type=jnp.float32)
                gold = jnp.take_along_axis(logits, li[..., None], -1)[..., 0]
                return jnp.sum(jax.nn.logsumexp(logits, -1) - gold, axis=1)

        return jax.lax.map(chunk, (xc, lc)).sum(0) / S


class LMClientModel(ClientModel):
    """LoRA fine-tuning of a frozen MoE decoder, one silo per client
    (module docstring).  ``num_layers`` and ``overrides`` (any
    ``ModelConfig`` field) cut or shrink :attr:`arch`."""

    name = "lm"
    arch = ARCH
    counters = ("train.tokens", "moe.rows", "moe.rows_max",
                "moe.dropped_rows")

    def __init__(self, n_clients: int, *, seed: int = 0,
                 num_layers: Optional[int] = None,
                 overrides: Optional[dict] = None, seq_len: int = 4096,
                 local_steps: int = 2, lr: float = 0.05,
                 lora_rank: int = 16, lora_alpha: float = 32.0):
        super().__init__(n_clients, seed=seed)
        cfg = get_config(self.arch)
        fields = dict(overrides or {})
        if num_layers is not None:
            fields["num_layers"] = int(num_layers)
        self.cfg = dataclasses.replace(cfg, **fields)
        self.step = LMStep(
            cfg=self.cfg, n_clients=self.n_clients, seq_len=int(seq_len),
            local_steps=int(local_steps), lr=float(lr), rank=int(lora_rank),
            alpha=float(lora_alpha))
        self.n_params = sum(int(np.prod(s)) for _, s in self.step.layout)
        self._frozen: Optional[dict] = None
        self._single: Optional[Callable] = None
        self._eval: Optional[Callable] = None

    def reference_config(self) -> dict:
        """The keys the plain reference reads (its ``sizes``): the sizes
        under the published ``config.json`` names, the training settings
        as ``model_args`` and the silo count."""
        cfg, step = self.cfg, self.step
        out = {"num_hidden_layers": cfg.num_layers,
               "hidden_size": cfg.d_model,
               "num_attention_heads": cfg.num_heads,
               "num_key_value_heads": cfg.num_kv_heads,
               "num_experts_per_tok": cfg.num_experts_per_tok,
               "moe_intermediate_size": cfg.d_ff,
               "vocab_size": cfg.vocab_size,
               "rms_norm_eps": cfg.rms_norm_eps,
               "tie_word_embeddings": cfg.tie_embeddings}
        if cfg.kv_lora_rank:
            out.update(self._mla_keys())
        else:
            out.update(self._gqa_keys())
        out.update({"n_clients": self.n_clients,
                    "model_args": {"seq_len": step.seq_len,
                                   "local_steps": step.local_steps,
                                   "lr": step.lr, "lora_rank": step.rank,
                                   "lora_alpha": step.alpha}})
        return out

    def _gqa_keys(self) -> dict:
        cfg = self.cfg
        flags = T.is_global_flags(cfg)
        sliding = {"rope_type": "default", "rope_theta": cfg.rope_theta}
        full = dict(sliding)
        if cfg.rope_yarn_factor:
            full = {"rope_type": "yarn", "rope_theta": cfg.rope_theta,
                    "factor": cfg.rope_yarn_factor,
                    "original_max_position_embeddings":
                        cfg.rope_yarn_original_max,
                    "beta_fast": cfg.rope_yarn_beta_fast,
                    "beta_slow": cfg.rope_yarn_beta_slow,
                    "attention_factor": cfg.rope_yarn_attention_factor}
        return {"head_dim": cfg.resolved_head_dim,
                "num_experts": cfg.num_experts,
                "sliding_window": cfg.sliding_window,
                "layer_types": ["full_attention" if f
                                else "sliding_attention" for f in flags],
                "rope_parameters": {"full_attention": full,
                                    "sliding_attention": sliding}}

    def _mla_keys(self) -> dict:
        """DeepSeek-V3's keys; no query low-rank, no expert groups (one
        group, all of it chosen)."""
        cfg = self.cfg
        return {"kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": None,
                "qk_nope_head_dim": cfg.qk_nope_head_dim,
                "qk_rope_head_dim": cfg.qk_rope_head_dim,
                "v_head_dim": cfg.v_head_dim,
                "rope_theta": cfg.rope_theta,
                "rope_interleave": cfg.rope_interleave,
                "n_routed_experts": cfg.num_experts,
                "n_shared_experts": cfg.n_shared_experts,
                "first_k_dense_replace": cfg.first_k_dense_replace,
                "intermediate_size": cfg.dense_d_ff,
                "moe_layer_freq": 1,
                "scoring_func": cfg.router_scoring,
                "topk_method": "noaux_tc" if cfg.router_bias else "greedy",
                "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
                "routed_scaling_factor": cfg.routed_scaling_factor}

    # -- ClientModel -------------------------------------------------------
    def frozen(self) -> dict:
        if self._frozen is None:
            key = jax.random.PRNGKey(self.seed)
            self._frozen = {"base": init_base(self.cfg, key), "key": key}
        return self._frozen

    def init_params(self) -> Any:
        rng = np.random.default_rng(self.seed)
        out = {}
        for name, shape in self.step.layout:
            if name.startswith("a_"):
                bound = shape[1] ** -0.5
                out[name] = rng.uniform(-bound, bound, shape).astype(
                    np.float32)
            else:
                out[name] = np.zeros(shape, np.float32)
        return out

    def loss(self, params: Any) -> float:
        """Mean next-token loss of ``params`` over one held-out sequence
        (round -1) of every silo."""
        if self._eval is None:
            step = self.step

            def held_out(stack, frozen):
                toks = jax.vmap(step.tokens, in_axes=(None, 0, None, None))(
                    frozen["key"], jnp.arange(step.n_clients), -1, 0)
                ad = step.unflatten(jnp.broadcast_to(
                    stack, (step.n_clients, stack.shape[1])))
                return step.loss(ad, frozen["base"], toks)[1]["loss"].mean()
            self._eval = jax.jit(held_out)
        vec = jnp.asarray(flatten_to_vector(params))[None]
        return float(self._eval(vec, self.frozen()))

    def train_fn(self, i: int, profile: Any = None) -> Callable:
        if self._single is None:
            self._single = jax.jit(self.jax_train)
        single = self._single
        template = self.init_params()
        idx = int(i)

        def _train(params: Any, round_idx: int, client: Any
                   ) -> tuple[Any, dict]:
            vec = jnp.asarray(flatten_to_vector(params))
            new, aux = single(vec, jnp.int32(idx), jnp.int32(round_idx),
                              self.frozen())
            metrics = {k: float(v) for k, v in aux.items()}
            for name in self.counters:
                tracing.count(name, int(metrics[name]))
            return unflatten_from_vector(np.asarray(new, np.float32),
                                         template), metrics

        return _train

    def jax_train(self, vec, client_idx, round_idx, frozen=None):
        new, aux = self.step(vec[None], client_idx[None], round_idx[None],
                             frozen)
        return new[0], {k: v[0] for k, v in aux.items()}

    def jax_train_batch(self, stack, client_idx, round_idx, frozen):
        """The batched step: the silos share each frozen product
        (projections, head) as one batch, and the experts take one silo's
        tokens at a time in a loop that holds one dispatch's rows, where
        ``jax.vmap`` of the dispatch would hold all of them."""
        return self.step(stack, client_idx, round_idx, frozen)


class MLAClientModel(LMClientModel):
    """LoRA on the latent attention of a frozen DeepSeek-V3-type MoE
    decoder, :data:`MLA_ARCH` (Moonlight-16B-A3B): the same client, step
    and streams as ``lm`` (module docstring)."""

    name = "lm_mla"
    arch = MLA_ARCH
