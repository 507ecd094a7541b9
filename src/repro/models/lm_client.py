"""A windowed/full-attention MoE decoder as a federated client: LoRA
fine-tuning of a frozen base, one silo per client.

The deployment is cross-silo federated instruction tuning (FedIT,
arXiv:2305.05644; OpenFedLLM, arXiv:2402.06954): every silo holds the same
frozen base model, trains low-rank adapters on the attention projections
on its own token stream, and sends only the adapters; the server averages
them.  The flat parameter vector the fleet moves is therefore the adapters
alone, and the base reaches the jitted step as an argument shared by the
whole batch (:meth:`LMClientModel.frozen`), never as a constant.

The architecture is the shelf's :class:`~repro.configs.base.ModelConfig`
:data:`ARCH` (Mellum2-12B-A2.5B), cut in depth or shrunk for tests through
``num_layers`` and ``overrides``.  One layer::

    h = x + Attn(RMSNorm(x))      GQA, RoPE; windowed or full by layer type
    y = h + MoE(RMSNorm(h))       softmax router, top-k renormalised,
                                  dropless SwiGLU experts

Windowed layers use the default RoPE table, full layers YaRN's, with cos
and sin scaled by the attention factor.  LoRA (rank ``lora_rank``, scale
``lora_alpha / lora_rank``) sits on ``wq``, ``wk``, ``wv`` and ``wo``:
``x W + s * (x A) B``.

Precision: base weights and activations bfloat16 with float32
accumulation; adapters, their products (``precision="highest"``), their
gradients and the SGD step float32.

Everything is drawn from the seed, on the device:

* base leaf ``BASE_LEAVES[j]``, layer ``l``:
  ``normal(fold_in(fold_in(PRNGKey(seed), j), l), shape) * fan_in**-0.5``
  in float32, then bfloat16 (layer-less leaves use ``fold_in(PRNGKey(seed),
  j)``); the norms' scales are ones;
* the token stream of silo ``c`` at round ``r``, local step ``s``:
  ``u = uniform(fold_in(fold_in(fold_in(fold_in(PRNGKey(seed), STREAM), c),
  r), s), (seq_len + 1,))``, token ``(c * stride + floor(u**4 * 2 *
  stride)) % vocab`` with ``stride = vocab // n_clients``: each silo
  favours its own slice of the vocabulary (non-IID), Zipf-like within it;
  the sequence is the first ``seq_len`` tokens, the labels the last;
* the adapters' starting values (host, numpy): ``A`` uniform in
  ``±fan_in**-0.5`` from ``default_rng(seed)``, ``B`` zero.

The plain reference of the same step is :mod:`repro.models.lm_reference`
(and ``bench/reference/lm.py``, which imports nothing of this package).
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

#: The shelf configuration the client trains.
ARCH = "mellum2-12b-a2.5b"
#: Base leaves in key order (``fold_in(PRNGKey(seed), index)``).
BASE_LEAVES = ("embed", "unembed", "final_norm", "attn_norm", "mlp_norm",
               "wq", "wk", "wv", "wo", "router", "we_gate", "we_up",
               "we_down")
#: ``fold_in`` tag of the token streams' key.
STREAM = 1 << 20
#: Projections that carry an adapter.
LORA_TARGETS = ("wq", "wk", "wv", "wo")
#: Queries per attention chunk and positions per chunk of the head's
#: logits: at 8 silos x 4,096 positions a full layer's chunk of scores is
#: 0.5 GB in float32, and the step fits one v5e beside its 7.6 GB base.
ATTN_CHUNK = 128
LOSS_CHUNK = 256
#: Queries per causal-prefix run of a full layer (its keys stop at the
#: run's last query: 56% of the all-keys work at 4,096 positions).
FULL_GROUP = 512


def base_layout(cfg: ModelConfig) -> dict[str, tuple[tuple, int, bool]]:
    """``{leaf: (shape of one layer or of the whole leaf, fan_in, per
    layer)}``; a fan-in of 0 marks a norm scale (ones)."""
    d, hd, F, E = cfg.d_model, cfg.resolved_head_dim, cfg.d_ff, \
        cfg.num_experts
    q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    return {
        "embed": ((cfg.vocab_size, d), d, False),
        "unembed": ((d, cfg.vocab_size), d, False),
        "final_norm": ((d,), 0, False),
        "attn_norm": ((d,), 0, True),
        "mlp_norm": ((d,), 0, True),
        "wq": ((d, q), d, True),
        "wk": ((d, kv), d, True),
        "wv": ((d, kv), d, True),
        "wo": ((q, d), q, True),
        "router": ((d, E), d, True),
        "we_gate": ((E, F, d), d, True),        # experts (out, in)
        "we_up": ((E, F, d), d, True),
        "we_down": ((E, d, F), F, True),
    }


def adapter_layout(cfg: ModelConfig, rank: int) -> list[tuple[str, tuple]]:
    """Flat-vector layout of the adapters: ``(name, shape)`` in sorted
    name order (``tree_leaves`` of the dict), each stacked over layers."""
    hd = cfg.resolved_head_dim
    width = {"wq": (cfg.d_model, cfg.num_heads * hd),
             "wk": (cfg.d_model, cfg.num_kv_heads * hd),
             "wv": (cfg.d_model, cfg.num_kv_heads * hd),
             "wo": (cfg.num_heads * hd, cfg.d_model)}
    out = {}
    for t in LORA_TARGETS:
        fan_in, fan_out = width[t]
        out[f"a_{t}"] = (cfg.num_layers, fan_in, rank)
        out[f"b_{t}"] = (cfg.num_layers, rank, fan_out)
    return sorted(out.items())


def init_base(cfg: ModelConfig, key: jax.Array) -> dict:
    """The frozen base, bfloat16, drawn on the device leaf by leaf and
    layer by layer (module docstring)."""
    out, layout = {}, base_layout(cfg)
    for j, name in enumerate(BASE_LEAVES):
        shape, fan_in, per_layer = layout[name]
        full = ((cfg.num_layers,) + shape) if per_layer else shape
        if not fan_in:
            out[name] = jnp.ones(full, jnp.bfloat16)
            continue
        leaf_key = jax.random.fold_in(key, j)
        if per_layer:
            keys = jax.vmap(lambda i: jax.random.fold_in(leaf_key, i))(
                jnp.arange(cfg.num_layers))
            out[name] = _draw_layers(keys, shape, fan_in)
        else:
            out[name] = _draw(leaf_key, shape, fan_in)
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
    (seq_len, head_dim / 2) float32."""
    hd = cfg.resolved_head_dim
    pos = jnp.arange(seq_len, dtype=jnp.int32)
    window = L.rope_cos_sin_freqs(pos, L.rope_inv_freq(hd, cfg.rope_theta))
    if not cfg.rope_yarn_factor:
        return window, window
    full = L.rope_cos_sin_freqs(pos, L.yarn_inv_freq(
        hd, cfg.rope_theta, cfg.rope_yarn_factor, cfg.rope_yarn_original_max,
        cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow),
        cfg.rope_yarn_attention_factor)
    return window, full


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
        row's gradient is its own silo's), and per-silo ``aux``."""
        cfg = self.cfg
        x = jnp.take(base["embed"], toks[:, :-1], axis=0)       # (K, T, d)
        tables = rope_tables(cfg, self.seq_len)
        flags = jnp.asarray(T.is_global_flags(cfg))
        layers = {n: base[n] for n, (_, _, per_layer)
                  in base_layout(cfg).items() if per_layer}
        lora = {n: jnp.swapaxes(a, 0, 1) for n, a in ad.items()}  # (L,K,..)

        @jax.checkpoint
        def layer(x, xs):
            p, a, full = xs
            x = x + self._attention(L.rmsnorm(x, p["attn_norm"]), p, a,
                                    full, tables)
            y, counts = self._moe(L.rmsnorm(x, p["mlp_norm"]), p)
            rows = counts.sum(-1)
            return x + y, (rows, counts.max(-1),
                           self.seq_len * cfg.num_experts_per_tok - rows)

        x, (rows, rows_max, dropped) = jax.lax.scan(
            layer, x, (layers, lora, flags))
        nll = self._head(L.rmsnorm(x, base["final_norm"]), base["unembed"],
                         toks[:, 1:])
        return jnp.sum(nll), {"loss": nll, "rows": rows.sum(0),
                              "rows_max": rows_max.sum(0),
                              "dropped": dropped.sum(0)}

    # -- blocks ------------------------------------------------------------
    def _proj(self, h, w, a, b):
        """``h W + s * (h A) B``: the base product in bfloat16 with float32
        accumulation, the adapter's in float32."""
        base = jnp.einsum("ktd,do->kto", h, w,
                          preferred_element_type=jnp.float32)
        hp = jax.lax.Precision.HIGHEST
        low = jnp.einsum("ktd,kdr->ktr", h.astype(jnp.float32), a,
                         precision=hp)
        lora = jnp.einsum("ktr,kro->kto", low, b, precision=hp)
        return (base + jnp.float32(self.alpha / self.rank) * lora
                ).astype(h.dtype)

    def _attention(self, h, p, a, full, tables):
        cfg = self.cfg
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

    def _moe(self, h, p):
        """Dropless top-k experts, one silo's sequence at a time, as a silo
        computes them on its own chip (each rematerialised in the backward
        pass: all 8 silos' token-expert rows at once would not fit beside
        the base); returns the output and each silo's rows per expert
        (K, E)."""
        cfg = self.cfg
        E, topk = cfg.num_experts, cfg.num_experts_per_tok

        @jax.checkpoint
        def silo(x):
            with jax.named_scope("lm.moe"):
                top_w, top_i = T.moe_route(x, p["router"], topk)
                y = T.moe_dispatch(x, top_w, top_i, p["we_gate"],
                                   p["we_up"], p["we_down"],
                                   transposed=True)
                counts = jnp.zeros((E,), jnp.int32).at[top_i].add(1)
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
    ``ModelConfig`` field) cut or shrink :data:`ARCH`."""

    name = "lm"
    counters = ("train.tokens", "moe.rows", "moe.rows_max",
                "moe.dropped_rows")

    def __init__(self, n_clients: int, *, seed: int = 0,
                 num_layers: Optional[int] = None,
                 overrides: Optional[dict] = None, seq_len: int = 4096,
                 local_steps: int = 2, lr: float = 0.05,
                 lora_rank: int = 16, lora_alpha: float = 32.0):
        super().__init__(n_clients, seed=seed)
        cfg = get_config(ARCH)
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
        """The keys the plain reference reads (``lm_reference.sizes``):
        the sizes under the published ``config.json`` names, the training
        settings as ``model_args`` and the silo count."""
        cfg, step = self.cfg, self.step
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
        return {
            "num_hidden_layers": cfg.num_layers,
            "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "moe_intermediate_size": cfg.d_ff,
            "vocab_size": cfg.vocab_size,
            "sliding_window": cfg.sliding_window,
            "rms_norm_eps": 1e-6,
            "layer_types": ["full_attention" if f else "sliding_attention"
                            for f in flags],
            "rope_parameters": {"full_attention": full,
                                "sliding_attention": sliding},
            "tie_word_embeddings": cfg.tie_embeddings,
            "n_clients": self.n_clients,
            "model_args": {"seq_len": step.seq_len,
                           "local_steps": step.local_steps, "lr": step.lr,
                           "lora_rank": step.rank,
                           "lora_alpha": step.alpha}}

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
