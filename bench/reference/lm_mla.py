"""Plain reference of the ``lm_mla`` client's local round, from the seed.

It imports nothing of the system under test.  The sizes are the
configuration file's own keys, named as in the model's published
``config.json`` (DeepSeek-V3's: ``kv_lora_rank``, ``qk_nope_head_dim``,
``n_routed_experts``, ...); the training settings are its ``model_args``.
The model is a decoder of ``num_hidden_layers`` layers::

    h = x + MLA(RMSNorm(x))
    y = h + FFN(RMSNorm(h))    a dense SwiGLU of ``intermediate_size`` in
                               the first ``first_k_dense_replace`` layers;
                               then routed experts plus shared experts

MLA (latent attention, no query low-rank), written out per position t and
head i as DeepSeek-V3 defines it::

    [q_nope | q_pe]_i = (x W_q)_i               nope + rope dims a head
    [c | k_pe]        = x W_kv_a                a latent and one RoPE key
    c                 = RMSNorm(c)              kv_a_layernorm
    [k_nope | v]_i    = (c W_kv_b)_i
    q_pe, k_pe rotated pair by pair: (x_2j, x_2j+1) by t * theta**(-2j/rope)
    score(t, s)       = [q_nope | q_pe]_i(t) . [k_nope_i | k_pe](s)
                        / sqrt(nope + rope), causal, softmax over s
    o_i(t)            = sum_s p(t, s) v_i(s);  out = [o_1 .. o_H] W_o

Routing (``scoring_func: sigmoid``, ``topk_method: noaux_tc``): scores
``sigmoid(x W_r)`` in float32; the experts chosen are the top-k of score +
``e_score_correction_bias``; a chosen expert's weight is its score over the
sum of the chosen scores (+1e-20, ``norm_topk_prob``) times
``routed_scaling_factor``; every other expert's weight is 0.  Every expert
is computed for every token and weighted, and the shared experts (one
SwiGLU of ``n_shared_experts * moe_intermediate_size``) are added with
weight 1.  LoRA (rank ``lora_rank``, scale ``lora_alpha / lora_rank``) sits
on ``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj`` and ``o_proj``; a final
RMSNorm and an untied head.  One silo's round is ``local_steps`` SGD steps
of ``lr`` on the mean next-token loss of one sequence of its stream, each.

Everything is computed plainly: dense masked attention a head at a time,
every expert for every token, the head's logits in blocks of positions
(:data:`HEAD_BLOCK`), each of these re-run in the backward pass, nothing
batched or cached across calls.  At ``"highest"`` every array is float32
and every product ``precision="highest"``; the bfloat16 weights are upcast
one layer (one expert) at a time.  At ``"bfloat16"`` (the control) the
adapters, their gradients, the step and every activation are bfloat16,
each product bfloat16 operands accumulated in float32 and rounded to
bfloat16; the router's product stays float32 in both.

The weights, the token streams and the adapters' layout are drawn by the
recipe that ``repro.models.lm_client`` documents, rebuilt here bit for
bit: leaf j of :data:`BASE_LEAVES` (counting only the leaves present:
the dense ones where ``first_k_dense_replace`` > 0) at layer l (the
model's own layer number; the dense leaves span the first
``first_k_dense_replace`` layers, the router, bias and expert leaves the
rest) from
``fold_in(fold_in(PRNGKey(seed), j), l)``, float32 normal times
``fan_in ** -0.5``, then bfloat16; norm scales one;
``e_score_correction_bias`` float32 normal times :data:`BIAS_STD`; silo
c's stream at round r, step s from ``fold_in(fold_in(fold_in(fold_in(
PRNGKey(seed), STREAM), c), r), s)``.  Projections are stored (in, out),
``x W``, their columns in the published order (each head's ``[nope |
rope]`` or ``[k_nope | v]``, the RoPE dims as adjacent pairs); experts
(out, in): gate and up (experts, ffn, hidden), down (experts, hidden,
ffn); the dense and shared SwiGLUs (in, out).  The flat vector is the
adapters in sorted name order (``a_kv_a_proj_with_mqa a_kv_b_proj a_o_proj
a_q_proj b_...``), A of shape (layers, fan_in, rank), B of (layers, rank,
fan_out).

Departures from the published description: random weights, and a drawn
``e_score_correction_bias`` in place of the trained buffer; ``n_group`` and
``topk_group`` are 1, so the group limit is left out; the sequence-wise
balance loss (``seq_aux``) of pre-training is left out, as inference code
leaves it out; there is no MTP layer (``num_nextn_predict_layers`` 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BIAS = "e_score_correction_bias"
BASE_LEAVES = ("embed", "unembed", "final_norm", "attn_norm", "mlp_norm",
               "q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm",
               "kv_b_proj", "o_proj", "dense_gate", "dense_up",
               "dense_down", "router", BIAS, "we_gate", "we_up", "we_down",
               "shared_gate", "shared_up", "shared_down")
STREAM = 1 << 20
BIAS_STD = 0.02
HEAD_BLOCK = 512            # positions per block of the head's logits


def sizes(config: dict) -> dict:
    """The model's sizes from a configuration file's published keys; the
    variants this reference does not write out are refused."""
    expect = {"q_lora_rank": None, "scoring_func": "sigmoid",
              "topk_method": "noaux_tc", "norm_topk_prob": True,
              "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
              "rope_interleave": True, "tie_word_embeddings": False}
    for key, want in expect.items():
        if config[key] != want:
            raise ValueError(f"{key}={config[key]!r}: only {want!r} is "
                             f"written out here")
    a = config["model_args"]
    return dict(
        layers=int(config["num_hidden_layers"]),
        d=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        latent=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]),
        vd=int(config["v_head_dim"]),
        experts=int(config["n_routed_experts"]),
        topk=int(config["num_experts_per_tok"]),
        shared=int(config["n_shared_experts"]),
        ffn=int(config["moe_intermediate_size"]),
        dense_ffn=int(config["intermediate_size"]),
        dense=int(config["first_k_dense_replace"]),
        vocab=int(config["vocab_size"]),
        eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]),
        route_scale=float(config["routed_scaling_factor"]),
        seq=int(a["seq_len"]), steps=int(a["local_steps"]),
        lr=float(a["lr"]), rank=int(a["lora_rank"]),
        alpha=float(a["lora_alpha"]), clients=int(config["n_clients"]))


def projections(s: dict) -> dict:
    """{target: (fan_in, fan_out)} of the four adapted projections."""
    d, H = s["d"], s["heads"]
    return {"q_proj": (d, H * (s["nope"] + s["rope"])),
            "kv_a_proj_with_mqa": (d, s["latent"] + s["rope"]),
            "kv_b_proj": (s["latent"], H * (s["nope"] + s["vd"])),
            "o_proj": (H * s["vd"], d)}


def base_shapes(s: dict) -> dict:
    """{leaf: (shape, fan_in, layers)}; fan-in 0 is a norm scale, layers
    the model layers the leaf is stacked over (None: not per layer)."""
    d, E, F, V = s["d"], s["experts"], s["ffn"], s["vocab"]
    Fd, Fs = s["dense_ffn"], s["shared"] * s["ffn"]
    every = range(s["layers"])
    dense, sparse = range(s["dense"]), range(s["dense"], s["layers"])
    io = projections(s)
    out = {"embed": ((V, d), d, None), "unembed": ((d, V), d, None),
           "final_norm": ((d,), 0, None), "attn_norm": ((d,), 0, every),
           "mlp_norm": ((d,), 0, every),
           "q_proj": (io["q_proj"], d, every),
           "kv_a_proj_with_mqa": (io["kv_a_proj_with_mqa"], d, every),
           "kv_a_layernorm": ((s["latent"],), 0, every),
           "kv_b_proj": (io["kv_b_proj"], s["latent"], every),
           "o_proj": (io["o_proj"], io["o_proj"][0], every),
           "dense_gate": ((d, Fd), d, dense), "dense_up": ((d, Fd), d, dense),
           "dense_down": ((Fd, d), Fd, dense),
           "router": ((d, E), d, sparse), BIAS: ((E,), 1, sparse),
           "we_gate": ((E, F, d), d, sparse), "we_up": ((E, F, d), d, sparse),
           "we_down": ((E, d, F), F, sparse),
           "shared_gate": ((d, Fs), d, sparse),
           "shared_up": ((d, Fs), d, sparse),
           "shared_down": ((Fs, d), Fs, sparse)}
    return {n: out[n] for n in BASE_LEAVES if not (
        out[n][2] is not None and len(out[n][2]) == 0)}


def adapter_shapes(s: dict) -> list[tuple[str, tuple]]:
    out = {}
    for t, (fan_in, fan_out) in projections(s).items():
        out[f"a_{t}"] = (s["layers"], fan_in, s["rank"])
        out[f"b_{t}"] = (s["layers"], s["rank"], fan_out)
    return sorted(out.items())


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


def base_weights(s: dict, key) -> dict:
    out = {}
    for j, (name, (shape, fan_in, layers)) in enumerate(
            base_shapes(s).items()):
        full = shape if layers is None else (len(layers),) + shape
        if not fan_in:
            out[name] = jnp.ones(full, jnp.bfloat16)
            continue
        leaf_key = jax.random.fold_in(key, j)
        if layers is None:
            out[name] = _draw(leaf_key, shape, fan_in)
            continue
        keys = jax.vmap(lambda i: jax.random.fold_in(leaf_key, i))(
            jnp.arange(layers.start, layers.stop))
        out[name] = (_draw_bias(keys, shape) if name == BIAS
                     else _draw_layers(keys, shape, fan_in))
    return out


def tokens(s: dict, key, client, round_idx, step):
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, STREAM), client), round_idx), step)
    u = jax.random.uniform(k, (s["seq"] + 1,), jnp.float32)
    stride = s["vocab"] // s["clients"]
    rank = jnp.floor(u ** 4 * np.float32(2 * stride)).astype(jnp.int32)
    return (client * stride + rank) % s["vocab"]


def cos_sin(s: dict):
    """(seq, rope / 2) cos and sin of the default RoPE frequencies."""
    r = s["rope"]
    inv = (1.0 / s["theta"] ** (np.arange(0, r, 2, dtype=np.float64) / r)
           ).astype(np.float32)
    ang = jnp.asarray(np.arange(s["seq"], dtype=np.float32))[:, None] \
        * jnp.asarray(inv)
    return jnp.cos(ang), jnp.sin(ang)


def rotate_pairs(x, cos, sin):
    """x (T, n, r) with cos, sin (T, r/2): the adjacent pair (x_2j,
    x_2j+1) rotated by angle j, as a complex product, kept in place."""
    c, s_ = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * c - odd * s_, even * s_ + odd * c],
                     axis=-1).reshape(x.shape)


class Reference:
    def __init__(self, config: dict, seed: int):
        self.s = sizes(config)
        self.key = jax.random.PRNGKey(int(seed))
        self._base = None
        self._jitted: dict = {}

    @staticmethod
    def flops_per_update(config: dict) -> int:
        """Operations of one silo's local round, as the algorithm needs
        them (a multiply-add is 2): per position, the forward pass, the
        input gradients through every frozen product (once more each), the
        attention scores' and values' backward (twice their forward) and
        the adapters' forward, input and weight gradients (three times).
        A position attends to every earlier one: per head ``nope + rope``
        multiply-adds a score, ``v_head_dim`` a value.  The router, the
        top-k routed experts, the shared experts, the dense layers and
        the head are counted; rematerialisation is not."""
        s = sizes(config)
        d, r, T, n, k0 = s["d"], s["rank"], s["seq"], s["layers"], \
            s["dense"]
        io = projections(s).values()
        proj = sum(2 * i * o for i, o in io)
        lora = sum(2 * (i * r + r * o) for i, o in io)
        dense = 3 * 2 * d * s["dense_ffn"]
        moe = 2 * d * s["experts"] + s["topk"] * 3 * 2 * d * s["ffn"] \
            + 3 * 2 * d * s["shared"] * s["ffn"]
        keys = T * (T + 1) // 2                       # summed over positions
        scores = n * 2 * s["heads"] * (s["nope"] + s["rope"] + s["vd"]) \
            * keys
        linear = T * (n * proj + k0 * dense + (n - k0) * moe
                      + 2 * d * s["vocab"])
        per_step = 2 * linear + 3 * scores + 3 * T * n * lora
        return per_step * s["steps"]

    @staticmethod
    def base_bytes(config: dict) -> int:
        """Bytes of the frozen base: bfloat16, the selection bias
        float32."""
        s = sizes(config)
        n = 0
        for name, (shape, _, layers) in base_shapes(s).items():
            size = int(np.prod(shape)) * (1 if layers is None
                                          else len(layers))
            n += size * (4 if name == BIAS else 2)
        return n

    def base(self) -> dict:
        if self._base is None:
            self._base = base_weights(self.s, self.key)
        return self._base

    # -- one silo's round ------------------------------------------------
    def _loss(self, ad, base, toks, dt, prec):
        s = self.s
        f32 = jnp.float32

        def norm(x, w):
            xf = x.astype(f32)
            xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                    + s["eps"])
            return (xf * w.astype(f32)).astype(dt)

        def up(a):
            return a.astype(dt).astype(f32)

        def mm(a, b):
            return jnp.matmul(up(a), up(b), precision=prec).astype(dt)

        scale = s["alpha"] / s["rank"]
        T, H, R = s["seq"], s["heads"], s["latent"]
        nope, rope, vd = s["nope"], s["rope"], s["vd"]
        cos, sin = cos_sin(s)
        pos = jnp.arange(T)
        causal = pos[None, :] <= pos[:, None]

        def proj(x, p, a, t):
            return mm(x, p[t]) + (scale * mm(mm(x, a[f"a_{t}"]),
                                             a[f"b_{t}"])).astype(dt)

        def attention(x, p, a):
            q = proj(x, p, a, "q_proj").reshape(T, H, nope + rope)
            ckv = proj(x, p, a, "kv_a_proj_with_mqa")
            c = norm(ckv[:, :R], p["kv_a_layernorm"])
            kv = proj(c, p, a, "kv_b_proj").reshape(T, H, nope + vd)
            q = jnp.concatenate(
                [q[..., :nope], rotate_pairs(q[..., nope:], cos, sin)], -1)
            k_pe = rotate_pairs(ckv[:, None, R:], cos, sin)      # (T, 1, r)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_pe, (T, H, rope))], -1)

            @jax.checkpoint
            def head(args):
                qh, kh, vh = args
                sc = jnp.matmul(up(qh), up(kh).T, precision=prec) \
                    * (nope + rope) ** -0.5
                pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
                return jnp.matmul(up(pr.astype(dt)), up(vh),
                                  precision=prec).astype(dt)

            o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0),
                                   jnp.moveaxis(k, 1, 0),
                                   jnp.moveaxis(kv[..., nope:], 1, 0)))
            return proj(jnp.moveaxis(o, 0, 1).reshape(T, H * vd), p, a,
                        "o_proj")

        def swiglu(x, wg, wu, wd):
            return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)

        def moe(x, p):
            logits = jnp.matmul(x.astype(f32), p["router"].astype(f32),
                                precision="highest")
            scores = jax.nn.sigmoid(logits)
            _, top_i = jax.lax.top_k(scores + p[BIAS], s["topk"])
            top_w = jnp.take_along_axis(scores, top_i, -1)
            top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20) \
                * s["route_scale"]
            weight = jnp.zeros_like(scores).at[
                jnp.arange(T)[:, None], top_i].set(top_w)     # (T, E)

            @jax.checkpoint
            def expert(acc, xs):
                wg, wu, wd, w = xs
                h = jax.nn.silu(mm(x, wg.T)) * mm(x, wu.T)
                return acc + w[:, None].astype(dt) * mm(h, wd.T), None

            out, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
                p["we_gate"], p["we_up"], p["we_down"], weight.T))
            return out + swiglu(x, p["shared_gate"], p["shared_up"],
                                p["shared_down"])

        def leaves(lo, hi):
            """Per-layer leaves covering layers lo..hi-1, cut to them."""
            out = {}
            for name, (_, _, layers) in base_shapes(s).items():
                if layers is not None and layers.start <= lo \
                        and hi <= layers.stop:
                    out[name] = base[name][lo - layers.start:
                                           hi - layers.start]
            return out

        x = jnp.take(base["embed"], toks[:-1], axis=0).astype(dt)

        @jax.checkpoint
        def dense_layer(x, p, a):
            x = x + attention(norm(x, p["attn_norm"]), p, a)
            return x + swiglu(norm(x, p["mlp_norm"]), p["dense_gate"],
                              p["dense_up"], p["dense_down"])

        for i in range(s["dense"]):
            x = dense_layer(x, {n: v[0] for n, v in leaves(i, i + 1).items()},
                            {n: v[i] for n, v in ad.items()})

        @jax.checkpoint
        def layer(x, xs):
            p, a = xs
            x = x + attention(norm(x, p["attn_norm"]), p, a)
            return x + moe(norm(x, p["mlp_norm"]), p), None

        x, _ = jax.lax.scan(layer, x, (
            leaves(s["dense"], s["layers"]),
            {n: v[s["dense"]:] for n, v in ad.items()}))
        x = norm(x, base["final_norm"])
        labels = toks[1:]
        nb = T // min(HEAD_BLOCK, T)

        @jax.checkpoint
        def head(args):
            xb, lb = args
            logits = mm(xb, base["unembed"]).astype(f32)
            gold = jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]
            return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

        return jax.lax.map(head, (x.reshape(nb, T // nb, -1),
                                  labels.reshape(nb, -1))).sum() / T

    def _one(self, vec, client, rnd, base, key, precision):
        s = self.s
        dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
        prec = "highest"
        ad, off = {}, 0
        for name, shape in adapter_shapes(s):
            size = int(np.prod(shape))
            ad[name] = vec[off:off + size].reshape(shape).astype(dt)
            off += size
        loss = jnp.float32(0)
        for step in range(s["steps"]):
            toks = tokens(s, key, client, rnd, step)
            loss, g = jax.value_and_grad(self._loss)(ad, base, toks, dt,
                                                     prec)
            ad = {n: (ad[n] - jnp.asarray(s["lr"], dt) * g[n]).astype(dt)
                  for n in ad}
        flat = jnp.concatenate([ad[n].reshape(-1).astype(jnp.float32)
                                for n, _ in adapter_shapes(s)])
        return flat, loss.astype(jnp.float32)

    def train(self, stack: np.ndarray, client_idx, round_idx,
              precision: str, losses: bool = False):
        """Rows of ``stack`` trained as silos ``client_idx`` in rounds
        ``round_idx``, one row at a time; with ``losses`` also each row's
        loss at its last step (the program's ``train_loss``)."""
        if precision not in self._jitted:
            self._jitted[precision] = jax.jit(functools.partial(
                self._one, precision=precision))
        fn = self._jitted[precision]
        base = self.base()
        k = stack.shape[0]
        out = np.empty((k, stack.shape[1]), np.float32)
        last = np.empty(k, np.float64)
        for j in range(k):
            row, loss = fn(jnp.asarray(stack[j], jnp.float32),
                           jnp.int32(int(np.asarray(client_idx)[j])),
                           jnp.int32(int(np.asarray(round_idx)[j])),
                           base, self.key)
            out[j] = np.asarray(row, np.float32)
            last[j] = float(loss)
        return (out, last) if losses else out
