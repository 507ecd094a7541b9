"""Plain reference of the ``lm`` client's local round, from the seed, for
the tests (``bench/reference/lm.py`` is the benchmark's copy, which imports
nothing of this package; the two are kept alike).

It imports nothing else of the program.  The sizes are the
configuration file's own keys, named as in the model's published
``config.json`` (``hidden_size``, ``layer_types``, ``rope_parameters``, ...);
the training settings are its ``model_args``.  The model is a decoder of
``num_hidden_layers`` layers::

    h = x + Attn(RMSNorm(x))   GQA with RoPE; sliding_attention layers see
                               the last ``sliding_window`` positions, with
                               the default table; full_attention layers see
                               every earlier position, with YaRN's table
                               (cos and sin times its attention factor)
    y = h + MoE(RMSNorm(h))    softmax router, top-k renormalised, SwiGLU
                               experts

with LoRA (rank ``lora_rank``, scale ``lora_alpha / lora_rank``) on the
q, k, v and o projections, a final RMSNorm and an untied head.  One silo's
round is ``local_steps`` SGD steps of ``lr`` on the mean next-token loss of
one sequence of its stream, each.

Everything is computed plainly: dense masked attention (a head at a
time, so that one head's scores are live), every expert for every token
weighted by the router (zero outside the top k), the head's logits in
blocks of positions (:data:`HEAD_BLOCK`), each of these re-run in the
backward pass, and nothing batched or cached across calls.  At
``"highest"`` every array is float32 and every product is
``precision="highest"``; the bfloat16 weights are upcast one layer (one
expert) at a time, so the float32 copy is never whole.  At ``"bfloat16"``
(the control) the adapters, their gradients, the step and every activation
are bfloat16: each product takes bfloat16 operands and accumulates in
float32 (written out as a float32 product of bfloat16 values, so that it
reads the same on a TPU and on a CPU), and its result is rounded to
bfloat16.

The weights, the token streams and the adapters' layout are drawn by the
recipe that ``repro.models.lm_client`` documents, rebuilt here bit for
bit: leaf j of :data:`BASE_LEAVES` at layer l from
``fold_in(fold_in(PRNGKey(seed), j), l)``, float32 normal times
``fan_in ** -0.5``, then bfloat16; norm scales one; silo c's stream at
round r, step s from ``fold_in(fold_in(fold_in(fold_in(PRNGKey(seed),
STREAM), c), r), s)``.  The flat vector is the adapters in sorted name
order: ``a_wk a_wo a_wq a_wv b_wk b_wo b_wq b_wv``, A of shape
(layers, fan_in, rank), B of (layers, rank, fan_out).  Expert weights are
stored (out, in): gate and up (experts, ffn, hidden), down (experts,
hidden, ffn).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BASE_LEAVES = ("embed", "unembed", "final_norm", "attn_norm", "mlp_norm",
               "wq", "wk", "wv", "wo", "router", "we_gate", "we_up",
               "we_down")
STREAM = 1 << 20
HEAD_BLOCK = 512            # positions per block of the head's logits


def sizes(config: dict) -> dict:
    """The model's sizes from a configuration file's published keys."""
    n = int(config["num_hidden_layers"])
    a = config["model_args"]
    return dict(
        layers=n, d=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv=int(config["num_key_value_heads"]), hd=int(config["head_dim"]),
        experts=int(config["num_experts"]),
        topk=int(config["num_experts_per_tok"]),
        ffn=int(config["moe_intermediate_size"]),
        vocab=int(config["vocab_size"]),
        window=int(config["sliding_window"]),
        eps=float(config["rms_norm_eps"]),
        full=tuple(t == "full_attention" for t in config["layer_types"][:n]),
        rope=config["rope_parameters"],
        seq=int(a["seq_len"]), steps=int(a["local_steps"]),
        lr=float(a["lr"]), rank=int(a["lora_rank"]),
        alpha=float(a["lora_alpha"]), clients=int(config["n_clients"]))


def base_shapes(s: dict) -> dict:
    """{leaf: (shape, fan_in, per layer)}; fan-in 0 is a norm scale."""
    d, q, kv = s["d"], s["heads"] * s["hd"], s["kv"] * s["hd"]
    E, F = s["experts"], s["ffn"]
    return {"embed": ((s["vocab"], d), d, False),
            "unembed": ((d, s["vocab"]), d, False),
            "final_norm": ((d,), 0, False),
            "attn_norm": ((d,), 0, True), "mlp_norm": ((d,), 0, True),
            "wq": ((d, q), d, True), "wk": ((d, kv), d, True),
            "wv": ((d, kv), d, True), "wo": ((q, d), q, True),
            "router": ((d, E), d, True), "we_gate": ((E, F, d), d, True),
            "we_up": ((E, F, d), d, True), "we_down": ((E, d, F), F, True)}


def adapter_shapes(s: dict) -> list[tuple[str, tuple]]:
    d, q, kv, r, n = s["d"], s["heads"] * s["hd"], s["kv"] * s["hd"], \
        s["rank"], s["layers"]
    io = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    out = {}
    for t, (fan_in, fan_out) in io.items():
        out[f"a_{t}"] = (n, fan_in, r)
        out[f"b_{t}"] = (n, r, fan_out)
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


def base_weights(s: dict, key) -> dict:
    out, layout = {}, base_shapes(s)
    for j, name in enumerate(BASE_LEAVES):
        shape, fan_in, per_layer = layout[name]
        full = ((s["layers"],) + shape) if per_layer else shape
        if not fan_in:
            out[name] = jnp.ones(full, jnp.bfloat16)
            continue
        leaf_key = jax.random.fold_in(key, j)
        if per_layer:
            keys = jax.vmap(lambda i: jax.random.fold_in(leaf_key, i))(
                jnp.arange(s["layers"]))
            out[name] = _draw_layers(keys, shape, fan_in)
        else:
            out[name] = _draw(leaf_key, shape, fan_in)
    return out


def tokens(s: dict, key, client, round_idx, step):
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, STREAM), client), round_idx), step)
    u = jax.random.uniform(k, (s["seq"] + 1,), jnp.float32)
    stride = s["vocab"] // s["clients"]
    rank = jnp.floor(u ** 4 * np.float32(2 * stride)).astype(jnp.int32)
    return (client * stride + rank) % s["vocab"]


def inv_freq(hd: int, section: dict) -> np.ndarray:
    """A RoPE section's frequencies: ``default``, or ``yarn`` as Hugging
    Face's ``_compute_yarn_parameters`` gives them."""
    theta = float(section["rope_theta"])
    pos_freqs = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if section.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32)
    factor = float(section["factor"])
    orig = float(section["original_max_position_embeddings"])

    def dim(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(dim(float(section["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(section["beta_slow"]))), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 - ramp
    return (1.0 / (factor * pos_freqs) * (1.0 - extra)
            + 1.0 / pos_freqs * extra).astype(np.float32)


def cos_sin(s: dict, section: dict):
    pos = np.arange(s["seq"], dtype=np.float32)
    ang = jnp.asarray(pos)[:, None] * jnp.asarray(inv_freq(s["hd"], section))
    scale = float(section.get("attention_factor", 1.0)) \
        if section.get("rope_type") == "yarn" else 1.0
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate(x, cos, sin):
    """x (T, n, hd) with cos, sin (T, hd/2): each half-pair rotated."""
    h = x.shape[-1] // 2
    c, s_ = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], axis=-1)


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
        input gradients through every frozen product (once more each),
        the attention scores' and values' backward (twice their forward:
        dQ, dK, dP, dV) and the adapters' forward, input and weight
        gradients (three times).  A windowed position attends to at most
        ``sliding_window`` positions, a full one to every earlier one.
        Rematerialisation is not counted."""
        s = sizes(config)
        d, q, kv, r, T = s["d"], s["heads"] * s["hd"], s["kv"] * s["hd"], \
            s["rank"], s["seq"]
        proj = 2 * d * q + 2 * 2 * d * kv + 2 * q * d
        moe = 2 * d * s["experts"] + s["topk"] * 3 * 2 * d * s["ffn"]
        lora = sum(2 * (i * r + r * o)
                   for i, o in ((d, q), (d, kv), (d, kv), (q, d)))
        keys_window = sum(min(t + 1, s["window"]) for t in range(T))
        keys_full = T * (T + 1) // 2
        scores = sum(2 * 2 * q * (keys_full if f else keys_window)
                     for f in s["full"])            # summed over positions
        linear = T * (s["layers"] * (proj + moe) + 2 * d * s["vocab"])
        per_step = 2 * linear + 3 * scores + 3 * T * s["layers"] * lora
        return per_step * s["steps"]

    @staticmethod
    def base_bytes(config: dict) -> int:
        """Bytes of the frozen base (bfloat16)."""
        s = sizes(config)
        n = 0
        for shape, _, per_layer in base_shapes(s).values():
            n += int(np.prod(shape)) * (s["layers"] if per_layer else 1)
        return 2 * n

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
        tables = {"sliding": cos_sin(s, s["rope"]["sliding_attention"]),
                  "full": cos_sin(s, s["rope"]["full_attention"])}
        T, H, KV, hd = s["seq"], s["heads"], s["kv"], s["hd"]
        G = H // KV
        pos = jnp.arange(T)
        causal = pos[None, :] <= pos[:, None]
        near = pos[:, None] - pos[None, :] < s["window"]

        def proj(x, w, a, t):
            return mm(x, w) + (scale * mm(mm(x, a[f"a_{t}"]),
                                          a[f"b_{t}"])).astype(dt)

        def attention(x, p, a, full):
            cos, sin = (jnp.where(full, f, w) for f, w in
                        zip(tables["full"], tables["sliding"]))
            q = rotate(proj(x, p["wq"], a, "wq").reshape(T, H, hd), cos, sin)
            k = rotate(proj(x, p["wk"], a, "wk").reshape(T, KV, hd), cos, sin)
            v = proj(x, p["wv"], a, "wv").reshape(T, KV, hd)
            mask = causal & (full | near)

            @jax.checkpoint
            def head(args):
                qh, kh, vh = args                 # (T, hd) each
                sc = jnp.matmul(up(qh), up(kh).T, precision=prec) \
                    * hd ** -0.5
                pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
                return jnp.matmul(up(pr.astype(dt)), up(vh),
                                  precision=prec).astype(dt)

            o = jax.lax.map(head, (             # query head h reads KV h // G
                jnp.moveaxis(q, 1, 0), jnp.repeat(jnp.moveaxis(k, 1, 0), G, 0),
                jnp.repeat(jnp.moveaxis(v, 1, 0), G, 0)))
            o = jnp.moveaxis(o, 0, 1).reshape(T, H * hd)
            return proj(o, p["wo"], a, "wo")

        def moe(x, p):
            probs = jax.nn.softmax(mm(x, p["router"]).astype(f32), axis=-1)
            top_w, top_i = jax.lax.top_k(probs, s["topk"])
            top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
            weight = jnp.zeros_like(probs).at[
                jnp.arange(T)[:, None], top_i].set(top_w)      # (T, E)

            @jax.checkpoint
            def expert(acc, xs):
                wg, wu, wd, w = xs
                h = jax.nn.silu(mm(x, wg.T)) * mm(x, wu.T)
                return acc + w[:, None].astype(dt) * mm(h, wd.T), None

            out, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
                p["we_gate"], p["we_up"], p["we_down"], weight.T))
            return out

        x = jnp.take(base["embed"], toks[:-1], axis=0).astype(dt)

        @jax.checkpoint
        def layer(x, xs):
            p, a, full = xs
            x = x + attention(norm(x, p["attn_norm"]), p, a, full)
            return x + moe(norm(x, p["mlp_norm"]), p), None

        x, _ = jax.lax.scan(layer, x, (
            {n: base[n] for n, (_, _, per) in base_shapes(s).items() if per},
            ad, jnp.asarray(s["full"])))
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
