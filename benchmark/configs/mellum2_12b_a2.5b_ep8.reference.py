"""Plain reference of mellum2_12b_a2.5b_ep8: the forward pass, loss and
gradients of one chip's share of Mellum2-12B-A2.5B as its `config.json`
describes it, in straightforward float32 jax.numpy at `highest` matmul
precision. No kernel, no sort, no cache: a boolean (L, L) mask per layer
type, and every token through every held expert, times its weight or 0.
It imports nothing from the package under test.

A layer of type t, on x (L, D):
    h = rms_norm(x; g1);  q = h Wq^T, k = h Wk^T, v = h Wv^T  (no bias)
    rotary (HF's rotate-half form) on every head of q and k, with the
      `rope_parameters` section of t: default, or YaRN with cos and sin
      times its attention factor
    query i of head h sees key j of head h // (heads / kv heads) iff j <= i
      and (t is full or i - j < sliding_window)
    x += concat_h softmax(q k^T / sqrt(head_dim)) v  Wo^T
    h2 = rms_norm(x; g2);  p = softmax(h2 Wr^T) over ALL experts
    S = the top-k of p;  w_e = p_e / sum_S p  (`norm_topk_prob`)
    x += sum over e in S that are HELD here of
         w_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
logits = rms_norm(x; gf) H^T over the held rows of the untied head; loss =
mean cross-entropy of position i against token i + 1. What the absent
experts would add is left out, as in the program (model-configs guide, 4).

`params` are float32 arrays in the order of the model's `collect_params()`:
the embedding table; a layer: g1, Wq, Wk, Wv, Wo (each (out, in)), g2, Wr
(experts, D), Wg and Wu (held, D, F), Wd (held, F, D), the load counter
(skipped); then gf and H.

Departures from the published model, as the configuration's `assumed` lists
them: no multi-token-prediction head (no key in `config.json`), softmax
before top-k and no router bias, no q/k norm, no auxiliary router loss.

`rows=` computes attention, the head and the loss in blocks of that many
query rows, each recomputed in backward (`jax.checkpoint`), so that the
cell's 8192 tokens fit beside the timed program: the same sums in the same
order, over fewer rows at a time. `operands=` rounds both operands of every
matrix product to that dtype first: the reading "in the next precision
below" that the cell's limits are set against (PERF.md).
"""
import functools
import math

import jax
import jax.numpy as jnp

PER_LAYER = 11      # arrays a layer: g1, Wq, Wk, Wv, Wo, g2, Wr, Wg, Wu, Wd, load


def inv_freq(head_dim, section):
    """(frequencies, factor on cos and sin) of one `rope_parameters`
    section, as HF's `_compute_default_rope_parameters` and
    `_compute_yarn_parameters` give them."""
    theta = section["rope_theta"]
    extra = [theta ** (-2.0 * j / head_dim) for j in range(head_dim // 2)]
    if section["rope_type"] == "default":
        return extra, 1.0
    original = section["original_max_position_embeddings"]

    def dim(turns):
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim(section["beta_fast"])), 0)
    high = min(math.ceil(dim(section["beta_slow"])), head_dim - 1)
    out = []
    for j, f in enumerate(extra):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(f / section["factor"] * ramp + f * (1.0 - ramp))
    return out, section["attention_factor"]


def _rounded(t, operands):
    """t with its values rounded to `operands`, the gradient passed
    straight through: a cast's own transpose rounds the cotangent too, and
    float8 flushes a gradient of 1e-5 to zero."""
    if operands is None:
        return t
    return t + jax.lax.stop_gradient(
        t.astype(operands).astype(jnp.float32) - t)


def _dot(a, b, operands):
    return _rounded(a, operands) @ _rounded(b, operands)


def _rms_norm(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _rotary(u, cos, sin):
    """u (L, heads, head_dim); cos, sin (L, head_dim)."""
    half = u.shape[-1] // 2
    rotated = jnp.concatenate([-u[..., half:], u[..., :half]], -1)
    return u * cos[:, None, :] + rotated * sin[:, None, :]


def _blocks(fn, rows, *per_row):
    """fn over blocks of `rows` leading rows of `per_row`, one block after
    the other (`jax.lax.map`), each recomputed in backward; joined again.
    The last block is padded with zeros, whose results are cut off."""
    length = per_row[0].shape[0]
    if rows is None or rows >= length:
        return fn(*per_row)
    count = -(-length // rows)

    def stacked(a):
        pad = [(0, count * rows - length)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad).reshape(count, rows, *a.shape[1:])
    out = jax.lax.map(lambda block: jax.checkpoint(fn)(*block),
                      tuple(stacked(a) for a in per_row))
    return jax.tree_util.tree_map(
        lambda a: a.reshape(count * rows, *a.shape[2:])[:length], out)


def _attention(doc, kind, x, wq, wk, wv, wo, rows, operands):
    length = x.shape[0]
    heads, kv_heads = doc["num_attention_heads"], doc["num_key_value_heads"]
    hd = doc["head_dim"]
    freq, factor = inv_freq(hd, doc["rope_parameters"][kind])
    angle = (jnp.arange(length, dtype=jnp.float32)[:, None]
             * jnp.asarray(freq, jnp.float32)[None, :])
    angle = jnp.concatenate([angle, angle], -1)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    q = _rotary(_dot(x, wq.T, operands).reshape(length, heads, hd), cos, sin)
    k = _rotary(_dot(x, wk.T, operands).reshape(length, kv_heads, hd), cos, sin)
    v = _dot(x, wv.T, operands).reshape(length, kv_heads, hd)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    i = jnp.arange(length)[:, None]
    j = jnp.arange(length)[None, :]
    visible = j <= i
    if kind == "sliding_attention":
        visible = visible & (i - j < doc["sliding_window"])

    def attend(q, visible):
        q, kk, vv = (_rounded(t, operands) for t in (q, k, v))
        scores = jnp.einsum("qhd,khd->hqk", q, kk) / math.sqrt(hd)
        # (the lowest float, not -inf: a padded row of a block sees nothing)
        weights = jax.nn.softmax(jnp.where(
            visible[None], scores, jnp.finfo(jnp.float32).min), -1)
        return jnp.einsum("hqk,khd->qhd", _rounded(weights, operands), vv)

    mixed = _blocks(attend, rows, q, visible)
    return _dot(mixed.reshape(length, heads * hd), wo.T, operands)


def _experts(doc, x, wr, wg, wu, wd, operands):
    """The held experts' part, dense: every token through every held
    expert, times its weight or 0; one expert after the other (`lax.scan`
    with the expert recomputed in backward: an unrolled loop compiled five
    times as long, PR 28)."""
    first, count = doc["num_experts_held"]["first"], wg.shape[0]
    prob = jax.nn.softmax(_dot(x, wr.T, operands), -1)
    top, chosen = jax.lax.top_k(prob, doc["num_experts_per_tok"])
    if doc["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)

    def add(out, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == first + e, top, 0.0), -1)
        inner = jax.nn.silu(_dot(x, gate, operands)) * _dot(x, up, operands)
        return out + weight[:, None] * _dot(inner, down, operands), None
    return jax.lax.scan(jax.checkpoint(add), jnp.zeros_like(x),
                        (jnp.arange(count), wg, wu, wd))[0]


def _layer(doc, kind, x, p, rows, operands):
    g1, wq, wk, wv, wo, g2, wr, wg, wu, wd, _load = p
    eps = doc["rms_norm_eps"]
    x = x + _attention(doc, kind, _rms_norm(x, g1, eps), wq, wk, wv, wo,
                       rows, operands)
    return x + _experts(doc, _rms_norm(x, g2, eps), wr, wg, wu, wd, operands)


def _hidden(doc, params, tokens, rows, operands):
    """The final hidden state (L, D) of one sequence, before the last norm."""
    x = params[0][tokens]
    for n in range(doc["num_hidden_layers"]):
        layer = functools.partial(_layer, doc, doc["layer_types"][n],
                                  rows=rows, operands=operands)
        if rows is not None:        # a layer at a time in backward too
            layer = jax.checkpoint(layer)
        x = layer(x, tuple(params[1 + n * PER_LAYER:1 + (n + 1) * PER_LAYER]))
    return x


def logits(doc, params, tokens, positions=None, rows=None, operands=None):
    """(B, L, vocabulary rows held) logits, or those of `positions` only."""
    with jax.default_matmul_precision("highest"):
        gf, head = params[-2], params[-1]

        def one(seq):
            x = _hidden(doc, params, seq, rows, operands)
            if positions is not None:
                x = x[jnp.asarray(positions)]
            return _dot(_rms_norm(x, gf, doc["rms_norm_eps"]), head.T,
                        operands)
        return jnp.stack([one(seq) for seq in tokens])


def loss(doc, params, tokens, targets, rows=None, operands=None):
    """Mean cross-entropy of position i against targets[i + 1]."""
    with jax.default_matmul_precision("highest"):
        gf, head = params[-2], params[-1]
        total = 0.0
        for seq, want in zip(tokens, targets):
            x = _hidden(doc, params, seq, rows, operands)[:-1]

            def picked(x, want):
                logp = jax.nn.log_softmax(_dot(
                    _rms_norm(x, gf, doc["rms_norm_eps"]), head.T, operands))
                return jnp.take_along_axis(logp, want[:, None], -1)[:, 0]
            total = total - _blocks(picked, rows, x,
                                    want[1:].astype(jnp.int32)).sum()
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def loss_and_grads(doc, params, tokens, targets, rows=None, operands=None):
    """(loss, its float32 gradient for every array of `params`; the load
    counters' are zeros)."""
    return jax.value_and_grad(functools.partial(
        loss, doc, rows=rows, operands=operands))(
            list(params), tokens, targets)


def _adam(doc):
    opt = doc["optimizer"]
    return (opt["learning_rate"], opt.get("beta1", 0.9),
            opt.get("beta2", 0.999), opt.get("epsilon", 1e-8))


def adam_step(doc, params, grads, step=1):
    """The configuration's optimizer, from a zero state: Adam's update
    number `step` = 1 with bias correction, as Kingma & Ba 2015 write it."""
    lr, b1, b2, eps = _adam(doc)
    out = []
    for p, g in zip(params, grads):
        m = (1 - b1) * g
        v = (1 - b2) * g * g
        out.append(p - lr * (m / (1 - b1 ** step))
                   / (jnp.sqrt(v / (1 - b2 ** step)) + eps))
    return out


def gradient_of_mean(doc, mean):
    """The gradient that Adam's first-moment state holds after update 1
    from a zero state, mean = (1 - beta1) g: how a fused step, which keeps
    no gradient, shows the one it computed."""
    return mean / (1 - _adam(doc)[1])
