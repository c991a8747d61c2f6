"""Plain reference of zaya1_8b_ep2: the forward pass, loss and gradients of
one chip's share of ZAYA1-8B as its `config.json`, the CCA paper
(arXiv:2510.04476) and the ZAYA1 report (arXiv:2511.17127) describe it, in
straightforward float32 jax.numpy at `highest` matmul precision. No kernel,
no sort, no cache: a boolean mask of the keys a row may see, and every
token through every held expert, times its weight or 0. It imports nothing from
the package under test.

A layer, on x (L, D), with the router state r of the layer before:
    x += mixer(rms_norm(x; g1));  x += experts(rms_norm(x; g2), r).

Mixer (CCA; H query heads over G key/value heads of d; h its input):
    1. q' = h Wq^T (H d),  k' = h Wk^T (G d)
    2. v = [h Wv1^T ; shift(h) Wv2^T], shift(h)_t = h_(t-1), zeros at t = 0;
       Wv1 and Wv2 are the lower and upper half of Wv's rows, and v is read
       as G heads of d
    3. [q_c ; k_c] = conv1(conv0([q' ; k'])): conv0 causal and depthwise,
       y_t = sum_j c0[j] x_(t - K0 + 1 + j); conv1 causal, mixing the d
       channels of each of the H + G heads, y_t[h] = sum_j x_(t - K1 + 1 +
       j)[h] c1[j, h]
    4. with q' as (L, G, H / G, d) and k' as (L, G, 1, d):
       q = q_c + (q' + k') / 2;  k = k_c + (mean of q' over the group + k') / 2
    5. q <- sqrt(d) q / sqrt(sum q^2 + 1e-6);
       k <- sqrt(d) temp_g k / sqrt(sum k^2 + 1e-6), by head
    6. rotary positions (rotate-half) on the first partial_rotary_factor x d
       channels of every head of q and k; the rest pass
    7. o = causal softmax(q k^T / sqrt(d)) v, query head h reading key/value
       head h // (H / G);  out = o Wo^T
Experts (h the layer's second norm's output):
    r = h Wdown^T;  r += gamma * r_before (no gamma, nothing added, in the
    first layer);  s = W3 gelu(W2 gelu(W1 r)), erf GELU;  p = softmax(s);
    the expert is argmax(p + b) (b a selection bias no gradient reaches),
    its weight p of that expert; out = weight (silu(h Wg_e) * (h Wu_e))
    Wd_e if expert e is HELD here, else 0. r goes on to the next layer.
logits = rms_norm(x; gf) T^T over the held rows of the table T that also
embeds the tokens; loss = mean cross-entropy of position i against token
i + 1. What the absent experts would add is left out, as in the program
(model-configs guide, 4).

`params` are float32 arrays in the order of the model's `collect_params()`:
the table T; a layer: g1, c0 (K0, (H + G) d), c1 (K1, H + G, d, d), temp
(G), Wq, Wk, Wv, Wo (each (out, in)), g2, Wdown, W1, W2, W3, gamma (not in
the first layer), Wg and Wu (held, D, F), Wd (held, F, D), the load counter
(skipped), b; then gf.

Departures from the published model are the configuration's `assumed`.

`rows=` runs attention, the head and the loss in blocks of that many rows,
each made again in backward (`jax.checkpoint`), a layer at a time and an
expert at a time: the same sums over less at a time, so that the cell's
8192 tokens fit beside the timed program. `operands=` rounds both operands
of every matrix product to that dtype first: the reading "in the next
precision below" that the cell's limits are set against (PERF.md).
"""
import functools
import math

import jax
import jax.numpy as jnp

MIXER = 7       # c0, c1, temp, Wq, Wk, Wv, Wo


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


def _shift(x):
    """x_(t-1) at t, zeros at t = 0."""
    return jnp.pad(x[:-1], ((1, 0), (0, 0)))


def _before(x, taps):
    """(taps, L, ...): x_(t - taps + 1 + j) at [j, t], zeros before 0."""
    length = x.shape[0]
    padded = jnp.pad(x, [(taps - 1, 0)] + [(0, 0)] * (x.ndim - 1))
    return jnp.stack([padded[j:j + length] for j in range(taps)])


def _unit(x):
    return x * math.sqrt(x.shape[-1]) / jnp.sqrt(
        jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _rotary(doc, x):
    """Rotate-half on the first partial_rotary_factor x d channels of every
    head of x (L, heads, d), position t by angles t theta^(-2j / that)."""
    rope = doc["rope_parameters"]["hybrid"]
    if rope["rope_type"] != "default":
        raise ValueError("the reference rotates by the default frequencies")
    turned = int(x.shape[-1] * rope["partial_rotary_factor"])
    half = turned // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * rope["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:turned]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., turned:]], -1)


def _mixer(doc, x, p, rows, operands):
    c0, c1, temp, wq, wk, wv, wo = p
    heads, groups = doc["num_attention_heads"], doc["num_key_value_heads"]
    d = doc["head_dim"]
    length = x.shape[0]
    # 1. down into the latent
    q1, k1 = _dot(x, wq.T, operands), _dot(x, wk.T, operands)
    # 2. the value shift
    half = wv.shape[0] // 2
    v = jnp.concatenate([_dot(x, wv[:half].T, operands),
                         _dot(_shift(x), wv[half:].T, operands)], -1)
    v = v.reshape(length, groups, d)
    # 3. depthwise, then mixing the channels of a head
    qk = jnp.concatenate([q1, k1], -1)
    qk = jnp.sum(c0[:, None] * _before(qk, c0.shape[0]), 0)
    qk = jnp.einsum(
        "jlhc,jhcd->lhd",
        _rounded(_before(qk.reshape(length, heads + groups, d), c1.shape[0]),
                 operands), _rounded(c1, operands))
    q_c = qk[:, :heads].reshape(length, groups, heads // groups, d)
    k_c = qk[:, heads:].reshape(length, groups, 1, d)
    # 4. the q-k mean
    q1 = q1.reshape(length, groups, heads // groups, d)
    k1 = k1.reshape(length, groups, 1, d)
    q = q_c + (q1 + k1) / 2
    k = k_c + (jnp.mean(q1, 2, keepdims=True) + k1) / 2
    # 5. unit norms, a temperature a key/value head
    q = _unit(q).reshape(length, heads, d)
    k = (_unit(k) * temp[:, None, None]).reshape(length, groups, d)
    # 6. positions on part of a head
    q, k = _rotary(doc, q), _rotary(doc, k)
    # 7. causal attention over grouped heads
    k_low, v_low = _rounded(k, operands), _rounded(v, operands)

    def attend(q, at):
        q = _rounded(q, operands).reshape(-1, groups, heads // groups, d)
        scores = jnp.einsum("qgnd,kgd->gnqk", q, k_low) / math.sqrt(d)
        visible = jnp.arange(length)[None, :] <= at[:, None]
        # (the lowest float, not -inf: a padded row sees nothing)
        weights = jax.nn.softmax(jnp.where(
            visible[None, None], scores, jnp.finfo(jnp.float32).min), -1)
        out = jnp.einsum("gnqk,kgd->qgnd", _rounded(weights, operands),
                         v_low)
        return out.reshape(-1, heads * d)

    return _dot(_blocks(attend, rows, q, jnp.arange(length)), wo.T, operands)


def _gated(x, gate, up, down, operands):
    return _dot(jax.nn.silu(_dot(x, gate, operands)) * _dot(x, up, operands),
                down, operands)


def _experts(doc, x, p, before, operands):
    """(the held experts' part, dense: every token through every held
    expert, times its weight or 0, one expert after the other; the router's
    state for the next layer)."""
    wdown, w1, w2, w3 = p[:4]
    gamma = p[4] if len(p) == 10 else None
    wg, wu, wd, _load, bias = p[-5:]
    first = doc["num_experts_held"]["first"]
    state = _dot(x, wdown.T, operands)
    if gamma is not None:
        state = state + gamma * before
    hidden = jax.nn.gelu(_dot(state, w1.T, operands), approximate=False)
    hidden = jax.nn.gelu(_dot(hidden, w2.T, operands), approximate=False)
    prob = jax.nn.softmax(_dot(hidden, w3.T, operands), -1)
    chosen = jnp.argmax(jax.lax.stop_gradient(prob + bias), -1)
    top = jnp.take_along_axis(prob, chosen[:, None], -1)[:, 0]

    def part(e, gate, up, down):
        weight = jnp.where(chosen == first + e, top, 0.0)
        return weight[:, None] * _gated(x, gate, up, down, operands)
    # an expert at a time, made again in backward; the sum keeps nothing
    out = jax.lax.scan(
        lambda out, expert: (out + jax.checkpoint(part)(*expert), None),
        jnp.zeros_like(x), (jnp.arange(wg.shape[0]), wg, wu, wd))[0]
    return out, state


def _layer(doc, x, before, p, rows, operands):
    eps = doc["rms_norm_eps"]
    g1, mixer, g2, ffn = p[0], p[1:1 + MIXER], p[1 + MIXER], p[2 + MIXER:]
    x = x + _mixer(doc, _rms_norm(x, g1, eps), mixer, rows, operands)
    out, state = _experts(doc, _rms_norm(x, g2, eps), ffn, before, operands)
    return x + out, state


def _hidden(doc, params, tokens, rows, operands):
    """The final hidden state (L, D) of one sequence, before the last norm."""
    if doc["num_experts_per_tok"] != 1 or not doc["tie_word_embeddings"]:
        raise ValueError("the reference routes to one expert a token and "
                         "ties the head to the table")
    x = params[0][tokens]
    at, state = 1, None
    for n in range(doc["num_hidden_layers"]):
        layer = functools.partial(_layer, doc, rows=rows, operands=operands)
        if rows is not None:        # a layer at a time in backward too
            layer = jax.checkpoint(layer)
        count = 2 + MIXER + (9 if n == 0 else 10)
        x, state = layer(x, state, tuple(params[at:at + count]))
        at += count
    return x


def logits(doc, params, tokens, positions=None, rows=None, operands=None):
    """(B, L, vocabulary rows held) logits, or those of `positions` only."""
    with jax.default_matmul_precision("highest"):
        gf, table = params[-1], params[0]

        def one(seq):
            x = _hidden(doc, params, seq, rows, operands)
            if positions is not None:
                x = x[jnp.asarray(positions)]
            return _dot(_rms_norm(x, gf, doc["rms_norm_eps"]), table.T,
                        operands)
        return jnp.stack([one(seq) for seq in tokens])


def loss(doc, params, tokens, targets, rows=None, operands=None):
    """Mean cross-entropy of position i against targets[i + 1]."""
    with jax.default_matmul_precision("highest"):
        gf, table = params[-1], params[0]
        total = 0.0
        for seq, want in zip(tokens, targets):
            x = _hidden(doc, params, seq, rows, operands)[:-1]

            def picked(x, want):
                logp = jax.nn.log_softmax(_dot(
                    _rms_norm(x, gf, doc["rms_norm_eps"]), table.T, operands))
                return jnp.take_along_axis(logp, want[:, None], -1)[:, 0]
            total = total - _blocks(picked, rows, x,
                                    want[1:].astype(jnp.int32)).sum()
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def loss_and_grads(doc, params, tokens, targets, rows=None, operands=None):
    """(loss, its float32 gradient for every array of `params`; the
    counters' and the selection bias's are zeros; the table's sums the
    lookup's and the head's)."""
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
