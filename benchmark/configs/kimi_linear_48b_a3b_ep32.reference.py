"""Plain reference of kimi_linear_48b_a3b_ep32: the forward pass, loss and
gradients of one chip's share of Kimi-Linear-48B-A3B as its `config.json`
and the Kimi Linear paper (arXiv:2510.26692) describe it, in straightforward
float32 jax.numpy at `highest` matmul precision. No kernel, no chunk, no
sort, no cache: the linear-attention recurrence TOKEN BY TOKEN, a boolean
(L, L) mask for the latent layer, and every token through every held expert,
times its weight or 0. It imports nothing from the package under test.

A layer, on x (L, D):  x += mixer(rms_norm(x; g1));  x += ffn(rms_norm(x; g2)).

Mixer "linear_attention" (KDA; H heads of d = 128 for keys and values):
    q, k, v = silu(conv(h Wq^T)), silu(conv(h Wk^T)), silu(conv(h Wv^T)),
      conv causal and depthwise: y_t = sum_j c[j] x_(t - 3 + j)
    by head  q^ = q / sqrt(sum q^2 + 1e-6) d^-1/2,  k^ = k / sqrt(sum k^2 + 1e-6)
    g = -exp(A_log_h) softplus((h Wfa^T) Wfb^T + dt_bias)      (L, H, d), <= 0
    beta = sigmoid(h Wb^T)                                     (L, H)
    S_t = (I - beta_t k^_t k^_t^T) diag(exp(g_t)) S_(t-1) + beta_t k^_t v_t^T,
      S_0 = 0, (d, d) a head;  o_t = S_t^T q^_t
    out = (rms_norm(o_t; gamma, by head) sigmoid((h Wga^T) Wgb^T)) Wo^T
Mixer "latent_attention" (MLA, no positions):
    q = h Wq^T (H x 192);  [c ; k_r] = h Wkva^T (512 + 64)
    [k_n ; v] = rms_norm(c; g_kv) Wkvb^T (H x (128 + 128));  key_h = [k_n,h ; k_r]
    causal softmax(q k^T / sqrt(192)) v, then Wo^T from H x 128
ffn "dense":  (silu(h Wg) * (h Wu)) Wd, D -> 9216 -> D
ffn "sparse": s = sigmoid(h Wr^T) over ALL experts; the chosen are the top-k
    of s + b (b a selection bias no gradient reaches); w = s_chosen / sum
    s_chosen x routed_scaling_factor; out = sum over the chosen experts HELD
    here of w_e (silu(h Wg_e) * (h Wu_e)) Wd_e, plus the shared expert
    (silu(h Wgs) * (h Wus)) Wds on every token.
logits = rms_norm(x; gf) H^T over the held rows of the untied head; loss =
mean cross-entropy of position i against token i + 1. What the absent
experts would add is left out, as in the program (model-configs guide, 4).

`params` are float32 arrays in the order of the model's `collect_params()`:
the embedding table; a layer: g1, the mixer's, g2, the feed-forward's; then
gf and H. KDA: the three taps (4, H d), A_log (H), dt_bias (H d), gamma (d),
the decay counter (skipped), Wq, Wk, Wv, Wfa, Wfb, Wb, Wga, Wgb, Wo (each
(out, in)). MLA: Wq, Wkva, g_kv, Wkvb, Wo. Dense: Wg, Wu (D, F), Wd (F, D).
Sparse: Wr (experts, D), Wg and Wu (held, D, F), Wd (held, F, D), the load
counter (skipped), b, then the shared expert's Wgs, Wus, Wds.

Departures from the published model are the configuration's `assumed`.

`rows=` runs the recurrence in blocks of that many tokens, each made again
in backward (`jax.checkpoint`), so that its 8192 states are never held at
once; attention, the dense feed-forwards, the head and the loss in blocks of
that many rows; and a mixer an eighth of its heads at a time (`_by_heads`):
the same sums, the heads' in another order, over less at a time, so that
the cell's 8192 tokens fit beside the timed program. `operands=` rounds both operands of
every matrix product to that dtype first (the state S and k, q in the
recurrence's reads; k and v - S^T k in its write): the reading "in the next
precision below" that the cell's limits are set against (PERF.md).
"""
import functools
import math

import jax
import jax.numpy as jnp

COUNT = {"linear_attention": 16, "latent_attention": 5, "dense": 3,
         "sparse": 9}


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


def _conv(x, taps):
    """Causal depthwise convolution: x (L, C), taps (K, C)."""
    size, length = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((size - 1, 0), (0, 0)))
    return sum(taps[j] * padded[j:j + length] for j in range(size))


def _recurrence(q, k, v, g, beta, rows, operands):
    """o (L, H, dv) of the gated delta rule from S_0 = 0, one token after
    the other: q, k, g (L, H, dk), v (L, H, dv), beta (L, H)."""
    def token(state, x):
        q, k, v, g, beta = x
        state = state * jnp.exp(g)[:, :, None]
        k_low = _rounded(k, operands)
        read = jnp.einsum("hkv,hk->hv", _rounded(state, operands), k_low)
        state = state + jnp.einsum(
            "hk,hv->hkv", k_low,
            _rounded(beta[:, None] * (v - read), operands))
        return state, jnp.einsum("hkv,hk->hv", _rounded(state, operands),
                                 _rounded(q, operands))

    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    state = jnp.zeros((heads, dk, dv), jnp.float32)
    xs = (q, k, v, g, beta)
    length = q.shape[0]
    if rows is None or rows >= length or length % rows:
        return block(state, xs)[1]
    xs = tuple(a.reshape(length // rows, rows, *a.shape[1:]) for a in xs)
    out = jax.lax.scan(jax.checkpoint(block), state, xs)[1]
    return out.reshape(length, heads, dv)


def _by_heads(part, heads, rows, out, per_head):
    """`out` plus the sum over the heads of part(arrays of a GROUP of heads):
    `per_head` arrays lead with the head axis. All heads at once, or with
    `rows` an eighth of them at a time, each group made again in backward:
    a mixer's heads meet only in that sum."""
    group = heads if rows is None else max(1, heads // 8)
    grouped = tuple(a.reshape(heads // group, group, *a.shape[1:])
                    for a in per_head)
    return jax.lax.scan(
        lambda acc, arrays: (acc + jax.checkpoint(part)(*arrays), None),
        out, grouped)[0]


def _linear_attention(doc, x, p, rows, operands):
    (cq, ck, cv, a_log, dt_bias, gamma, _lowest, wq, wk, wv, wfa, wfb, wb,
     wga, wgb, wo) = p
    heads = doc["linear_attn_config"]["num_heads"]
    d = doc["linear_attn_config"]["head_dim"]
    length, width = x.shape
    rate_low = _dot(x, wfa.T, operands)         # the two ranks' first halves
    gate_low = _dot(x, wga.T, operands)

    def by_head(w):                             # (H d, in) -> (H, d, in)
        return w.reshape(heads, d, w.shape[1])

    def taps(c):                                # (K, H d) -> (H, K, d)
        return c.reshape(c.shape[0], heads, d).transpose(1, 0, 2)

    def project(t, w):                          # (L, in), (G, d, in)
        return jnp.einsum("li,gci->lgc", _rounded(t, operands),
                          _rounded(w, operands))

    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    def part(wq, wk, wv, cq, ck, cv, a_log, dt_bias, wfb, wb, wgb, wo):
        group = wq.shape[0]

        def mixed(w, c):
            flat = _conv(project(x, w).reshape(length, group * d),
                         c.transpose(1, 0, 2).reshape(-1, group * d))
            return jax.nn.silu(flat).reshape(length, group, d)

        q, k, v = unit(mixed(wq, cq)) * d ** -0.5, unit(mixed(wk, ck)), \
            mixed(wv, cv)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            project(rate_low, wfb) + dt_bias)
        beta = jax.nn.sigmoid(_dot(x, wb.T, operands))
        o = _recurrence(q, k, v, g, beta, rows, operands)
        o = _rms_norm(o, gamma, doc["rms_norm_eps"]) * jax.nn.sigmoid(
            project(gate_low, wgb))
        return jnp.einsum("lgc,gDc->lD", _rounded(o, operands),
                          _rounded(wo, operands))

    return _by_heads(part, heads, rows, jnp.zeros_like(x), (
        by_head(wq), by_head(wk), by_head(wv), taps(cq), taps(ck), taps(cv),
        a_log, dt_bias.reshape(heads, d), by_head(wfb), wb, by_head(wgb),
        wo.reshape(width, heads, d).transpose(1, 0, 2)))


def _latent_attention(doc, x, p, rows, operands):
    wq, wkva, g_kv, wkvb, wo = p
    heads = doc["num_attention_heads"]
    rank, nope = doc["kv_lora_rank"], doc["qk_nope_head_dim"]
    rope, vd = doc["qk_rope_head_dim"], doc["v_head_dim"]
    length, width = x.shape
    down = _dot(x, wkva.T, operands)
    latent = _rms_norm(down[:, :rank], g_kv, doc["rms_norm_eps"])
    shared = down[:, rank:]                     # one part for every head
    visible = jnp.arange(length)[None, :] <= jnp.arange(length)[:, None]

    def part(wq, wkvb, wo):
        group = wq.shape[0]
        q = jnp.einsum("li,gci->lgc", _rounded(x, operands),
                       _rounded(wq, operands))
        up = jnp.einsum("li,gci->lgc", _rounded(latent, operands),
                        _rounded(wkvb, operands))
        k = jnp.concatenate([up[:, :, :nope], jnp.broadcast_to(
            shared[:, None], (length, group, rope))], -1)  # nothing rotated
        v = up[:, :, nope:]

        def attend(q, visible):
            q_, kk, vv = (_rounded(t, operands) for t in (q, k, v))
            scores = jnp.einsum("qhd,khd->hqk", q_, kk) / math.sqrt(
                nope + rope)
            # (the lowest float, not -inf: a padded row sees nothing)
            weights = jax.nn.softmax(jnp.where(
                visible[None], scores, jnp.finfo(jnp.float32).min), -1)
            return jnp.einsum("hqk,khd->qhd", _rounded(weights, operands),
                              vv)

        mixed = _blocks(attend, rows, q, visible)
        return jnp.einsum("lgc,gDc->lD", _rounded(mixed, operands),
                          _rounded(wo, operands))

    return _by_heads(part, heads, rows, jnp.zeros_like(x), (
        wq.reshape(heads, nope + rope, width),
        wkvb.reshape(heads, nope + vd, rank),
        wo.reshape(width, heads, vd).transpose(1, 0, 2)))


def _gated(x, gate, up, down, operands):
    return _dot(jax.nn.silu(_dot(x, gate, operands)) * _dot(x, up, operands),
                down, operands)


def _experts(doc, x, wr, wg, wu, wd, bias, operands):
    """The held ROUTED experts' part, dense: every token through every held
    expert, times its weight or 0; one expert after the other."""
    first, count = doc["num_experts_held"]["first"], wg.shape[0]
    score = jax.nn.sigmoid(_dot(x, wr.T, operands))
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(score + bias),
                              doc["num_experts_per_token"])
    top = jnp.take_along_axis(score, chosen, -1)
    if doc["moe_renormalize"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * doc["routed_scaling_factor"]

    def add(out, expert):
        e, gate, up, down = expert
        weight = jnp.sum(jnp.where(chosen == first + e, top, 0.0), -1)
        return out + weight[:, None] * _gated(x, gate, up, down,
                                              operands), None
    return jax.lax.scan(jax.checkpoint(add), jnp.zeros_like(x),
                        (jnp.arange(count), wg, wu, wd))[0]


def _sparse(doc, x, p, rows, operands):
    wr, wg, wu, wd, _load, bias, sg, su, sd = p
    return (_experts(doc, x, wr, wg, wu, wd, bias, operands)
            + _blocks(lambda h: _gated(h, sg, su, sd, operands), rows, x))


def _layer(doc, kind, mlp, x, p, rows, operands):
    eps = doc["rms_norm_eps"]
    n = COUNT[kind]
    g1, mixer, g2, ffn = p[0], p[1:1 + n], p[1 + n], p[2 + n:]
    mix = (_linear_attention if kind == "linear_attention"
           else _latent_attention)
    x = x + mix(doc, _rms_norm(x, g1, eps), mixer, rows, operands)
    h = _rms_norm(x, g2, eps)
    if mlp == "dense":
        return x + _blocks(lambda h: _gated(h, *ffn, operands), rows, h)
    return x + _sparse(doc, h, ffn, rows, operands)


def _hidden(doc, params, tokens, rows, operands):
    """The final hidden state (L, D) of one sequence, before the last norm."""
    x = params[0][tokens]
    at = 1
    for n in range(doc["num_hidden_layers"]):
        kind, mlp = doc["layer_types"][n], doc["mlp_layer_types"][n]
        layer = functools.partial(_layer, doc, kind, mlp, rows=rows,
                                  operands=operands)
        if rows is not None:        # a layer at a time in backward too
            layer = jax.checkpoint(layer)
        count = 2 + COUNT[kind] + COUNT[mlp]
        x = layer(x, tuple(params[at:at + count]))
        at += count
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
    """(loss, its float32 gradient for every array of `params`; the
    counters' and the selection bias's are zeros)."""
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
