"""Plain reference of joyai_llm_flash_ep32: the forward pass, loss and
gradients of one chip's share of JoyAI-LLM-Flash as its `config.json` and
the DeepSeek-V3 report (arXiv:2412.19437, sections 2.1 and 2.2) describe
it, in straightforward float32 jax.numpy at `highest` matmul precision. No
kernel, no sort, no cache: a boolean (L, L) mask, every token through
every held expert times its weight or 0, and the rotation as complex
multiplication of adjacent pairs. It imports nothing from the package
under test.

A layer, on x (L, D):  x += mla(rms_norm(x; g1));  x += ffn(rms_norm(x; g2)).

mla:  c_q = rms_norm(h Wqa^T; g_q) (1536);  q = c_q Wqb^T (H x (128 + 64))
      [c ; k_r] = h Wkva^T (512 + 64);  [k_n ; v] = rms_norm(c; g_kv) Wkvb^T
      (H x (128 + 128));  q_r and k_r rotated: channels (2j, 2j + 1) are
      the complex number a + ib, times exp(i t theta^(-2j/64)) at position
      t;  key_h = [k_n,h ; k_r];  causal softmax(q k^T / sqrt(192)) v, then
      Wo^T from H x 128
ffn "dense":  (silu(h Wg) * (h Wu)) Wd, D -> 7168 -> D
ffn "sparse": s = sigmoid(h Wr^T) over ALL experts; the chosen are the top-k
    of s + b (b a selection bias no gradient reaches); w = s_chosen / sum
    s_chosen x routed_scaling_factor; out = sum over the chosen experts HELD
    here of w_e (silu(h Wg_e) * (h Wu_e)) Wd_e, plus the shared expert
    (silu(h Wgs) * (h Wus)) Wds on every token.
logits = rms_norm(x; gf) H^T over the held rows of the untied head.
MTP:  x' = [rms_norm(E[t_(i+1)]; g_e) ; rms_norm(x_i; g_h)] Weh^T (the last
    position's t_L is id 0), one more expert layer, logits' =
    rms_norm(.; g_o) H^T with the SAME E and H.
loss = mean CE of position i against t_(i+1) + mtp_loss_weight x mean CE of
    the MTP's position i against t_(i+2).
What the absent experts would add is left out, as in the program, which
holds one chip's share of them.

`params` are float32 arrays in the order of the model's `collect_params()`:
the embedding table; a layer: g1, the mixer's Wqa, g_q, Wqb, Wkva, g_kv,
Wkvb, Wo (each (out, in)), g2, the feed-forward's; gf and H; then the MTP
module's g_e, g_h, Weh, its layer as above, g_o. Dense: Wg, Wu (D, F), Wd
(F, D). Sparse: Wr (experts, D), Wg and Wu (held, D, F), Wd (held, F, D),
the load counter (skipped), b, then the shared expert's Wgs, Wus, Wds.

Departures from the published model are the configuration's `assumed`.

`rows=` runs attention, the dense feed-forwards and the losses in blocks of
that many rows, each made again in backward, a layer at a time, and the
mixer an eighth of its heads at a time (`_by_heads`): the same sums over
less at a time, so that the cell's 8192 tokens fit beside the timed
program. `operands=` rounds both operands of every matrix product to that
dtype first: the reading "in the next precision below" that the cell's
limits are set against (PERF.md).
"""
import functools
import math

import jax
import jax.numpy as jnp

COUNT = {"latent_attention": 7, "dense": 3, "sparse": 9}


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


def _rotated(x, theta):
    """x (L, ..., d) turned by position: channels (2j, 2j + 1) as the
    complex number x_2j + i x_(2j+1), times exp(i t theta^(-2j/d)) at
    position t, and written back in place."""
    length, d = x.shape[0], x.shape[-1]
    turn = (jnp.arange(length, dtype=jnp.float32)[:, None]
            * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    turn = turn.reshape(length, *[1] * (x.ndim - 2), d // 2)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * jnp.exp(1j * turn)
    return jnp.stack([jnp.real(z), jnp.imag(z)], -1).reshape(x.shape)


def _latent_attention(doc, x, p, rows, operands):
    wqa, g_q, wqb, wkva, g_kv, wkvb, wo = p
    heads, eps, theta = (doc["num_attention_heads"], doc["rms_norm_eps"],
                         doc["rope_theta"])
    rank, nope = doc["kv_lora_rank"], doc["qk_nope_head_dim"]
    rope, vd = doc["qk_rope_head_dim"], doc["v_head_dim"]
    length, width = x.shape
    c_q = _rms_norm(_dot(x, wqa.T, operands), g_q, eps)
    down = _dot(x, wkva.T, operands)
    latent = _rms_norm(down[:, :rank], g_kv, eps)
    shared = _rotated(down[:, rank:], theta)    # one part for every head
    visible = jnp.arange(length)[None, :] <= jnp.arange(length)[:, None]

    def part(wqb, wkvb, wo):
        group = wqb.shape[0]
        q = jnp.einsum("li,gci->lgc", _rounded(c_q, operands),
                       _rounded(wqb, operands))
        q = jnp.concatenate([q[:, :, :nope], _rotated(q[:, :, nope:], theta)],
                            -1)
        up = jnp.einsum("li,gci->lgc", _rounded(latent, operands),
                        _rounded(wkvb, operands))
        k = jnp.concatenate([up[:, :, :nope], jnp.broadcast_to(
            shared[:, None], (length, group, rope))], -1)
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
        wqb.reshape(heads, nope + rope, wqb.shape[1]),
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
                              doc["num_experts_per_tok"])
    top = jnp.take_along_axis(score, chosen, -1)
    if doc["norm_topk_prob"]:
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


def _layer(doc, mlp, x, p, rows, operands):
    eps = doc["rms_norm_eps"]
    n = COUNT["latent_attention"]
    g1, mixer, g2, ffn = p[0], p[1:1 + n], p[1 + n], p[2 + n:]
    x = x + _latent_attention(doc, _rms_norm(x, g1, eps), mixer, rows,
                              operands)
    h = _rms_norm(x, g2, eps)
    if mlp == "dense":
        return x + _blocks(lambda h: _gated(h, *ffn, operands), rows, h)
    return x + _sparse(doc, h, ffn, rows, operands)


def _run(doc, mlp, x, p, rows, operands):
    layer = functools.partial(_layer, doc, mlp, rows=rows, operands=operands)
    if rows is not None:            # a layer at a time in backward too
        layer = jax.checkpoint(layer)
    return layer(x, tuple(p))


def _parts(doc, params):
    """(the table, each layer's parameters, gf, H, the MTP module's)."""
    at, layers = 1, []
    for mlp in doc["mlp_layer_types"][:doc["num_hidden_layers"]]:
        count = 2 + COUNT["latent_attention"] + COUNT[mlp]
        layers.append(params[at:at + count])
        at += count
    return params[0], layers, params[at], params[at + 1], params[at + 2:]


def _hidden(doc, params, tokens, rows, operands):
    """The main stack's last hidden state (L, D) of one sequence, before
    the last norm."""
    table, layers = _parts(doc, params)[:2]
    x = table[tokens]
    for mlp, p in zip(doc["mlp_layer_types"], layers):
        x = _run(doc, mlp, x, p, rows, operands)
    return x


def _ahead(doc, params, tokens, x, rows, operands):
    """The MTP block's hidden state (L, D) of one sequence, before its
    norm, from the main stack's `x`."""
    table, *_, mtp = _parts(doc, params)
    g_e, g_h, weh = mtp[:3]
    eps = doc["rms_norm_eps"]
    following = jnp.concatenate([tokens[1:], jnp.zeros(1, tokens.dtype)])
    joined = jnp.concatenate([_rms_norm(table[following], g_e, eps),
                              _rms_norm(x, g_h, eps)], -1)
    return _run(doc, "sparse", _dot(joined, weh.T, operands), mtp[3:-1],
                rows, operands)


def logits(doc, params, tokens, positions=None, rows=None, operands=None):
    """(B, L, vocabulary rows held) logits of the main head, or those of
    `positions` only."""
    with jax.default_matmul_precision("highest"):
        _, _, gf, head, _ = _parts(doc, params)

        def one(seq):
            x = _hidden(doc, params, seq, rows, operands)
            if positions is not None:
                x = x[jnp.asarray(positions)]
            return _dot(_rms_norm(x, gf, doc["rms_norm_eps"]), head.T,
                        operands)
        return jnp.stack([one(seq) for seq in tokens])


def mtp_logits(doc, params, tokens, rows=None, operands=None):
    """(B, L, vocabulary rows held) logits of the MTP module."""
    with jax.default_matmul_precision("highest"):
        _, _, _, head, mtp = _parts(doc, params)

        def one(seq):
            y = _ahead(doc, params, seq,
                       _hidden(doc, params, seq, rows, operands), rows,
                       operands)
            return _dot(_rms_norm(y, mtp[-1], doc["rms_norm_eps"]), head.T,
                        operands)
        return jnp.stack([one(seq) for seq in tokens])


def loss(doc, params, tokens, targets, rows=None, operands=None):
    """Mean cross-entropy of position i against targets[i + 1], plus
    mtp_loss_weight x that of the MTP's position i against targets[i + 2]."""
    with jax.default_matmul_precision("highest"):
        _, _, gf, head, mtp = _parts(doc, params)
        eps = doc["rms_norm_eps"]

        def summed(x, gamma, want):
            def picked(x, want):
                logp = jax.nn.log_softmax(_dot(
                    _rms_norm(x, gamma, eps), head.T, operands))
                return jnp.take_along_axis(logp, want[:, None], -1)[:, 0]
            return -_blocks(picked, rows, x, want.astype(jnp.int32)).sum()

        main = ahead = 0.0
        for seq, want in zip(tokens, targets):
            x = _hidden(doc, params, seq, rows, operands)
            y = _ahead(doc, params, seq, x, rows, operands)
            main = main + summed(x[:-1], gf, want[1:])
            ahead = ahead + summed(y[:-2], mtp[-1], want[2:])
        batch, length = tokens.shape
        return (main / (batch * (length - 1)) + doc["mtp_loss_weight"]
                * ahead / (batch * (length - 2)))


def loss_and_grads(doc, params, tokens, targets, rows=None, operands=None):
    """(loss, its float32 gradient for every array of `params`; the
    counters' and the selection biases' are zeros)."""
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
