"""Plain reference of gpt2_base: the forward pass and loss of
models.TransformerLM as published (pre-LN GPT-2 blocks, learned positions,
tied head, shifted cross-entropy) in straightforward float32 jax.numpy: no
kernel, no fused QKV trickery beyond the one matrix, no cache. Departure
kept from the model under test: the token embedding is scaled by sqrt(u).

`params` are the model's parameters as float32 arrays in the order of
`net.collect_params()`: token table, position table, then for each layer
the qkv, output, ffn-in and ffn-out matrices (weight (out, in), bias) and
the two layer norms (gamma, beta), then the final layer norm.
"""
import jax
import jax.numpy as jnp

EPS = 1e-5


def _layer_norm(x, gamma, beta):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * gamma + beta


def _dense(x, weight, bias):
    return x @ weight.T + bias


def loss(doc, params, tokens, targets):
    with jax.default_matmul_precision("highest"):
        params = iter(params)
        table, positions = next(params), next(params)
        b, seq = tokens.shape
        u, heads = doc["n_embd"], doc["n_head"]
        h = table[tokens] * jnp.sqrt(float(u)) + positions[:seq]
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        for _ in range(doc["n_layer"]):
            qkv, out, ffn_in, ffn_out, ln1, ln2 = (
                (next(params), next(params)) for _ in range(6))
            q, k, v = jnp.split(_dense(_layer_norm(h, *ln1), *qkv), 3, -1)
            q, k, v = (t.reshape(b, seq, heads, u // heads) for t in (q, k, v))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                float(u // heads))
            weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            mixed = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
            h = h + _dense(mixed.reshape(b, seq, u), *out)
            inner = jax.nn.gelu(_dense(_layer_norm(h, *ln2), *ffn_in),
                                approximate=False)
            h = h + _dense(inner, *ffn_out)
        logits = _layer_norm(h, next(params), next(params)) @ table.T
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        picked = jnp.take_along_axis(
            logp, targets[:, 1:, None].astype(jnp.int32), -1)
        return -picked.mean()
