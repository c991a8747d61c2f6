"""GPT-2 base (12 layers, 768 wide, 12 heads, 50257 tokens) as
models.TransformerLM builds it, through the package's public API, and the
operations one sequence needs, from the shapes in gpt2_base.json."""
import jax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.models import TransformerLM
from incubator_mxnet_tpu.models.transformer_lm import lm_loss


def net(doc, seed):
    mx.random.seed(seed)
    model = TransformerLM(doc["vocab_size"], num_layers=doc["n_layer"],
                          units=doc["n_embd"], hidden_size=doc["n_inner"],
                          num_heads=doc["n_head"],
                          max_length=doc["n_positions"],
                          dropout=doc["dropout"],
                          tie_weights=doc["tie_word_embeddings"])
    model.initialize(init=mx.init.Normal(0.02))
    model.cast(doc["dtype"])
    return model


def loss(doc):
    return lambda logits, targets: lm_loss(logits, targets).mean()


def optimizer(doc):
    opt = dict(doc["optimizer"])
    return mx.optimizer.create(opt.pop("name"), **opt)


def batch(doc, traffic, seed):
    """One batch of uniform random tokens, made on the device; a sequence
    is its own target (lm_loss shifts it)."""
    seq = min(traffic["seq"], doc["n_positions"])
    tokens = nd.array(jax.jit(
        lambda key: jax.random.randint(key, (traffic["batch"], seq), 0,
                                       doc["vocab_size"]))(
        jax.random.PRNGKey(seed)))
    return tokens, tokens


def flops_per_sample(doc, traffic):
    """Forward and backward (3 x forward) of one sequence, 2 operations to
    a multiply-add: the block's matrix multiplications (12 u^2 weights a
    layer), CAUSAL attention (QK^T and AV over the lower triangle: half of
    the 4 L^2 u that bench.py:687 counts for full attention) and the tied
    head's L x u x vocab product. The embedding gather, layer norms, GELU,
    softmax and Adam are not counted."""
    seq = min(traffic["seq"], doc["n_positions"])
    u, inner = doc["n_embd"], doc["n_inner"]
    block = 2 * seq * (4 * u * u + 2 * u * inner)
    attention = 2 * seq * seq * u
    head = 2 * seq * u * doc["vocab_size"]
    return 3 * ((block + attention) * doc["n_layer"] + head)
