"""One chip's share of ZAYA1-8B (zaya1_8b_ep2.json: 8 of 16 experts a layer,
an eighth of the tied table, layers 0-4 of 40) as models.MoeLM builds it,
through the package's public API; the operations one sequence needs, and
the operations and bytes of the kernels whose share of the roofline the
benchmark reports."""
import jax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.models import MoeLM
from incubator_mxnet_tpu.models.transformer_lm import lm_loss


def net(doc, seed):
    mx.random.seed(seed)
    depth = doc["num_hidden_layers"]
    held = doc["num_experts_held"]
    if (not doc["tie_word_embeddings"] or doc["attention_bias"]
            or doc["lm_head_bias"] or doc["sliding_window"] is not None
            or doc["hidden_act"] != "silu"):
        raise ValueError("MoeLM builds this family with a tied table, no "
                         "bias, no window and silu-gated experts")
    model = MoeLM(doc["vocab_rows_held"], doc["layer_types"][:depth],
                  units=doc["hidden_size"],
                  num_heads=doc["num_attention_heads"],
                  num_kv_heads=doc["num_key_value_heads"],
                  head_dim=doc["head_dim"],
                  moe_hidden_size=doc["moe_intermediate_size"],
                  num_experts=doc["num_experts"],
                  top_k=doc["num_experts_per_tok"],
                  held=(held["first"], held["count"]),
                  rope_parameters=doc["rope_parameters"],
                  rms_norm_eps=doc["rms_norm_eps"],
                  # one expert a token: its weight is its own probability
                  norm_topk_prob=False,
                  compressed_attention={key: doc[key] for key in
                                        ("cca_time0", "cca_time1")},
                  router={"router_hidden_size": doc["router_hidden_size"],
                          "selection_bias": True},
                  tie_word_embeddings=doc["tie_word_embeddings"])
    # the table too (the file's `assumed`, "init"): it is the head as well,
    # and at unit scale every logit would be of the order of the width
    model.initialize(init=mx.init.Normal(0.02))
    model.cast(doc["dtype"])
    return model


def loss(doc):
    """Shifted cross-entropy with the logits cast to float32 first, as the
    Mellum2 configuration's (mellum2_12b_a2.5b_ep8.py)."""
    return lambda logits, targets: lm_loss(logits.astype("float32"),
                                           targets).mean()


def optimizer(doc):
    opt = dict(doc["optimizer"])
    return mx.optimizer.create(opt.pop("name"), **opt)


def batch(doc, traffic, seed):
    """One batch of uniform random tokens over the held rows of the
    vocabulary, made on the device; a sequence is its own target (lm_loss
    shifts it)."""
    tokens = nd.array(jax.jit(
        lambda key: jax.random.randint(
            key, (traffic["batch"], traffic["seq"]), 0,
            doc["vocab_rows_held"]))(jax.random.PRNGKey(seed)))
    return tokens, tokens


def expert_flops(doc, live_rows):
    """Forward operations of one layer's held experts on `live_rows`
    assignments: gate, up and down, 2 a multiply-add."""
    return (live_rows * 3 * 2 * doc["hidden_size"]
            * doc["moe_intermediate_size"])


def expert_bytes(doc, live_rows, itemsize=2):
    """Bytes one pass over one layer's held experts has to move: their
    weights once, the live rows in and out."""
    weights = (doc["num_experts_held"]["count"] * 3 * doc["hidden_size"]
               * doc["moe_intermediate_size"])
    return itemsize * (weights + live_rows * 2 * doc["hidden_size"])


def attention_flops(doc, seq, kind):
    """Forward operations of one layer's attention kernel on one sequence:
    q k^T and p v over the causal pairs of every query head, inside the
    latent (heads of head_dim)."""
    pairs = seq * (seq + 1) // 2
    return 4 * pairs * doc["head_dim"] * doc["num_attention_heads"]


def flops_per_sample(doc, traffic, live_rows=None):
    """Forward and backward (3 x forward) of one sequence, 2 operations to
    a multiply-add. A layer: the mixer's four projections (q and the
    output at heads x head_dim, k and v at key/value heads x head_dim), the
    convolution that mixes a head's channels (its taps x heads of head_dim
    squared), attention over the causal pairs, the router (the down
    projection, two square products, the experts' scores) and the held
    experts on the rows that are live (`live_rows`, one count a layer,
    summed over the batch, as the program counted them; else an even
    share, tokens x held / experts); then the tied head over the held
    rows. Norms, the depthwise taps, the mean, rotary, softmax, GELU, the
    dispatch and Adam are not counted."""
    seq, depth = traffic["seq"], doc["num_hidden_layers"]
    d, hd = doc["hidden_size"], doc["head_dim"]
    heads, kv_heads = doc["num_attention_heads"], doc["num_key_value_heads"]
    wide = doc["router_hidden_size"]
    if live_rows is None:
        share = (doc["num_experts_per_tok"]
                 * doc["num_experts_held"]["count"] / doc["num_experts"])
        live_rows = [seq * traffic["batch"] * share] * depth
    projections = 2 * seq * d * hd * (2 * heads + 2 * kv_heads)
    mixing = 2 * seq * doc["cca_time1"] * (heads + kv_heads) * hd * hd
    router = 2 * seq * (d * wide + 2 * wide * wide
                        + wide * doc["num_experts"])
    total = 2 * seq * d * doc["vocab_rows_held"]
    for kind, rows in zip(doc["layer_types"][:depth], live_rows):
        total += (projections + mixing + router
                  + attention_flops(doc, seq, kind)
                  + expert_flops(doc, rows / traffic["batch"]))
    return 3 * total
