"""One chip's share of Mellum2-12B-A2.5B (mellum2_12b_a2.5b_ep8.json: 8 of
64 experts a layer, an eighth of the vocabulary, the first period of four
layers) as models.MoeLM builds it, through the package's public API; the
operations one sequence needs, and the operations and bytes of the two
kernels whose share of the roofline the benchmark reports."""
import jax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.models import MoeLM
from incubator_mxnet_tpu.models.transformer_lm import lm_loss


def net(doc, seed):
    mx.random.seed(seed)
    depth = doc["num_hidden_layers"]
    held = doc["num_experts_held"]
    if (doc["tie_word_embeddings"]
            or set(doc["mlp_layer_types"][:depth]) != {"sparse"}):
        raise ValueError("MoeLM builds sparse layers and an untied head")
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
                  sliding_window=doc["sliding_window"],
                  rms_norm_eps=doc["rms_norm_eps"],
                  norm_topk_prob=doc["norm_topk_prob"])
    model.initialize(init=mx.init.Normal(0.02))
    # the table at unit scale (the file's `assumed`, "init"): a token's own
    # embedding, not the running average attention adds to it, decides its
    # routing, so the experts see the balanced load they are deployed for
    model.embedding.initialize(init=mx.init.Normal(1.0), force_reinit=True)
    model.cast(doc["dtype"])
    return model


def loss(doc):
    """Shifted cross-entropy with the logits cast to float32 first, as
    mixed-precision trainers compute it: the matrix products stay in the
    configuration's dtype, the loss has float32's resolution (a bfloat16
    loss near 9.9 moves in steps of 0.0625, which no reference could be
    held to)."""
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


def visible_pairs(doc, seq, kind):
    """Query-key pairs one head scores in a layer of type `kind`: the
    lower triangle, cut to the window on a sliding layer."""
    window = doc["sliding_window"] if kind == "sliding_attention" else seq
    return sum(min(i + 1, window) for i in range(seq))


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
    q k^T and p v over the visible pairs of every query head."""
    return (4 * visible_pairs(doc, seq, kind) * doc["head_dim"]
            * doc["num_attention_heads"])


def flops_per_sample(doc, traffic, live_rows=None):
    """Forward and backward (3 x forward) of one sequence, 2 operations to
    a multiply-add: the q, k, v and output projections, attention over the
    VISIBLE pairs only (causal, and the window on a sliding layer), the
    router, the held experts on the rows that are live (`live_rows`, one
    count a layer, summed over the batch, as the program counted them; else
    one an expert-parallel holder's share: a row a token a layer) and the
    head over the held rows of the vocabulary. Norms, rotary, softmax, the
    sort and gathers of the dispatch and Adam are not counted."""
    seq, depth = traffic["seq"], doc["num_hidden_layers"]
    d, hd = doc["hidden_size"], doc["head_dim"]
    heads, kv_heads = doc["num_attention_heads"], doc["num_key_value_heads"]
    if live_rows is None:
        live_rows = [seq * traffic["batch"]] * depth
    projections = 2 * seq * d * hd * (2 * heads + 2 * kv_heads)
    router = 2 * seq * d * doc["num_experts"]
    total = 2 * seq * d * doc["vocab_rows_held"]
    for kind, rows in zip(doc["layer_types"][:depth], live_rows):
        total += (projections + router + attention_flops(doc, seq, kind)
                  + expert_flops(doc, rows / traffic["batch"]))
    return 3 * total
