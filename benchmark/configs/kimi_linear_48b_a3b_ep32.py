"""One chip's share of Kimi-Linear-48B-A3B (kimi_linear_48b_a3b_ep32.json: 8
of 256 routed experts a layer, an eighth of the vocabulary, the leading
dense layer and the four that follow it) as models.MoeLM builds it, through
the package's public API; the operations one sequence needs, and the
operations and bytes of the kernels whose share of the roofline the
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
    if (doc["tie_word_embeddings"] or not doc["mla_use_nope"]
            or doc["q_lora_rank"] is not None or doc["num_expert_group"] != 1
            or doc["num_shared_experts"] != 1):
        raise ValueError("MoeLM builds an untied head, latent attention "
                         "without positions or a query rank, a plain top-k "
                         "and one shared expert")
    model = MoeLM(doc["vocab_rows_held"], doc["layer_types"][:depth],
                  units=doc["hidden_size"],
                  num_heads=doc["num_attention_heads"],
                  num_kv_heads=doc["num_key_value_heads"],
                  head_dim=doc["head_dim"],
                  moe_hidden_size=doc["moe_intermediate_size"],
                  num_experts=doc["num_experts"],
                  top_k=doc["num_experts_per_token"],
                  held=(held["first"], held["count"]),
                  rms_norm_eps=doc["rms_norm_eps"],
                  norm_topk_prob=doc["moe_renormalize"],
                  mlp_layer_types=doc["mlp_layer_types"][:depth],
                  hidden_size=doc["intermediate_size"],
                  linear_attention=doc["linear_attn_config"],
                  latent_attention=doc,
                  router={"scoring": doc["moe_router_activation_func"],
                          "selection_bias": True,
                          "scale": doc["routed_scaling_factor"],
                          "shared_hidden_size": doc["num_shared_experts"]
                          * doc["moe_intermediate_size"]})
    model.initialize(init=mx.init.Normal(0.02))
    # the table at unit scale (the file's `assumed`, "init"): a token's own
    # embedding decides its routing, so the experts see an even load
    model.embedding.initialize(init=mx.init.Normal(1.0), force_reinit=True)
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
    q k^T over keys of 128 + 64 and p v over values of 128, on the causal
    pairs of every head of a latent layer; a linear-attention layer runs no
    such kernel."""
    if kind != "latent_attention":
        return 0
    pairs = seq * (seq + 1) // 2
    return (2 * pairs * doc["num_attention_heads"]
            * (doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
               + doc["v_head_dim"]))


def linear_attention_flops(doc, seq):
    """Forward operations of one layer's RECURRENCE on one sequence, as
    the token-by-token rule counts them whatever chunking runs it: a token
    a head decays the state (1 a state entry), reads it with k (2), writes
    the rank-one correction (2) and reads it with q (2)."""
    kda = doc["linear_attn_config"]
    return 7 * seq * kda["num_heads"] * kda["head_dim"] ** 2


def linear_attention_bytes(doc, seq, itemsize=2):
    """Bytes one pass of one layer's recurrence has to move: q, k, v and
    the log-decay in and o out, head_dim wide each, and beta."""
    kda = doc["linear_attn_config"]
    return itemsize * seq * kda["num_heads"] * (5 * kda["head_dim"] + 1)


def flops_per_sample(doc, traffic, live_rows=None):
    """Forward and backward (3 x forward) of one sequence, 2 operations to
    a multiply-add. A KDA layer: its q, k, v and output projections, the
    two ranks (decay, gate), beta, and the recurrence; the latent layer: q,
    the down and up projections, the output, attention over the causal
    pairs; the dense feed-forward; an expert layer: the router over all
    256, the shared expert on every token, the held experts on the rows
    that are live (`live_rows`, one count an EXPERT layer, as the program
    counted them; else an even share, tokens x 8 / 32); the head over the
    held rows. Norms, convolutions, gates, softmax, the dispatch and Adam
    are not counted."""
    seq, depth = traffic["seq"], doc["num_hidden_layers"]
    d, heads = doc["hidden_size"], doc["num_attention_heads"]
    kda = doc["linear_attn_config"]
    wide = kda["num_heads"] * kda["head_dim"]
    kinds = list(zip(doc["layer_types"][:depth],
                     doc["mlp_layer_types"][:depth]))
    if live_rows is None:
        share = (doc["num_experts_per_token"]
                 * doc["num_experts_held"]["count"] / doc["num_experts"])
        live_rows = [seq * traffic["batch"] * share] * sum(
            mlp == "sparse" for _, mlp in kinds)
    live_rows = iter(live_rows)
    qk = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
    total = 2 * seq * d * doc["vocab_rows_held"]
    for kind, mlp in kinds:
        if kind == "linear_attention":
            total += (2 * seq * d * (4 * wide + kda["num_heads"])
                      + 2 * 2 * seq * kda["head_dim"] * (d + wide)
                      + linear_attention_flops(doc, seq))
        else:
            total += (2 * seq * d * (heads * qk + doc["kv_lora_rank"]
                                     + doc["qk_rope_head_dim"])
                      + 2 * seq * doc["kv_lora_rank"] * heads
                      * (doc["qk_nope_head_dim"] + doc["v_head_dim"])
                      + 2 * seq * heads * doc["v_head_dim"] * d
                      + attention_flops(doc, seq, kind))
        if mlp == "dense":
            total += 3 * 2 * seq * d * doc["intermediate_size"]
        else:
            total += (2 * seq * d * doc["num_experts"]
                      + 3 * 2 * seq * d * doc["num_shared_experts"]
                      * doc["moe_intermediate_size"]
                      + expert_flops(doc, next(live_rows) / traffic["batch"]))
    return 3 * total
