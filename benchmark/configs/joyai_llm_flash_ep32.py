"""One chip's share of JoyAI-LLM-Flash (joyai_llm_flash_ep32.json: 8 of 256
routed experts a layer, an eighth of the vocabulary, the leading dense layer
and the four expert layers behind it, and the multi-token-prediction module)
as models.MoeLM builds it, through the package's public API; the operations
one sequence needs, and the operations and bytes of the kernels whose share
of the roofline the benchmark reports."""
import jax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.models import MoeLM
from incubator_mxnet_tpu.models.transformer_lm import lm_loss


def net(doc, seed):
    mx.random.seed(seed)
    depth = doc["num_hidden_layers"]
    held = doc["num_experts_held"]
    if (doc["tie_word_embeddings"] or doc["n_group"] != 1
            or doc["topk_group"] != 1 or doc["n_shared_experts"] != 1
            or doc["num_nextn_predict_layers"] > 1
            or doc["rope_scaling"] is not None
            or doc["scoring_func"] != "sigmoid"
            or doc["topk_method"] != "noaux_tc"):
        raise ValueError("MoeLM builds an untied head, a sigmoid top-k "
                         "router with a selection bias and no groups, one "
                         "shared expert, rotary positions without scaling "
                         "and at most one MTP depth")
    model = MoeLM(doc["vocab_rows_held"], doc["layer_types"][:depth],
                  units=doc["hidden_size"],
                  num_heads=doc["num_attention_heads"],
                  num_kv_heads=doc["num_key_value_heads"],
                  head_dim=doc["head_dim"],
                  moe_hidden_size=doc["moe_intermediate_size"],
                  num_experts=doc["n_routed_experts"],
                  top_k=doc["num_experts_per_tok"],
                  held=(held["first"], held["count"]),
                  rope_parameters={"latent_attention": {
                      "rope_type": "default",
                      "rope_theta": doc["rope_theta"]}},
                  rms_norm_eps=doc["rms_norm_eps"],
                  norm_topk_prob=doc["norm_topk_prob"],
                  mlp_layer_types=doc["mlp_layer_types"][:depth],
                  hidden_size=doc["intermediate_size"],
                  latent_attention=doc,
                  router={"scoring": doc["scoring_func"],
                          "selection_bias": True,
                          "scale": doc["routed_scaling_factor"],
                          "shared_hidden_size": doc["n_shared_experts"]
                          * doc["moe_intermediate_size"]},
                  num_nextn_predict_layers=doc["num_nextn_predict_layers"])
    model.initialize(init=mx.init.Normal(0.02))
    # the table at unit scale (the file's `assumed`, "init"): a token's own
    # embedding decides its routing, so the experts see an even load
    model.embedding.initialize(init=mx.init.Normal(1.0), force_reinit=True)
    model.cast(doc["dtype"])
    return model


def loss(doc):
    """Shifted cross-entropy from logits cast to float32, as the Kimi
    configuration's; while training the model also returns the MTP
    module's logits, whose loss against the token two ahead joins with
    the file's weight (`assumed`, "mtp_loss")."""
    weight = doc["mtp_loss_weight"]

    def total(out, targets):
        if not isinstance(out, tuple):
            return lm_loss(out.astype("float32"), targets).mean()
        logits, ahead = out
        return (lm_loss(logits.astype("float32"), targets).mean()
                + weight * lm_loss(ahead.astype("float32"), targets,
                                   shift=2).mean())
    return total


def optimizer(doc):
    opt = dict(doc["optimizer"])
    return mx.optimizer.create(opt.pop("name"), **opt)


def batch(doc, traffic, seed):
    """One batch of uniform random tokens over the held rows of the
    vocabulary, made on the device; a sequence is its own target (the loss
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
    pairs of every head."""
    if kind != "latent_attention":
        return 0
    pairs = seq * (seq + 1) // 2
    return (2 * pairs * doc["num_attention_heads"]
            * (doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
               + doc["v_head_dim"]))


def flops_per_sample(doc, traffic, live_rows=None):
    """Forward and backward (3 x forward) of one sequence, 2 operations to
    a multiply-add. Every layer and the MTP block: the query's two
    projections through its rank, the key/value down and up projections,
    the output, attention over the causal pairs; the dense feed-forward; an
    expert layer: the router over all 256, the shared expert on every
    token, the held experts on the rows that are live (`live_rows`, one
    count an EXPERT layer, the MTP block's last, as the program counted
    them; else an even share, tokens x 8 / 32); the head over the held
    rows, once for the main stack and once for the MTP module, and the
    MTP's projection from 2 x hidden. Norms, rotations, softmax, the
    dispatch and Adam are not counted."""
    seq, depth = traffic["seq"], doc["num_hidden_layers"]
    d, heads = doc["hidden_size"], doc["num_attention_heads"]
    mtp = doc["num_nextn_predict_layers"]
    mlps = doc["mlp_layer_types"][:depth] + ["sparse"] * mtp
    if live_rows is None:
        share = (doc["num_experts_per_tok"]
                 * doc["num_experts_held"]["count"] / doc["n_routed_experts"])
        live_rows = [seq * traffic["batch"] * share] * mlps.count("sparse")
    live_rows = iter(live_rows)
    qk = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
    latent = (2 * seq * (d * doc["q_lora_rank"]
                         + doc["q_lora_rank"] * heads * qk
                         + d * (doc["kv_lora_rank"] + doc["qk_rope_head_dim"])
                         + doc["kv_lora_rank"] * heads
                         * (doc["qk_nope_head_dim"] + doc["v_head_dim"])
                         + heads * doc["v_head_dim"] * d)
              + attention_flops(doc, seq, "latent_attention"))
    total = ((1 + mtp) * 2 * seq * d * doc["vocab_rows_held"]
             + mtp * 2 * seq * 2 * d * d)
    for mlp in mlps:
        total += latent
        if mlp == "dense":
            total += 3 * 2 * seq * d * doc["intermediate_size"]
        else:
            total += (2 * seq * d * doc["n_routed_experts"]
                      + 3 * 2 * seq * d * doc["n_shared_experts"]
                      * doc["moe_intermediate_size"]
                      + expert_flops(doc, next(live_rows) / traffic["batch"]))
    return 3 * total
