"""Decoder-only language model of the current sparse-expert families: RMS
norm, a head of its own or the embedding table's, and layer by layer one of
five sequence mixers (window or full attention over grouped key/value heads
with rotary positions; gated delta-rule linear attention; latent attention,
with or without a query rank and rotated positions; attention inside a
compressed latent with convolutions) and one of two feed-forwards (sparse
experts, with the routers of the three families and a shared expert; a
dense gated one), and optionally a multi-token-prediction module — built
from a published `config.json`'s own keys (`layer_types`,
`mlp_layer_types`, `rope_parameters`, `sliding_window`,
`tie_word_embeddings`, `num_nextn_predict_layers` and the widths).

A holder of an expert-parallel deployment builds the model with its share:
`held=(first, count)` of every layer's experts (gluon.nn.SparseExperts
computes their part of the result and no other) and the rows of the
vocabulary it holds as `vocab_size`. Training runs through
`parallel.FusedTrainStep(net, loss, optimizer)` like any other model.

forward(tokens): (B, L) int ids -> (B, L, vocab_size) logits (with an MTP
module, while training, a pair of them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd, ops
from .. import ndarray as nd
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..initializer import Initializer
from ..ops._raw import rope_frequencies

__all__ = ["MoeLM", "MoeLMCell", "MTP", "GroupedQueryAttentionCell",
           "LinearAttentionCell", "LatentAttentionCell",
           "CompressedAttentionCell"]


def _dense(out_units, in_units, weight_initializer):
    """A projection without bias over the last axis."""
    return nn.Dense(out_units, flatten=False, in_units=in_units,
                    use_bias=False, weight_initializer=weight_initializer)


class GroupedQueryAttentionCell(HybridBlock):
    """Causal self-attention with separate q / k / v / output projections
    (no bias), `num_kv_heads` key/value heads shared by groups of query
    heads, rotary positions on q and k, and optionally a window: a token
    sees the `window` tokens up to itself.

    `rope` is one section of a published `rope_parameters` (`rope_type`
    "default" or "yarn", `rope_theta`, ...: ops/_raw.py `rope_frequencies`),
    or None for no positions at all."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope=None,
                 window=None, weight_initializer=None, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        self._num_heads = num_heads
        self._num_kv_heads = num_kv_heads
        self._window = window
        self._rope = (None if rope is None
                      else rope_frequencies(head_dim, **rope))
        init = weight_initializer
        self.q = _dense(num_heads * head_dim, units, init)
        self.k = _dense(num_kv_heads * head_dim, units, init)
        self.v = _dense(num_kv_heads * head_dim, units, init)
        self.proj = _dense(units, num_heads * head_dim, init)

    def forward(self, x):
        q, k, v = self.q(x), self.k(x), self.v(x)
        if self._rope is not None:
            inv_freq, factor = self._rope
            q = ops.rope(q, inv_freq, self._num_heads, factor)
            k = ops.rope(k, inv_freq, self._num_kv_heads, factor)
        out = ops.multihead_attention(
            q, k, v, self._num_heads, causal=True,
            num_kv_heads=self._num_kv_heads, window=self._window)
        return self.proj(out)


class _DecayRate(Initializer):
    """The state-space families' start for a decay: `A_log` = log U(1, 16);
    `dt_bias` = softplus^-1 of exp U(log 0.001, log 0.1)."""

    def __init__(self, low, high, log_uniform=False):
        self.low, self.high, self.log_uniform = low, high, log_uniform

    def _init(self, key, shape, dtype):
        if not self.log_uniform:
            return jnp.log(jax.random.uniform(
                key, shape, jnp.float32, self.low, self.high)).astype(dtype)
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, np.log(self.low), np.log(self.high)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)


class LinearAttentionCell(HybridBlock):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): q, k and v
    through a causal depthwise convolution of `conv_size` taps and silu, q
    and k of unit length by head, a log-decay a CHANNEL through a rank of
    `head_dim` (-exp(A_log) softplus(. + dt_bias)), a writing strength a
    head (sigmoid), the gated delta rule in chunks (ops/_raw.py
    `gated_delta_rule`), and an RMS norm by head times a sigmoid gate, also
    through a rank of `head_dim`, before the output projection. No bias, no
    positions. `A_log` starts as log U(1, 16) and `dt_bias` so that
    softplus gives exp U(log 0.001, log 0.1): the state-space families'
    convention, a memory of 1 to 1000 tokens.

    `log_decay_min` (float32, `grad_req="null"`) holds the most negative
    log-decay cumulated over any chunk of the last training step;
    `read_decay()` puts it on the profiler's counters."""

    def __init__(self, units, num_heads, head_dim, conv_size=4,
                 epsilon=1e-5, weight_initializer=None, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        self._num_heads = num_heads
        self._eps = epsilon
        init, wide = weight_initializer, num_heads * head_dim
        self.q = _dense(wide, units, init)
        self.k = _dense(wide, units, init)
        self.v = _dense(wide, units, init)
        self.decay_down = _dense(head_dim, units, init)
        self.decay_up = _dense(wide, head_dim, init)
        self.beta = _dense(num_heads, units, init)
        self.gate_down = _dense(head_dim, units, init)
        self.gate_up = _dense(wide, head_dim, init)
        self.proj = _dense(units, wide, init)
        get = self.params.get
        self.conv_q = get("conv_q", shape=(conv_size, wide), init=init)
        self.conv_k = get("conv_k", shape=(conv_size, wide), init=init)
        self.conv_v = get("conv_v", shape=(conv_size, wide), init=init)
        self.a_log = get("a_log", shape=(num_heads,),
                         init=_DecayRate(1.0, 16.0))
        self.dt_bias = get("dt_bias", shape=(wide,),
                           init=_DecayRate(1e-3, 1e-1, log_uniform=True))
        self.gamma = get("gamma", shape=(head_dim,), init="ones")
        self.log_decay_min = get("log_decay_min", shape=(1,), init="zeros",
                                 grad_req="null")
        self._chunks = 0

    def cast(self, dtype):
        # the counter stays float32: bfloat16 holds a cumulated log-decay
        # of -300 to 2 digits
        for p in self._reg_params.values():
            if p is not self.log_decay_min:
                p.cast(dtype)
        for child in self._children.values():
            child.cast(dtype)
        self._dtype = dtype

    def forward(self, x):
        # the projections' weights go to the op, which makes their products
        # again in its backward (ops/_raw.py `linear_attention`)
        out, lowest = ops.linear_attention(
            x, [block.weight.data() for block in (
                self.q, self.k, self.v, self.decay_down, self.decay_up,
                self.beta, self.gate_down, self.gate_up, self.proj)]
            + [p.data() for p in (self.conv_q, self.conv_k, self.conv_v,
                                  self.a_log, self.dt_bias, self.gamma)],
            self._num_heads, self._eps)
        self._chunks = x.shape[0] * -(-x.shape[1] // ops._raw._DELTA_CHUNK)
        if autograd.is_training():
            self.log_decay_min.update_aux(lowest._data.reshape(1))
        return out

    def read_decay(self):
        """{log_decay_min, chunks} of the last training step, set as the
        counters `linear_attention.log_decay_min` and
        `linear_attention.chunks` (chunks of the scan a sequence mixer ran:
        sequences x ceil(length / 64))."""
        from .. import profiler as _prof
        got = {"log_decay_min": float(self.log_decay_min.data().asnumpy()[0]),
               "chunks": self._chunks}
        for name, value in got.items():
            _prof.set_gauge("linear_attention." + name, value)
        return got


class LatentAttentionCell(HybridBlock):
    """Multi-head latent attention (MLA, DeepSeek-V2/V3): q = h Wq in heads
    of `nope_dim + rope_dim`, or with `q_rank` q = rms_norm(h Wq_a) Wq_b
    through that rank; [c ; k_r] = h Wkv_a, a latent of `kv_rank` and one
    part of `rope_dim` that every head's key shares; [k_n ; v] =
    rms_norm(c) Wkv_b by head; head h's key is [k_n,h ; k_r]; causal
    softmax(q k^T / sqrt(nope_dim + rope_dim)) v over values of `v_dim`,
    then Wo. No bias.

    `rope` (a section of a published `rope_parameters`) rotates the last
    `rope_dim` channels of every query head and the shared k_r by position,
    adjacent pairs together where `interleaved` (`rope_interleave`); None
    rotates nothing (`mla_use_nope`).

    The parameters keep their published shapes and order; the step reads
    the query product's weight (Wq_b, or Wq without a rank) as two: every
    head's `nope_dim` rows, then every head's `rope_dim` rows, those in
    rotate-half order (channel pairs (2j, 2j + 1) as j and j + rope_dim /
    2) where `interleaved`. So the queries come out of two products as the
    parts `ops.latent_attention` takes, q_n (B, L, H nope_dim) and q_r (B,
    L, H rope_dim), and the rotation is a plain rotate-half; k_r is taken
    apart in the rotation as before, the same order on both sides of q .
    k. The keys are never assembled: the key/value product (B, L, H
    (nope_dim + v_dim)) and k_r go to the op as they are. Op scopes:
    `latent_attention/q_down` (Wq_a and its norm), `/q_up`, `/kv_down`,
    `/rope`, `/kv_up`, `/attention`; without a rank the two query products
    are outside them."""

    def __init__(self, units, num_heads, kv_rank, nope_dim, rope_dim, v_dim,
                 epsilon=1e-5, weight_initializer=None, q_rank=None,
                 rope=None, interleaved=False, prefix=None, params=None):
        super().__init__(prefix, params)
        self._num_heads = num_heads
        self._dims = (kv_rank, nope_dim, rope_dim, v_dim)
        self._q_rank = q_rank
        self._rope = (None if rope is None
                      else rope_frequencies(rope_dim, **rope))
        self._interleaved = interleaved
        init, wide = weight_initializer, num_heads * (nope_dim + rope_dim)
        if q_rank is None:
            self.q = _dense(wide, units, init)
        else:
            self.q_down = _dense(q_rank, units, init)
            self.q_norm = nn.RMSNorm(epsilon, in_channels=q_rank)
            self.q_up = _dense(wide, q_rank, init)
        self.kv_down = _dense(kv_rank + rope_dim, units, init)
        self.kv_norm = nn.RMSNorm(epsilon, in_channels=kv_rank)
        self.kv_up = _dense(num_heads * (nope_dim + v_dim), kv_rank, init)
        self.proj = _dense(units, num_heads * v_dim, init)

    def _queries(self, x, dense):
        """(q_n, q_r): x through the rows of `dense`'s weight that give
        every head's part without positions, then through those that give
        every head's rotated part (pairs taken apart where
        `interleaved`)."""
        _, nope_dim, rope_dim, _ = self._dims
        heads = self._num_heads
        weight = dense.weight.data().reshape(heads, nope_dim + rope_dim, -1)
        w_r = weight[:, nope_dim:]
        if self._interleaved and self._rope is not None:
            w_r = w_r.reshape(heads, rope_dim // 2, 2, -1).swapaxes(1, 2)
        return tuple(ops.FullyConnected(x, w.reshape(heads * width, -1),
                                        no_bias=True, flatten=False)
                     for w, width in ((weight[:, :nope_dim], nope_dim),
                                      (w_r, rope_dim)))

    def forward(self, x):
        kv_rank, nope_dim, rope_dim, v_dim = self._dims
        heads = self._num_heads
        if self._q_rank is None:
            q_n, q_r = self._queries(x, self.q)
        with jax.named_scope("latent_attention"):
            if self._q_rank is not None:
                with jax.named_scope("q_down"):
                    q = self.q_norm(self.q_down(x))
                with jax.named_scope("q_up"):
                    q_n, q_r = self._queries(q, self.q_up)
            with jax.named_scope("kv_down"):
                down = self.kv_down(x)
                latent = self.kv_norm(down[:, :, :kv_rank])
                shared = down[:, :, kv_rank:]
            if self._rope is not None:
                inv_freq, factor = self._rope
                # q_r's pairs were taken apart by its weight's rows
                q_r = ops.rope(q_r, inv_freq, heads, factor)
                shared = ops.rope(shared, inv_freq, 1, factor,
                                  interleaved=self._interleaved)
            with jax.named_scope("kv_up"):
                kv = self.kv_up(latent)
            out = ops.latent_attention(q_n, q_r, kv, shared, heads)
        return self.proj(out)


class CompressedAttentionCell(HybridBlock):
    """Compressed convolutional attention (CCA, arXiv:2510.04476): q, k and
    v are projected DOWN to `num_heads`, `num_kv_heads` and `num_kv_heads`
    heads of `head_dim`, everything between them and the output projection
    happens at that width (ops/_raw.py `compressed_attention`: the value
    shift, a depthwise and a head-mixing causal convolution of `conv_sizes`
    taps on [q ; k], the q-k mean, unit norms with a temperature a
    key/value head, rotary positions on the first `rotary_dim` channels of
    a head, causal attention), and `proj` maps the heads back to `units`.
    No bias. `rope` is a section of a published `rope_parameters`; its
    `partial_rotary_factor` gives `rotary_dim`."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rope,
                 conv_sizes=(2, 2), weight_initializer=None, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        self._heads = (num_heads, num_kv_heads)
        rope = dict(rope)
        self._rotary_dim = int(head_dim * rope.pop("partial_rotary_factor",
                                                   1.0))
        self._rope = rope_frequencies(self._rotary_dim, **rope)
        init, every = weight_initializer, num_heads + num_kv_heads
        self.q = _dense(num_heads * head_dim, units, init)
        self.k = _dense(num_kv_heads * head_dim, units, init)
        self.v = _dense(num_kv_heads * head_dim, units, init)
        self.proj = _dense(units, num_heads * head_dim, init)
        get = self.params.get
        self.conv0 = get("conv0", shape=(conv_sizes[0], every * head_dim),
                         init=init)
        self.conv1 = get("conv1", shape=(conv_sizes[1], every, head_dim,
                                         head_dim), init=init)
        self.temp = get("temp", shape=(num_kv_heads,), init="ones")

    def forward(self, x):
        inv_freq, factor = self._rope
        out = ops.compressed_attention(
            self.q(x), self.k(x), self.v(x), self.conv0.data(),
            self.conv1.data(), self.temp.data(), inv_freq, *self._heads,
            self._rotary_dim, factor)
        return self.proj(out)


class MoeLMCell(HybridBlock):
    """Pre-norm block: x += attention(norm(x)); x += ffn(norm(x)), where the
    feed-forward is a `nn.SparseExperts` or a dense `nn.GatedFFN`. Where
    the experts' router carries a state from layer to layer
    (`SparseExperts(router_hidden_size=)`), forward(x, state) takes the
    layer before's and returns (x, this layer's)."""

    def __init__(self, attention, ffn, units, epsilon=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        self.norm1 = nn.RMSNorm(epsilon, in_channels=units)
        self.attention = attention
        self.norm2 = nn.RMSNorm(epsilon, in_channels=units)
        self.ffn = ffn

    def forward(self, x, state=None):
        x = x + self.attention(self.norm1(x))
        h = self.norm2(x)
        carried = state is not None and isinstance(self.ffn, nn.SparseExperts)
        y = self.ffn(h, state) if carried else self.ffn(h)
        if isinstance(y, tuple):
            return x + y[0], y[1]
        return x + y


class MTP(HybridBlock):
    """One multi-token-prediction depth (DeepSeek-V3, arXiv:2412.19437
    section 2.2): from the main stack's output h_i (before its final norm)
    and the embedding of token t_(i+1), h'_i = eh_proj [enorm(Emb(t_(i+1)))
    ; hnorm(h_i)], one more `block`, and the model's own head after `norm`:
    logits for token t_(i+2). The last position has no t_L; id 0 stands in,
    and causal attention keeps it from every other position. The embedding
    and the head are the model's, handed to forward, so their gradients sum
    both uses; every parameter here is shaped at construction."""

    def __init__(self, units, block, epsilon=1e-6, prefix=None, params=None):
        super().__init__(prefix, params)
        self.enorm = nn.RMSNorm(epsilon, in_channels=units)
        self.hnorm = nn.RMSNorm(epsilon, in_channels=units)
        self.eh_proj = _dense(units, 2 * units, None)
        self.block = block
        self.norm = nn.RMSNorm(epsilon, in_channels=units)

    def forward(self, h, tokens, embedding, head):
        following = nd.concat(tokens[:, 1:], nd.zeros_like(tokens[:, :1]),
                              dim=1)
        x = self.eh_proj(nd.concat(self.enorm(embedding(following)),
                                   self.hnorm(h), dim=2))
        return head(self.norm(self.block(x)))


class MoeLM(HybridBlock):
    """Token embedding (no scale, no position table), one `MoeLMCell` for
    each entry of `layer_types`, a final RMS norm and the vocabulary head:
    a `Dense` of its own, or with `tie_word_embeddings` the embedding table
    transposed (its gradient then sums both uses). With
    `num_nextn_predict_layers=1` an `MTP` module (block scope `mtp_N`) whose
    block is built like the last layer's: forward then returns (logits,
    the MTP's logits) while training, and the logits alone in predict mode,
    where the module is not run.

    `layer_types[i]` names layer i's sequence mixer: "sliding_attention" or
    "full_attention" (`GroupedQueryAttentionCell`; `rope_parameters` has a
    section for each kind in use), "linear_attention" (`LinearAttentionCell`,
    built from `linear_attention={num_heads, head_dim,
    short_conv_kernel_size}`, a published `linear_attn_config`),
    "latent_attention" (`LatentAttentionCell`, from `latent_attention=
    {kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim}` and,
    where the family has them, `q_lora_rank` and `rope_interleave`, with
    rotary positions where `rope_parameters["latent_attention"]` is given) or
    "hybrid" (`CompressedAttentionCell`, from `compressed_attention=
    {cca_time0, cca_time1}` and `rope_parameters["hybrid"]`, whose
    `partial_rotary_factor` says how much of a head is rotated).
    `mlp_layer_types[i]` names its feed-forward: "sparse" (the default for
    every layer: `nn.SparseExperts`, with `router={scoring, selection_bias,
    scale, shared_hidden_size}` where the family scores by sigmoid and adds
    a shared expert, or `router={router_hidden_size, selection_bias}` where
    the router is an MLP whose state every layer hands to the next) or
    "dense" (`nn.GatedFFN` of `hidden_size`)."""

    def __init__(self, vocab_size, layer_types, units, num_heads,
                 num_kv_heads, head_dim, moe_hidden_size, num_experts, top_k,
                 held=None, rope_parameters=None, sliding_window=None,
                 rms_norm_eps=1e-6, norm_topk_prob=True, mlp_layer_types=None,
                 hidden_size=None, linear_attention=None,
                 latent_attention=None, compressed_attention=None,
                 router=None, tie_word_embeddings=False,
                 num_nextn_predict_layers=0, prefix=None, params=None):
        super().__init__(prefix, params)
        rope_parameters = rope_parameters or {}
        mlp_layer_types = mlp_layer_types or ["sparse"] * len(layer_types)
        router = dict(router or {})
        sized = {"linear_attention": linear_attention,
                 "latent_attention": latent_attention,
                 "hybrid": compressed_attention}
        if num_nextn_predict_layers not in (0, 1):
            raise ValueError(f"num_nextn_predict_layers = "
                             f"{num_nextn_predict_layers}; MoeLM builds one "
                             f"MTP depth or none")

        def cell(i, kind, mlp):
            if kind in ("sliding_attention", "full_attention"):
                attention = GroupedQueryAttentionCell(
                    units, num_heads, num_kv_heads, head_dim,
                    rope=rope_parameters.get(kind),
                    window=(sliding_window if kind == "sliding_attention"
                            else None))
            elif kind in sized and sized[kind] is None:
                name = ("compressed_attention" if kind == "hybrid" else kind)
                raise ValueError(f"layer_types[{i}] = {kind!r} needs its "
                                 f"sizes: {name}={{...}}")
            elif kind == "linear_attention":
                attention = LinearAttentionCell(
                    units, linear_attention["num_heads"],
                    linear_attention["head_dim"],
                    linear_attention["short_conv_kernel_size"], rms_norm_eps)
            elif kind == "latent_attention":
                attention = LatentAttentionCell(
                    units, num_heads, latent_attention["kv_lora_rank"],
                    latent_attention["qk_nope_head_dim"],
                    latent_attention["qk_rope_head_dim"],
                    latent_attention["v_head_dim"], rms_norm_eps,
                    q_rank=latent_attention.get("q_lora_rank"),
                    rope=rope_parameters.get(kind),
                    interleaved=latent_attention.get("rope_interleave",
                                                     False))
            elif kind == "hybrid":
                attention = CompressedAttentionCell(
                    units, num_heads, num_kv_heads, head_dim,
                    rope_parameters[kind],
                    (compressed_attention["cca_time0"],
                     compressed_attention["cca_time1"]))
            else:
                raise ValueError(
                    f"layer_types[{i}] = {kind!r}; MoeLM builds "
                    f"'sliding_attention', 'full_attention', "
                    f"'linear_attention', 'latent_attention' and 'hybrid'")
            if mlp == "sparse":
                if "router_hidden_size" in router:
                    # the first expert layer is handed no state to average
                    router["previous"] = any(
                        isinstance(cell.ffn, nn.SparseExperts)
                        for cell in self.layers)
                ffn = nn.SparseExperts(units, moe_hidden_size, num_experts,
                                       top_k, held, norm_topk_prob, **router)
            elif mlp == "dense":
                ffn = nn.GatedFFN(units, hidden_size)
            else:
                raise ValueError(f"mlp_layer_types[{i}] = {mlp!r}")
            return MoeLMCell(attention, ffn, units, rms_norm_eps)

        self.embedding = nn.Embedding(vocab_size, units)
        self.layers = []
        for i, (kind, mlp) in enumerate(zip(layer_types, mlp_layer_types)):
            self.layers.append(self.register_child(cell(i, kind, mlp),
                                                   f"layer{i}"))
        self.norm = nn.RMSNorm(rms_norm_eps, in_channels=units)
        self.head = (None if tie_word_embeddings
                     else _dense(vocab_size, units, None))
        self.mtp = (MTP(units, cell(len(layer_types), layer_types[-1],
                                    mlp_layer_types[-1]), rms_norm_eps)
                    if num_nextn_predict_layers else None)

    def _head(self, h):
        if self.head is None:
            return nd.dot(h, self.embedding.weight.data(), transpose_b=True)
        return self.head(h)

    def forward(self, tokens):
        h = self.embedding(tokens)
        state = None        # of a router that carries one
        for layer in self.layers:
            h = layer(h) if state is None else layer(h, state)
            if isinstance(h, tuple):
                h, state = h
        logits = self._head(self.norm(h))
        if self.mtp is None or not autograd.is_training():
            return logits
        return logits, self.mtp(h, tokens, self.embedding, self._head)

    def read_load(self):
        """`nn.SparseExperts.read_load()` of every expert layer, in order of
        depth, the MTP block's last; the counters keep the last layer's."""
        cells = self.layers + ([] if self.mtp is None else [self.mtp.block])
        return [cell.ffn.read_load() for cell in cells
                if isinstance(cell.ffn, nn.SparseExperts)]

    def read_decay(self):
        """`LinearAttentionCell.read_decay()` of every linear-attention
        layer, in order of depth; the counters keep the last layer's."""
        return [layer.attention.read_decay() for layer in self.layers
                if isinstance(layer.attention, LinearAttentionCell)]
