"""Decoder-only language model of the current sparse-expert families: RMS
norm, rotary positions, grouped key/value heads, layers that mix window and
full attention, a sparse-expert feed-forward in every layer and an untied
head — built from a published `config.json`'s own keys (`layer_types`,
`rope_parameters`, `sliding_window` and the widths).

A holder of an expert-parallel deployment builds the model with its share:
`held=(first, count)` of every layer's experts (gluon.nn.SparseExperts
computes their part of the result and no other) and the rows of the
vocabulary it holds as `vocab_size`. Training runs through
`parallel.FusedTrainStep(net, loss, optimizer)` like any other model.

forward(tokens): (B, L) int ids -> (B, L, vocab_size) logits.
"""
from __future__ import annotations

from .. import ops
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ops._raw import rope_frequencies

__all__ = ["MoeLM", "MoeLMCell", "GroupedQueryAttentionCell"]


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


class MoeLMCell(HybridBlock):
    """Pre-norm block: x += attention(norm(x)); x += ffn(norm(x)), where the
    feed-forward is a `nn.SparseExperts`."""

    def __init__(self, attention, ffn, units, epsilon=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        self.norm1 = nn.RMSNorm(epsilon, in_channels=units)
        self.attention = attention
        self.norm2 = nn.RMSNorm(epsilon, in_channels=units)
        self.ffn = ffn

    def forward(self, x):
        x = x + self.attention(self.norm1(x))
        return x + self.ffn(self.norm2(x))


class MoeLM(HybridBlock):
    """Token embedding (no scale, no position table), one `MoeLMCell` for
    each entry of `layer_types` ("sliding_attention" or "full_attention";
    `rope_parameters` has a section for each kind in use), a final RMS norm
    and the untied vocabulary head."""

    def __init__(self, vocab_size, layer_types, units, num_heads,
                 num_kv_heads, head_dim, moe_hidden_size, num_experts, top_k,
                 held=None, rope_parameters=None, sliding_window=None,
                 rms_norm_eps=1e-6, norm_topk_prob=True, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        rope_parameters = rope_parameters or {}
        self.embedding = nn.Embedding(vocab_size, units)
        self.layers = []
        for i, kind in enumerate(layer_types):
            if kind not in ("sliding_attention", "full_attention"):
                raise ValueError(f"layer_types[{i}] = {kind!r}")
            attention = GroupedQueryAttentionCell(
                units, num_heads, num_kv_heads, head_dim,
                rope=rope_parameters.get(kind),
                window=sliding_window if kind == "sliding_attention" else None)
            ffn = nn.SparseExperts(units, moe_hidden_size, num_experts, top_k,
                                   held, norm_topk_prob)
            cell = MoeLMCell(attention, ffn, units, rms_norm_eps)
            self.register_child(cell, f"layer{i}")
            self.layers.append(cell)
        self.norm = nn.RMSNorm(rms_norm_eps, in_channels=units)
        self.head = _dense(vocab_size, units, None)

    def forward(self, tokens):
        h = self.embedding(tokens)
        for layer in self.layers:
            h = layer(h)
        return self.head(self.norm(h))

    def read_load(self):
        """`nn.SparseExperts.read_load()` of every layer, in order of depth;
        the counters keep the last layer's."""
        return [layer.ffn.read_load() for layer in self.layers]
