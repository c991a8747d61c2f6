"""BERT (parity: GluonNLP scripts/bert + reference src/operator/contrib/
transformer.cc interleaved_matmul ops; model API mirrors
gluonnlp.model.bert.BERTModel / get_bert_model).

TPU-first design decisions:
- QKV projection is ONE fused (D, 3D) matmul (the reference's
  interleaved_matmul_selfatt_qk trick, done here at the layer level) so the
  MXU sees a single large GEMM per attention block.
- The attention core dispatches to the pallas flash-attention kernel when no
  padding mask is needed (ops/pallas/flash_attention.py): O(L) memory,
  scores never hit HBM; at 128 or 512 tokens a head is one or two grid
  steps, its head size of 64 unpadded. With a valid_length mask it falls
  back to the fused XLA softmax path.
- Everything is a HybridBlock: `hybridize()` compiles the whole encoder into
  one XLA computation; FusedTrainStep fuses fwd+bwd+AdamW into one program.
- Long sequences: two exact sequence-parallel cores via
  ring=(mesh, axis[, scheme]): scheme "ring" (KV rotation,
  parallel/ring_attention.py, O(L/n) memory) or "ulysses" (all-to-all
  head sharding, parallel/ulysses.py, needs num_heads % n == 0).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..ndarray import NDArray, _apply
from .. import ndarray as nd
from .. import ops
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.loss import Loss

__all__ = ["BERTModel", "BERTEncoder", "BERTEncoderCell", "PositionwiseFFN",
           "MultiHeadAttentionCell", "BERTForPretrain", "BERTPretrainLoss",
           "get_bert_model", "bert_12_768_12", "bert_24_1024_16"]


class MultiHeadAttentionCell(HybridBlock):
    """Self-attention with fused QKV projection.

    One (D,3D) GEMM -> split heads -> flash attention (pallas) or masked
    softmax -> output projection. Mirrors gluonnlp.model.attention_cell.
    MultiHeadAttentionCell but restructured for the MXU.
    """

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True,
                 weight_initializer=None, ring=None, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._dropout = dropout
        # (mesh, axis) or (mesh, axis, "ring"|"ulysses"):
        # sequence-parallel attention core scheme
        self._ring = ring
        if ring is not None:
            scheme = ring[2] if len(ring) > 2 else "ring"
            if scheme not in ("ring", "ulysses"):
                raise ValueError(f"unknown sequence-parallel scheme "
                                 f"{scheme!r}; choose 'ring' or 'ulysses'")
            if scheme == "ulysses":
                n = ring[0].shape[ring[1]]
                if num_heads % n:
                    raise ValueError(
                        f"ulysses shards heads: num_heads={num_heads} must "
                        f"divide by mesh axis {ring[1]}={n} (use 'ring' "
                        f"otherwise)")
        if ring is not None and dropout > 0.0:
            import warnings
            warnings.warn(
                "ring attention applies no attention-weight dropout (flash-"
                "style kernels keep weights in registers); residual/FFN "
                "dropout still applies", stacklevel=3)
        self.qkv = nn.Dense(3 * units, flatten=False, in_units=units,
                            use_bias=use_bias,
                            weight_initializer=weight_initializer)
        self.proj = nn.Dense(units, flatten=False, in_units=units,
                             use_bias=use_bias,
                             weight_initializer=weight_initializer)

    def forward(self, x, mask=None):
        q, k, v = nd.split(self.qkv(x), 3, axis=-1)
        if self._ring is not None:
            if mask is not None:
                raise ValueError("ring attention path needs full sequences "
                                 "(valid_length mask unsupported); pad to "
                                 "max_length instead")
            out = self._ring_core(q, k, v)
        else:
            out = ops.multihead_attention(q, k, v, self._num_heads, mask,
                                          self._dropout)
        return self.proj(out)

    def _ring_core(self, q, k, v, causal=False):
        """Long-context core: sequence dim sharded over the mesh 'sp' axis.
        scheme "ring" rotates KV blocks over ICI
        (parallel/ring_attention.py); "ulysses" trades the sequence shard
        for a head shard with two all-to-alls (parallel/ulysses.py). Both
        cores are position-aware, so causal masking stays exact across
        sequence shards (the causal-LM subclass passes causal=True)."""
        from ..parallel import ring_attention, ulysses_attention
        mesh, axis = self._ring[0], self._ring[1]
        scheme = self._ring[2] if len(self._ring) > 2 else "ring"
        core = {"ring": ring_attention,
                "ulysses": ulysses_attention}[scheme]
        heads = self._num_heads

        def f(qr, kr, vr):
            b, L, d = qr.shape
            hd = d // heads

            def split(t):
                return t.reshape(b, L, heads, hd).transpose(0, 2, 1, 3)

            o = core(split(qr), split(kr), split(vr), mesh, axis,
                     causal=causal)
            return o.transpose(0, 2, 1, 3).reshape(b, L, d)
        return _apply(f, [q, k, v], name=scheme + "_self_attention")


class PositionwiseFFN(HybridBlock):
    """FFN sublayer (gluonnlp.model.transformer.PositionwiseFFN)."""

    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix, params)
        self.ffn_1 = nn.Dense(hidden_size, flatten=False, in_units=units,
                              weight_initializer=weight_initializer)
        self.activation = nn.GELU()if activation == "gelu" else \
            nn.Activation(activation)
        self.ffn_2 = nn.Dense(units, flatten=False, in_units=hidden_size,
                              weight_initializer=weight_initializer)
        self.dropout = nn.Dropout(dropout)

    def forward(self, x):
        return self.dropout(self.ffn_2(self.activation(self.ffn_1(x))))


class BERTEncoderCell(HybridBlock):
    """One transformer layer: MHA + Add&LN, FFN + Add&LN.

    `pre_norm=False` is BERT's post-LN (reference default); True gives the
    pre-LN variant used for deep/stable training.
    """

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 pre_norm=False, layer_norm_eps=1e-12,
                 weight_initializer=None, ring=None, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        self._pre_norm = pre_norm
        self.attention = MultiHeadAttentionCell(
            units, num_heads, dropout, weight_initializer=weight_initializer,
            ring=ring)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                   weight_initializer=weight_initializer)
        self.dropout = nn.Dropout(dropout)
        self.ln1 = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.ln2 = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)

    def forward(self, x, mask=None):
        if self._pre_norm:
            x = x + self.dropout(self.attention(self.ln1(x), mask))
            return x + self.ffn(self.ln2(x))
        x = self.ln1(x + self.dropout(self.attention(x, mask)))
        return self.ln2(x + self.ffn(x))


class BERTEncoder(HybridBlock):
    """Stack of BERTEncoderCells (gluonnlp.model.BERTEncoder)."""

    def __init__(self, num_layers, units, hidden_size, num_heads,
                 max_length=512, dropout=0.0, pre_norm=False,
                 layer_norm_eps=1e-12, weight_initializer=None, ring=None,
                 prefix=None, params=None):
        super().__init__(prefix, params)
        self._units = units
        self._max_length = max_length
        self.position_weight = self.params.get(
            "position_weight", shape=(max_length, units), init="normal")
        self.dropout = nn.Dropout(dropout)
        self.ln = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.cells = nn.HybridSequential()
        for _ in range(num_layers):
            self.cells.add(BERTEncoderCell(
                units, hidden_size, num_heads, dropout, pre_norm,
                layer_norm_eps, weight_initializer, ring=ring))

    def forward(self, x, mask=None):
        from ..ndarray import _symbolic
        pos = self.position_weight.data()
        if _symbolic(x):
            # symbol trace has no python shape: the first L rows of the
            # table are the positional embeddings; slice_like ties the
            # length to the input and an over-length bind fails the
            # broadcast instead of silently clamping
            x = x + nd.slice_like(pos, nd.swapaxes(x, 0, 1), axes=(0,))
        else:
            # eager/hybridized: static row slice (no transposed copy of
            # the activations just to read a shape)
            seq_len = x.shape[1]
            x = _apply(lambda xr, pr: xr + pr[:seq_len][None, :, :],
                       [x, pos], name="add_position_embed")
        x = self.dropout(self.ln(x))
        for cell in self.cells:
            x = cell(x, mask)
        return x


def _length_mask(valid_length, seq_len):
    """(B,) valid lengths -> (B, 1, 1, L) boolean attention mask."""
    def f(vl):
        ar = jnp.arange(seq_len)
        return (ar[None, :] < vl[:, None].astype(jnp.int32))[:, None, None, :]
    return _apply(f, [valid_length], name="length_mask")


class BERTModel(HybridBlock):
    """Embeddings + encoder + pooler (gluonnlp.model.bert.BERTModel).

    forward(inputs, token_types, valid_length=None) ->
        (sequence_output (B,L,D), pooled_output (B,D))
    """

    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, max_length=512, vocab_size=30522,
                 token_type_vocab_size=2, dropout=0.1, pre_norm=False,
                 use_pooler=True, layer_norm_eps=1e-12, ring=None,
                 prefix=None, params=None):
        """ring=(mesh, 'sp') switches every attention core to sequence-
        parallel ring attention for long-context training: activations stay
        sharded (B, L/sp, D) per device, only KV blocks move over ICI."""
        super().__init__(prefix, params)
        self._units = units
        self.word_embed = nn.Embedding(vocab_size, units)
        self.token_type_embed = nn.Embedding(token_type_vocab_size, units)
        self.encoder = BERTEncoder(num_layers, units, hidden_size, num_heads,
                                   max_length, dropout, pre_norm,
                                   layer_norm_eps, ring=ring)
        self.pooler = (nn.Dense(units, flatten=False, in_units=units,
                                activation="tanh") if use_pooler else None)

    def forward(self, inputs, token_types=None, valid_length=None):
        x = self.word_embed(inputs)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        mask = None
        if valid_length is not None:
            from ..ndarray import _symbolic
            if _symbolic(inputs):
                raise ValueError(
                    "symbol tracing of BERTModel does not support "
                    "valid_length (the mask needs a static length); pad "
                    "to max_length and trace without it")
            mask = _length_mask(valid_length, inputs.shape[1])
        seq = self.encoder(x, mask)
        if self.pooler is None:
            return seq
        pooled = self.pooler(seq[:, 0, :])
        return seq, pooled


class BERTForPretrain(HybridBlock):
    """MLM + NSP heads on a BERTModel (gluonnlp scripts/bert/pretraining).

    forward(inputs, token_types, valid_length, masked_positions) ->
        (mlm_scores (B,M,V), nsp_scores (B,2))
    The MLM decoder ties the word-embedding matrix (reference behaviour).
    """

    def __init__(self, bert: BERTModel, vocab_size, prefix=None, params=None):
        super().__init__(prefix, params)
        if bert.pooler is None:
            raise ValueError("BERTForPretrain needs a BERTModel built with "
                             "use_pooler=True (the NSP head reads the pooled "
                             "[CLS] output)")
        self.bert = bert
        self._vocab_size = vocab_size
        units = bert._units
        self.mlm_transform = nn.Dense(units, flatten=False, in_units=units)
        self.mlm_ln = nn.LayerNorm(epsilon=1e-12, in_channels=units)
        self.mlm_bias = self.params.get("mlm_bias", shape=(vocab_size,),
                                        init="zeros")
        self.nsp_classifier = nn.Dense(2, in_units=units)

    def forward(self, inputs, token_types, valid_length, masked_positions):
        seq, pooled = self.bert(inputs, token_types, valid_length)
        # gather the masked positions: (B, L, D) -> (B, M, D)
        h = _apply(lambda s, p: jnp.take_along_axis(
            s, p.astype(jnp.int32)[:, :, None], axis=1),
            [seq, masked_positions], name="gather_masked")
        h = self.mlm_ln(nd.gelu(self.mlm_transform(h)))
        embed_w = self.bert.word_embed.weight.data()
        mlm = _apply(lambda hr, wr, br: hr @ wr.T + br,
                     [h, embed_w, self.mlm_bias.data()], name="mlm_decoder")
        nsp = self.nsp_classifier(pooled)
        return mlm, nsp


class BERTPretrainLoss(Loss):
    """MLM CE (over masked positions, ignoring pads labelled -1) + NSP CE."""

    def forward(self, mlm_scores, nsp_scores, masked_labels, nsp_labels,
                sample_weight=None):
        import jax

        def f(ms, ml, ns, nl):
            valid = (ml >= 0)
            labels = jnp.maximum(ml, 0)
            logp = jax.nn.log_softmax(ms.astype(jnp.float32), axis=-1)
            mlm_nll = -jnp.take_along_axis(
                logp, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]
            denom = jnp.maximum(valid.sum(), 1)
            mlm_loss = jnp.where(valid, mlm_nll, 0.0).sum() / denom
            nlogp = jax.nn.log_softmax(ns.astype(jnp.float32), axis=-1)
            nsp_loss = -jnp.take_along_axis(
                nlogp, nl.astype(jnp.int32)[:, None], axis=-1).mean()
            return mlm_loss + nsp_loss
        return _apply(f, [mlm_scores, masked_labels, nsp_scores, nsp_labels],
                      name="bert_pretrain_loss")


_BERT_CONFIGS = {
    # name: (num_layers, units, hidden_size, num_heads)
    "bert_12_768_12": (12, 768, 3072, 12),     # BERT-base
    "bert_24_1024_16": (24, 1024, 4096, 16),   # BERT-large
}


def get_bert_model(model_name="bert_12_768_12", vocab_size=30522,
                   max_length=512, dropout=0.1, pre_norm=False,
                   use_pooler=True, **kwargs):
    num_layers, units, hidden, heads = _BERT_CONFIGS[model_name]
    return BERTModel(num_layers, units, hidden, heads, max_length,
                     vocab_size, dropout=dropout, pre_norm=pre_norm,
                     use_pooler=use_pooler, **kwargs)


def bert_12_768_12(**kwargs):
    return get_bert_model("bert_12_768_12", **kwargs)


def bert_24_1024_16(**kwargs):
    return get_bert_model("bert_24_1024_16", **kwargs)
