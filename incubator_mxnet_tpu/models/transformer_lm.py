"""Decoder-only transformer language model with KV-cache generation
(parity: the GluonNLP language-model family — gluonnlp.model.train lm
scripts — re-shaped as the modern causal-LM architecture).

TPU-first design decisions:
- Training forward is one causal pass: fused (D,3D) QKV GEMM per layer
  and the causal pallas flash-attention kernel (ops/pallas/
  flash_attention.py) — O(L) memory, no (L,L) score tensor in HBM; K and
  V of a head stay in VMEM and the key loop stops at the diagonal.
- Pre-LN blocks + final LN (the stable deep-transformer variant); the
  output head can tie to the input embedding table (tie_weights) — one
  (D,V) GEMM either way, MXU-friendly.
- Generation keeps per-layer KV caches at a STATIC max_length so the
  one-token decode step has a fixed shape: it compiles once under
  hybridize()/jit and replays for every position (the reference's
  bucketing trick, collapsed to a single bucket). Cache positions beyond
  the current step are masked, mirroring how the flash kernel's decode
  path is exercised in tests/test_pallas.py::test_flash_decode_step.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import ndarray as nd
from .. import ops
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ops import _raw
from .bert import MultiHeadAttentionCell, PositionwiseFFN

__all__ = ["TransformerLM", "TransformerLMCell", "CausalSelfAttention",
           "transformer_lm_small", "transformer_lm_base", "lm_loss"]


class CausalSelfAttention(MultiHeadAttentionCell):
    """bert.MultiHeadAttentionCell with causal masking and a KV-cache
    decode path.

    Training: full-sequence causal attention (pallas flash kernel when
    available) through the shared fused-QKV cell. Decode: ONE qkv GEMM
    per step — the new token's K/V are written into the fixed-size cache
    and its Q attends over valid (<= current) positions."""

    def forward(self, x, mask=None):
        if mask is not None:
            raise ValueError("causal attention builds its own mask")
        q, k, v = nd.split(self.qkv(x), 3, axis=-1)
        if self._ring is not None:
            # sequence-parallel long-context training: ring / ulysses
            # cores are position-aware, so causality is exact across
            # sequence shards
            out = self._ring_core(q, k, v, causal=True)
        else:
            out = ops.multihead_attention(q, k, v, self._num_heads,
                                          dropout_rate=self._dropout,
                                          causal=True)
        return self.proj(out)

    def forward_step(self, x_t, k_cache, v_cache, pos, pos_mask):
        """One decode step: x_t (B,1,D) already layer-normed; caches
        (B,max_len,D); pos the write index; pos_mask (1,1,1,max_len)
        marking positions <= pos. Returns (out (B,1,D), k_cache,
        v_cache)."""
        q, k_t, v_t = nd.split(self.qkv(x_t), 3, axis=-1)
        k_cache[:, pos:pos + 1] = k_t
        v_cache[:, pos:pos + 1] = v_t
        out = ops.multihead_attention(q, k_cache, v_cache, self._num_heads,
                                      mask=pos_mask)
        return self.proj(out), k_cache, v_cache

    def project_kv(self, x_t):
        """K,V for prefill token(s) (B,L,D) -> two (B,L,D)."""
        _, k, v = nd.split(self.qkv(x_t), 3, axis=-1)
        return k, v


class TransformerLMCell(HybridBlock):
    """Pre-LN decoder block: LN→causal-MHA→residual, LN→FFN→residual."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 weight_initializer=None, ring=None, prefix=None,
                 params=None):
        super().__init__(prefix, params)
        self.attention = CausalSelfAttention(
            units, num_heads, dropout, weight_initializer=weight_initializer,
            ring=ring)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout,
                                   weight_initializer=weight_initializer)
        self.dropout = nn.Dropout(dropout)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.ln2 = nn.LayerNorm(in_channels=units)

    def forward(self, x):
        x = x + self.dropout(self.attention(self.ln1(x)))
        return x + self.ffn(self.ln2(x))

    def forward_step(self, x_t, k_cache, v_cache, pos, pos_mask):
        a, k_cache, v_cache = self.attention.forward_step(
            self.ln1(x_t), k_cache, v_cache, pos, pos_mask)
        x_t = x_t + a
        return x_t + self.ffn(self.ln2(x_t)), k_cache, v_cache


class TransformerLM(HybridBlock):
    """Decoder-only LM: token + learned position embeddings, N pre-LN
    causal blocks, final LN, vocab head (optionally weight-tied).

    forward(inputs): (B, L) int token ids -> (B, L, vocab) logits.
    generate(...): greedy/temperature sampling with per-layer KV caches.
    """

    def __init__(self, vocab_size, num_layers=2, units=128,
                 hidden_size=512, num_heads=4, max_length=512, dropout=0.0,
                 tie_weights=True, ring=None, prefix=None, params=None):
        super().__init__(prefix, params)
        self._units = units
        self._max_length = max_length
        self._vocab_size = vocab_size
        self._tie = tie_weights
        self._ring = ring
        self.embedding = nn.Embedding(vocab_size, units)
        self.pos_embedding = nn.Embedding(max_length, units)
        self.layers = []
        for i in range(num_layers):
            cell = TransformerLMCell(units, hidden_size, num_heads, dropout,
                                     ring=ring)
            self.register_child(cell, f"layer{i}")
            self.layers.append(cell)
        self.ln_f = nn.LayerNorm(in_channels=units)
        if not tie_weights:
            self.head = nn.Dense(vocab_size, flatten=False, in_units=units)
        self.dropout = nn.Dropout(dropout)

    def _logits(self, h):
        if self._tie:
            # transpose_b (not .data().T): keeps the weight itself as the
            # op input, so symbol tracing maps it to its parameter
            # Variable and eager mode avoids materializing the transpose
            return nd.dot(h, self.embedding.weight.data(),
                          transpose_b=True)
        return self.head(h)

    def _embed(self, inputs, position_offset=0):
        if not isinstance(inputs, nd.NDArray):
            # symbol trace: positions are 0..L-1, so the first L rows of
            # the table ARE the positional embeddings — slice_like keeps
            # the length tied to the input, and an L > max_length bind
            # fails the broadcast add (a gather would silently clamp)
            if position_offset:
                raise ValueError("symbolic trace supports "
                                 "position_offset=0 only")
            pos_emb = nd.slice_like(self.pos_embedding.weight.data(),
                                    nd.swapaxes(inputs, 0, 1), axes=(0,))
            h = (self.embedding(inputs) * float(np.sqrt(self._units))
                 + pos_emb)
            return self.dropout(h)
        L = inputs.shape[1]
        if position_offset + L > self._max_length:
            raise ValueError(
                f"sequence length {position_offset + L} exceeds "
                f"max_length {self._max_length}")
        pos = nd.arange(position_offset, position_offset + L)
        h = (self.embedding(inputs) * float(np.sqrt(self._units))
             + self.pos_embedding(pos))
        return self.dropout(h)

    def forward(self, inputs):
        h = self._embed(inputs)
        for layer in self.layers:
            h = layer(h)
        return self._logits(self.ln_f(h))

    # -- KV-cache generation ---------------------------------------------
    def init_cache(self, batch_size):
        """Per-layer (k, v) caches, (B, max_length, D) zeros."""
        return [(nd.zeros((batch_size, self._max_length, self._units)),
                 nd.zeros((batch_size, self._max_length, self._units)))
                for _ in self.layers]

    def _write_cache(self, caches, h_stack, start):
        """Project K/V for positions [start, start+L) of each layer's
        INPUT activations h_stack[i] and write them into the caches."""
        new = []
        for (k_c, v_c), layer, h in zip(caches, self.layers, h_stack):
            k_t, v_t = layer.attention.project_kv(layer.ln1(h))
            k_c[:, start:start + h.shape[1]] = k_t
            v_c[:, start:start + h.shape[1]] = v_t
            new.append((k_c, v_c))
        return new

    def _step_with_cache(self, token, pos, caches):
        """Decode one token at `pos` given caches filled for [0, pos).
        Returns (logits (B, vocab), updated caches)."""
        h = self._embed(token, position_offset=pos)
        mask = (nd.arange(self._max_length) <= float(pos)).reshape(
            1, 1, 1, self._max_length)
        for i, layer in enumerate(self.layers):
            k_c, v_c = caches[i]
            h, k_c, v_c = layer.forward_step(h, k_c, v_c, pos, mask)
            caches[i] = (k_c, v_c)
        return self._logits(self.ln_f(h))[:, 0], caches

    def generate(self, prompt, max_new_tokens, temperature=0.0, seed=None):
        """Continue `prompt` (B, Lp) by max_new_tokens.

        temperature=0 is greedy argmax; >0 samples softmax(logits/T).
        Prefill runs ONE full causal pass (flash path) and fills the
        caches; each subsequent token is a fixed-shape one-step call.
        Returns (B, Lp + max_new_tokens) token ids."""
        if self._ring is not None:
            raise ValueError(
                "generate() decodes single-device; build the model without "
                "ring= for inference (sequence parallelism is a training "
                "configuration — load the same parameters into a dense "
                "model)")
        prompt = nd.array(prompt) if not isinstance(prompt, nd.NDArray) \
            else prompt
        b, lp = prompt.shape
        if lp + max_new_tokens > self._max_length:
            raise ValueError("prompt + max_new_tokens exceeds max_length")
        rng = np.random.RandomState(seed)

        # prefill: full causal pass, keeping each layer's INPUT activations
        # so the caches hold exactly what forward_step's attention sees
        h = self._embed(prompt)
        h_stack = []
        for layer in self.layers:
            h_stack.append(h)
            h = layer(h)
        logits_last = self._logits(self.ln_f(h))[:, -1]
        caches = self._write_cache(self.init_cache(b), h_stack, 0)

        out = [prompt]
        for i in range(max_new_tokens):
            if temperature > 0.0:
                p = nd.softmax(logits_last / temperature, axis=-1).asnumpy()
                p = p / p.sum(-1, keepdims=True)  # exact simplex for choice
                nxt = np.array([rng.choice(self._vocab_size, p=p[j])
                                for j in range(b)], np.int32)
            else:
                nxt = logits_last.asnumpy().argmax(-1).astype(np.int32)
            tok = nd.array(nxt[:, None])
            out.append(tok)
            if i == max_new_tokens - 1:
                break
            logits_last, caches = self._step_with_cache(
                tok, lp + i, caches)
        return nd.concat(*out, dim=1)


def transformer_lm_small(vocab_size=10000, **kwargs):
    """4-layer, 256-unit causal LM (toy scale)."""
    kwargs.setdefault("num_layers", 4)
    kwargs.setdefault("units", 256)
    kwargs.setdefault("hidden_size", 1024)
    kwargs.setdefault("num_heads", 4)
    return TransformerLM(vocab_size, **kwargs)


def transformer_lm_base(vocab_size=50257, **kwargs):
    """12-layer, 768-unit causal LM (GPT-2-base scale)."""
    kwargs.setdefault("num_layers", 12)
    kwargs.setdefault("units", 768)
    kwargs.setdefault("hidden_size", 3072)
    kwargs.setdefault("num_heads", 12)
    kwargs.setdefault("max_length", 1024)
    return TransformerLM(vocab_size, **kwargs)


def lm_loss(logits, targets, shift=1):
    """Shifted causal-LM loss: per-position CE of logits[:, :-shift] vs
    targets[:, shift:], shape (B*(L-shift),) in the logits' dtype — gluon
    loss convention; call .mean() for the scalar. `shift=2` is a
    multi-token-prediction head's: position i against token i + 2.

    The targets move, not the logits: the cross-entropy runs over the
    (B, L, V) logits as the head wrote them, each position against the
    token `shift` ahead (the last `shift` against the last tokens, losses
    never used, so their gradient is exactly zero), and the last `shift`
    columns of the small (B, L) result are dropped. Slicing and flattening
    the logits would copy all of them wherever L - shift rows do not fill
    the chip's tiles."""
    def shifted(x, t):
        ce = _raw.softmax_cross_entropy(
            x, jnp.concatenate([t[:, shift:], t[:, -shift:]], axis=1))
        return ce[:, :-shift].reshape(-1)
    return nd._apply(shifted, [logits, nd._as_nd(targets)], name="lm_loss")
