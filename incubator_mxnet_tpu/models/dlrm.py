"""DLRM — the recsys model family (Naumov et al. 2019, arXiv:1906.00091).

Embedding bags + bottom/top MLP + pairwise dot-product feature
interaction — the canonical memory-bound, all-to-all-bound workload:
the (vocab, dim) tables dominate bytes (not FLOPs), so this is the
model family that makes the sharding/comms/memscope layers load-bearing
(docs/embedding.md).

Input convention (one record of a recsys record stream): one
float32 matrix ``(batch, dense_dim + num_tables * bag_size)`` — dense
features first, then the categorical ids FLOAT-ENCODED (a record
stream's natural carrier; exact for any vocab < 2^24). The id policy
(embedding/lookup.normalize_ids) rounds them back to int32 — the
non-integer-index path `gluon.nn.Embedding` historically got wrong.

forward(x) -> (batch, 1) click logits; pair with
:func:`dlrm_loss` (sigmoid BCE).
"""
from __future__ import annotations

import numpy as np

from .. import ndarray as nd
from ..embedding import EmbeddingBag
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.loss import SigmoidBinaryCrossEntropyLoss

__all__ = ["DLRM", "dlrm_loss", "dlrm_small", "dlrm_flops_per_sample",
           "dlrm_bytes_per_sample"]


class DLRM(HybridBlock):
    def __init__(self, num_tables=8, vocab_size=512, embed_dim=32,
                 dense_dim=13, bag_size=4, bottom_units=(64,),
                 top_units=(128, 64), dedup=True, dedup_capacity=None,
                 oor_policy="clip", prefix=None, params=None):
        super().__init__(prefix, params)
        self.num_tables = int(num_tables)
        self.vocab_size = int(vocab_size)
        self.embed_dim = int(embed_dim)
        self.dense_dim = int(dense_dim)
        self.bag_size = int(bag_size)
        self.embeddings = []
        for t in range(self.num_tables):
            bag = EmbeddingBag(vocab_size, embed_dim, mode="sum",
                               dedup=dedup, dedup_capacity=dedup_capacity,
                               oor_policy=oor_policy)
            setattr(self, f"embed{t}", bag)      # register as child
            self.embeddings.append(bag)
        self.bottom = nn.HybridSequential()
        for u in tuple(bottom_units) + (embed_dim,):
            self.bottom.add(nn.Dense(u, activation="relu"))
        self.top = nn.HybridSequential()
        for u in top_units:
            self.top.add(nn.Dense(u, activation="relu"))
        self.top.add(nn.Dense(1))
        # upper-triangle (i < j) flat indices of the (T+1, T+1) gram
        # matrix — the distinct pairwise interactions
        n = self.num_tables + 1
        self._tri = np.array([i * n + j for i in range(n)
                              for j in range(i + 1, n)], dtype=np.int32)

    def forward(self, x):
        b = x.shape[0]
        dense = nd.slice_axis(x, 1, 0, self.dense_dim)
        ids = nd.slice_axis(x, 1, self.dense_dim,
                            self.dense_dim
                            + self.num_tables * self.bag_size)
        ids = ids.reshape((b, self.num_tables, self.bag_size))
        bottom = self.bottom(dense)                       # (B, D)
        feats = [bottom]
        for t, bag in enumerate(self.embeddings):
            ids_t = nd.slice_axis(ids, 1, t, t + 1).reshape(
                (b, self.bag_size))
            feats.append(bag(ids_t))                      # (B, D)
        f = nd.stack(*feats, axis=1)                      # (B, T+1, D)
        z = nd.batch_dot(f, f, transpose_b=True)          # (B, T+1, T+1)
        n = self.num_tables + 1
        inter = nd.take(z.reshape((b, n * n)), nd.array(self._tri), axis=1)
        return self.top(nd.concat(bottom, inter, dim=1))  # (B, 1)


def dlrm_loss(logits, labels):
    """Per-sample sigmoid BCE of (B, 1) click logits vs (B,) labels —
    gluon loss convention; call .mean() for the scalar."""
    return SigmoidBinaryCrossEntropyLoss()(logits, labels.reshape(
        (labels.shape[0], 1)))


def dlrm_flops_per_sample(net: DLRM) -> float:
    """fwd+bwd MLP + interaction FLOPs per sample (3x fwd); the table
    gathers are excluded — they are bytes, not FLOPs (the roofline for
    this family is memory/comms-bound by design)."""
    d = net.embed_dim
    fwd = 0.0
    prev = net.dense_dim
    for layer in net.bottom._children.values():
        u = layer._units
        fwd += 2.0 * prev * u
        prev = u
    t1 = net.num_tables + 1
    fwd += 2.0 * t1 * t1 * d                      # pairwise gram
    prev = d + (t1 * (t1 - 1)) // 2
    for layer in net.top._children.values():
        u = layer._units
        fwd += 2.0 * prev * u
        prev = u
    return 3.0 * fwd


def dlrm_bytes_per_sample(net: DLRM, dedup_rate: float = 0.0) -> float:
    """Table bytes one sample moves: gather + backward scatter of
    ``bag*T`` rows, discounted by the measured dedup rate."""
    rows = net.num_tables * net.bag_size * (1.0 - dedup_rate)
    return 2.0 * rows * net.embed_dim * 4.0


def dlrm_small(**kwargs) -> DLRM:
    """The default config: 8 tables x 512 rows x 32 dims, 4-hot
    bags, 13 dense features (a scaled-down Criteo shape)."""
    cfg = dict(num_tables=8, vocab_size=512, embed_dim=32, dense_dim=13,
               bag_size=4, bottom_units=(64,), top_units=(128, 64))
    cfg.update(kwargs)
    return DLRM(**cfg)
