"""Gluon blocks for sharded large-table embeddings (docs/embedding.md).

:class:`ShardedEmbedding` is `gluon.nn.Embedding` re-architected for
tables that do not fit one device: the (vocab, dim) weight is annotated
``PartitionSpec('vocab', None)`` at construction, so under any mesh with
an ``mp``/``tp`` axis the existing logical axis rules
(parallel/sharding.DEFAULT_RULES) shard the rows across the model axis —
no per-callsite mesh knowledge, the same annotation path `Block.shard`
uses. The lookup goes through the dedup path (lookup.dedup_lookup) so
the one collective XLA emits for the sharded gather moves
``capacity × dim`` floats instead of ``n_ids × dim``.

:class:`EmbeddingBag` adds the recsys pooling mode: a (batch, bag) id
matrix pools (sum/mean) into one (batch, dim) vector per sample —
DLRM's per-feature multi-hot aggregation.

Defaults (all through settings.py, mxlint-governed):
``MXTPU_EMBEDDING_DEDUP`` (default on) and
``MXTPU_EMBEDDING_OOR_POLICY`` (default ``clip``) set the
construction-time defaults; explicit constructor args win.
"""
from __future__ import annotations

from ..gluon.block import HybridBlock
from ..ndarray import _apply
from . import lookup as _lookup
from . import stats as _stats

__all__ = ["ShardedEmbedding", "EmbeddingBag"]


def _default_dedup() -> bool:
    from ..settings import env_flag
    return env_flag("MXTPU_EMBEDDING_DEDUP", True)


def _default_policy() -> str:
    from ..settings import env_str
    return env_str("MXTPU_EMBEDDING_OOR_POLICY", "clip")


class ShardedEmbedding(HybridBlock):
    """Embedding whose table rides the logical ``vocab`` axis.

    forward(x): ids of any shape/carrier dtype -> ``x.shape + (dim,)``.
    ``dedup=True`` routes through unique→gather→inverse-take;
    ``dedup_capacity`` caps the static unique bound (default
    ``min(n_ids, vocab)`` — lossless). ``oor_policy`` is the shared
    id policy (lookup.normalize_ids): 'clip' or 'error'."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, dedup=None, dedup_capacity=None,
                 oor_policy=None, logical_axis="vocab", prefix=None,
                 params=None):
        super().__init__(prefix, params)
        self._input_dim = int(input_dim)
        self._output_dim = int(output_dim)
        self._dedup = _default_dedup() if dedup is None else bool(dedup)
        self._capacity = dedup_capacity
        policy = _default_policy() if oor_policy is None else oor_policy
        if policy not in _lookup.OOR_POLICIES:
            raise ValueError(f"oor_policy must be one of "
                             f"{_lookup.OOR_POLICIES}, got {policy!r}")
        self._oor_policy = policy
        self.weight = self.params.get("weight",
                                      shape=(input_dim, output_dim),
                                      dtype=dtype, init=weight_initializer)
        from jax.sharding import PartitionSpec
        self.weight._sharding = PartitionSpec(logical_axis, None)
        _stats.register_table(self)

    def _lookup_fn(self, pool=None):
        input_dim, policy = self._input_dim, self._oor_policy
        dedup, capacity = self._dedup, self._capacity

        def fn(i, w):
            out = _lookup.embed(i, w, input_dim, policy=policy,
                                dedup=dedup, capacity=capacity)
            if pool is not None:
                import jax.numpy as jnp
                out = (jnp.mean(out, axis=-2) if pool == "mean"
                       else jnp.sum(out, axis=-2))
            return out
        return fn

    def _count(self):
        from ..profiler.counters import counter
        counter("embedding.lookups", "embedding").increment()
        if self._dedup:
            counter("embedding.dedup_lookups", "embedding").increment()

    def forward(self, x):
        self._count()
        return _apply(self._lookup_fn(), [x, self.weight.data()],
                      name="sharded_embedding")


class EmbeddingBag(ShardedEmbedding):
    """Pooled embedding: (…, bag) ids -> (…,) pooled ``dim`` vectors.

    ``mode='sum'`` (default) or ``'mean'`` — pooling runs inside the
    same fused op as the lookup, after the dedup inverse-take."""

    def __init__(self, input_dim, output_dim, mode="sum", **kwargs):
        if mode not in ("sum", "mean"):
            raise ValueError(f"mode must be 'sum' or 'mean', got {mode!r}")
        super().__init__(input_dim, output_dim, **kwargs)
        self._mode = mode

    def forward(self, x):
        self._count()
        return _apply(self._lookup_fn(pool=self._mode),
                      [x, self.weight.data()], name="embedding_bag")
