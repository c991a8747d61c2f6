"""mxtpu.embedding — sharded large-table embeddings + row-sparse updates.

The TPU-native rebuild of the reference framework's recsys machinery
(row_sparse NDArray gradients + lazy_update optimizers), re-architected
for GSPMD (docs/embedding.md):

* :mod:`.lookup` — the pure kernels: one id policy
  (``normalize_ids``: int32 + documented clip/error out-of-range
  handling, shared with `gluon.nn.Embedding`), the dedup lookup
  (unique → gather → inverse-take inside the jit, so the sharded
  table's collective scales with unique ids), and the segment-summed
  row-gradient backward.
* :mod:`.blocks` — :class:`ShardedEmbedding` / :class:`EmbeddingBag`,
  whose (vocab, dim) table is annotated on the logical ``vocab`` axis
  and shards across ``mp``/``tp`` under the standard axis rules.
* :mod:`.optimizers` — :class:`RowSparseAdaGrad` / :class:`LazyAdam`:
  scatter-update only touched rows and their per-row state, verified
  equivalent to the dense reference rule on overlapping ids
  (tests/test_embedding.py).
* :mod:`.stats` — the table census behind ``extra.embedding``
  (per-device vs replicated table bytes, dedup rate, rows
  touched/step), schema-gated by tools/trace_check.py.

``models/dlrm.py`` is the model that exercises all of it end to end.
"""
from .lookup import (OOR_POLICIES, normalize_ids, dedup_lookup,
                     dedup_capacity, segment_rowgrads, embed)
from .blocks import ShardedEmbedding, EmbeddingBag
from .optimizers import RowSparseAdaGrad, LazyAdam, adagrad_rows, adam_rows
from .stats import (register_table, observe_batch, table_stats, bench_extra,
                    reset)

__all__ = [
    "OOR_POLICIES", "normalize_ids", "dedup_lookup", "dedup_capacity",
    "segment_rowgrads", "embed",
    "ShardedEmbedding", "EmbeddingBag",
    "RowSparseAdaGrad", "LazyAdam", "adagrad_rows", "adam_rows",
    "register_table", "observe_batch", "table_stats", "bench_extra", "reset",
]
