"""The Pallas kernels' public names.

Interpret-mode selection lives in :mod:`repro.kernels.interpret`: every
kernel takes ``interpret: bool | None = None``, which resolves to the
interpreter on the CPU (the only backend with no kernel lowering) and to
the compiled kernel everywhere else. Pass ``interpret=`` explicitly to pin
a mode.
"""

from __future__ import annotations

from repro.kernels.bm25_block import bm25_block_scores
from repro.kernels.bm25_pruned import bm25_pruned_topk
from repro.kernels.dot_topk import dot_topk, dot_topk_batch
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention
from repro.kernels.topk import topk

__all__ = ["bm25_block_scores", "bm25_pruned_topk", "dot_topk",
           "dot_topk_batch", "embedding_bag", "flash_attention", "topk"]
