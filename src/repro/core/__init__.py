"""Core contribution of the paper: memory-constrained contrastive training
for dual-encoder retrieval (ContAccum) plus the baselines it is compared to.
"""

from repro.core.infonce import info_nce, in_batch_loss, extended_loss, similarity_logits, InfoNCEOutput
from repro.core.memory_bank import (
    BankState, init_bank, push, push_pair, clear, n_valid, ordered,
    aligned_valid, capacity, columns_view, shard_push, shard_push_pair,
    bank_spec,
)
from repro.core.loss import (
    contrastive_loss, contrastive_step_loss, LossAux,
    ExtraColumns, ExtraRows, bank_extra_columns, bank_extra_rows,
    sharded_bank_extra_columns, sharded_bank_extra_rows,
    LossBackend, DenseLossBackend, FusedLossBackend, LOSS_BACKENDS,
    resolve_loss_backend,
)
from repro.core.dist import DistCtx
from repro.core.precision import (
    PRECISION_PRESETS,
    PrecisionPolicy,
    bank_bytes_per_device,
    resolve_precision,
)
from repro.core.step_program import (
    COMPOSITIONS,
    SOURCES,
    STRATEGIES,
    BackpropStrategy,
    NegativeSource,
    StepProgram,
    available_methods,
    build_step_program,
    method_composition,
    method_needs_mesh,
    method_uses_banks,
    resolve_composition,
)
from repro.core.types import (
    ContrastiveConfig,
    ContrastiveState,
    DualEncoder,
    RetrievalBatch,
    StepMetrics,
    chunk_tree,
    flatten_hard,
)
from repro.core.methods import (
    init_state,
    make_update_fn,
    make_dpr_update,
    make_grad_accum_update,
    make_grad_cache_update,
    make_contaccum_update,
)

__all__ = [
    "info_nce", "in_batch_loss", "extended_loss", "similarity_logits", "InfoNCEOutput",
    "BankState", "init_bank", "push", "push_pair", "clear", "n_valid", "ordered",
    "aligned_valid", "capacity", "columns_view", "shard_push", "shard_push_pair",
    "bank_spec",
    "contrastive_loss", "contrastive_step_loss", "LossAux",
    "ExtraColumns", "ExtraRows", "bank_extra_columns", "bank_extra_rows",
    "sharded_bank_extra_columns", "sharded_bank_extra_rows",
    "LossBackend", "DenseLossBackend", "FusedLossBackend", "LOSS_BACKENDS",
    "resolve_loss_backend",
    "DistCtx",
    "PRECISION_PRESETS", "PrecisionPolicy",
    "bank_bytes_per_device", "resolve_precision",
    "ContrastiveConfig", "ContrastiveState", "DualEncoder", "RetrievalBatch",
    "StepMetrics", "chunk_tree", "flatten_hard",
    "COMPOSITIONS", "SOURCES", "STRATEGIES",
    "BackpropStrategy", "NegativeSource", "StepProgram",
    "available_methods", "build_step_program", "method_composition",
    "method_needs_mesh", "method_uses_banks", "resolve_composition",
    "init_state", "make_update_fn", "make_dpr_update", "make_grad_accum_update",
    "make_grad_cache_update", "make_contaccum_update",
]
