"""Dual-encoder wrappers binding backbones to the core DualEncoder interface.

The paper's retriever is two BERTs ([CLS] pooling); the LM-retriever variant
(GTR/E5 style) wraps a causal-LM backbone with mean pooling. Two towers give
``params = {"query": ..., "passage": ...}``; a shared tower gives
``{"shared": ...}``, one backbone held once, which both sides encode with
and whose gradient sums both sides' (core/types.py ``DualEncoder``). The
gradient-norm diagnostics (Fig. 5) then give each side's contribution.
"""

from __future__ import annotations


import jax

from repro.core.types import DualEncoder
from repro.models.bert import BertConfig, bert_encode, init_bert
from repro.models.lm import LMConfig, encode_pooled, init_lm


def _as_tokens(batch):
    """Batches may be {'tokens': ..., 'mask': ...} dicts or raw token arrays."""
    if isinstance(batch, dict):
        return batch["tokens"], batch.get("mask")
    return batch, None


def make_bert_dual_encoder(
    cfg: BertConfig, *, shared: bool = False, precision=None
) -> DualEncoder:
    """``precision`` (a PrecisionPolicy or preset name, core/precision.py)
    rebinds the towers' dtypes via ``BertConfig.with_precision``: stored
    params in ``param_dtype`` (fp32 masters), activations and the emitted
    [CLS] representations in ``compute_dtype``. None keeps cfg's dtypes."""
    if precision is not None:
        cfg = cfg.with_precision(precision)

    def init(rng):
        kq, kp = jax.random.split(rng)
        if shared:
            return {"shared": init_bert(kq, cfg)}
        return {"query": init_bert(kq, cfg), "passage": init_bert(kp, cfg)}

    def encode_query(params, batch):
        tokens, mask = _as_tokens(batch)
        return bert_encode(params["shared" if shared else "query"], cfg, tokens, mask)

    def encode_passage(params, batch):
        tokens, mask = _as_tokens(batch)
        return bert_encode(params["shared" if shared else "passage"], cfg, tokens, mask)

    return DualEncoder(
        init=init,
        encode_query=encode_query,
        encode_passage=encode_passage,
        rep_dim=cfg.d_model,
        shared=shared,
    )


def make_lm_dual_encoder(
    cfg: LMConfig, *, shared: bool = True, precision=None
) -> DualEncoder:
    """LM-as-retriever: one shared causal-LM backbone (the common modern
    setup: E5-Mistral, RepLLaMA), mean pooling over valid positions.
    ``precision`` (a PrecisionPolicy or preset name) rebinds the backbone's
    dtypes via ``LMConfig.with_precision``: fp32 masters, activations and
    the pooled representations in the compute dtype. The update reads the
    expert layers' counters through ``encode_counters``."""
    if precision is not None:
        cfg = cfg.with_precision(precision)

    def init(rng):
        kq, kp = jax.random.split(rng)
        if shared:
            return {"shared": init_lm(kq, cfg)}
        return {"query": init_lm(kq, cfg), "passage": init_lm(kp, cfg)}

    def encode_counters(params, side, batch):
        tokens, mask = _as_tokens(batch)
        tower = params["shared" if shared else side]
        return encode_pooled(tower, cfg, tokens, mask)

    return DualEncoder(
        init=init,
        encode_query=lambda params, batch: encode_counters(params, "query", batch)[0],
        encode_passage=lambda params, batch: encode_counters(params, "passage", batch)[0],
        rep_dim=cfg.d_model,
        shared=shared,
        encode_counters=encode_counters,
    )
