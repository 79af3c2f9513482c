"""Architecture registry: ``get_arch(arch_id)`` -> ArchSpec.

One module per assigned architecture (exact public-literature configs) plus
the paper's own dual-encoder (dpr-bert-base).
"""

from repro.configs.base import ArchSpec, ShapeCell, get_arch, register, list_archs

# import for registration side effects
from repro.configs import (  # noqa: F401
    dpr_bert_base,
    stablelm_3b,
    internlm2_1p8b,
    qwen1p5_110b,
    qwen3_moe_235b,
    olmoe_1b_7b,
    schnet,
    dcn_v2,
    deepfm,
    dlrm_mlperf,
    dlrm_rm2,
    moonlight_16b_a3b,
)

TINY_BERT = "bert-tiny"


def bert_tower(arch_id: str):
    """The BertConfig the drivers' ``--arch`` names: ``bert-tiny`` (the CPU
    default, ``models.bert.tiny_bert``) or any registered bert-family arch
    (e.g. ``dpr-bert-base``, the paper's bert-base-uncased towers)."""
    from repro.models.bert import tiny_bert

    if arch_id == TINY_BERT:
        return tiny_bert()
    spec = get_arch(arch_id)
    if spec.family != "bert":
        raise ValueError(f"--arch {arch_id!r} is a {spec.family} arch, not a bert tower")
    return spec.model_cfg


def bert_archs():
    return [TINY_BERT] + [a for a in list_archs() if get_arch(a).family == "bert"]


def lm_tower_archs():
    """Registered LM backbones built as retriever towers (no LM head)."""
    return [a for a in list_archs()
            if get_arch(a).family == "lm" and not get_arch(a).model_cfg.head]


def tower_archs():
    """Every ``--arch`` a training driver can build a dual encoder from."""
    return bert_archs() + lm_tower_archs()


__all__ = [
    "ArchSpec", "ShapeCell", "get_arch", "register", "list_archs",
    "TINY_BERT", "bert_tower", "bert_archs", "lm_tower_archs", "tower_archs",
]
