"""Model zoo (L1): ResNet-9, FixupResNet, GPT-2, Laguna — all flax, all pure params.

The reference's models are plain ``nn.Module`` classes driven by a
``compute_loss(model, batch)`` convention (SURVEY.md §1 L1). Here every model
is a flax module whose entire state is the parameter pytree (no mutable
batch stats): norm layers default to GroupNorm / Fixup-style init precisely
because running statistics don't survive federated averaging — the same
observation that made the reference carry FixupResNet
(``CommEfficient/models/fixup_resnet.py``).
"""

from commefficient_tpu.models.resnet9 import ResNet9
from commefficient_tpu.models.fixup_resnet import FixupResNet, fixup_resnet50
from commefficient_tpu.models.gpt2 import (
    GPT2Config,
    GPT2DoubleHeads,
    gpt2_tiny_config,
)
from commefficient_tpu.models.losses import (
    softmax_cross_entropy,
    classification_loss,
    gpt2_double_heads_loss,
    block_diffusion_lm_loss,
    causal_lm_loss,
)

_LAGUNA = ("LagunaConfig", "LagunaLM", "laguna_tiny", "laguna_xs2")


def __getattr__(name):
    # Laguna brings the Pallas library's attention and grouped-matmul modules
    # with it: imported when first asked for, so the other entries never load them
    if name in _LAGUNA:
        from commefficient_tpu.models import laguna

        return getattr(laguna, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "ResNet9",
    "FixupResNet",
    "fixup_resnet50",
    "GPT2Config",
    "GPT2DoubleHeads",
    "gpt2_tiny_config",
    "LagunaConfig",
    "LagunaLM",
    "laguna_tiny",
    "laguna_xs2",
    "softmax_cross_entropy",
    "classification_loss",
    "gpt2_double_heads_loss",
    "block_diffusion_lm_loss",
    "causal_lm_loss",
]
