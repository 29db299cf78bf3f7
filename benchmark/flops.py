"""Operations the forward and backward passes require, per unit of work.
Compression, clipping and anything recomputed do not count."""

from __future__ import annotations


def resnet9_train_flops_per_sample(**_):
    """ResNet-9 at 32x32: 3x3 convs at 2*H*W*Cin*Cout*9 each plus the head,
    backward taken as twice the forward."""
    convs = [(32, 3, 64), (32, 64, 128), (16, 128, 128), (16, 128, 128),
             (16, 128, 256), (8, 256, 512), (4, 512, 512), (4, 512, 512)]
    fwd = sum(2 * h * h * cin * cout * 9 for h, cin, cout in convs)
    fwd += 2 * 512 * 10
    return 3.0 * fwd


def gpt2_flops_per_token(*, n_params, n_layer, n_embd, seq, **_):
    """``6 D + 12 L T E`` per padded token position: the tied LM head's
    matmul stands in for the embedding rows that do none; the second term
    is the QK^T and AV work, forward and backward."""
    return 6.0 * n_params + 12.0 * n_layer * seq * n_embd
