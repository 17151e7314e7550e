"""Operations the ``resnet50`` configuration needs, from its shapes."""

from __future__ import annotations


def forward_macs_per_example(cfg) -> int:
    """Multiply-accumulates of one image's forward pass: every convolution
    and the classifier.  BatchNorm, ReLU, pooling and the softmax are not
    matrix work and are not counted.  SAME padding: a stride-s convolution
    or pool leaves ceil(n / s)."""
    w = cfg["stem_filters"]
    spatial = -(-cfg["image_size"] // 2)
    macs = spatial * spatial * 7 * 7 * 3 * w
    spatial = -(-spatial // 2)                 # the 3x3/2 max pool
    cin, f = w, w
    for si, blocks in enumerate(cfg["stage_blocks"]):
        for bi in range(blocks):
            # the stride sits on the block's first 1x1 (ResNet v1)
            spatial = -(-spatial // (2 if (si > 0 and bi == 0) else 1))
            per_pixel = cin * f + 9 * f * f + f * 4 * f
            if bi == 0:
                per_pixel += cin * 4 * f       # the projection shortcut
            macs += spatial * spatial * per_pixel
            cin = 4 * f
        f *= 2
    return macs + cin * cfg["num_classes"]


def train_flops_per_example(cfg) -> float:
    """Forward and backward: two operations a multiply-accumulate, and the
    backward pass twice the forward's (one product for the activations'
    gradient, one for the weights')."""
    return 3.0 * 2.0 * forward_macs_per_example(cfg)
