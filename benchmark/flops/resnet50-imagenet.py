"""Model FLOPs of one ResNet training step, from the configuration's shapes.

Counted: the multiply-adds of every convolution and of the final product,
2 FLOPs each, forward once and backward twice (input and weight gradients):
3 x forward. Not counted: batch norm, ReLU, pooling, the loss, the optimizer,
and anything recomputed. (The arithmetic of paddle_tpu/flops.py
`topology_fwd_flops` for conv and fc layers, kept here so that no later PR
can move it.)
"""

STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def conv_macs(out_hw, c_out, c_in, k):
    """Multiply-adds of one image through one k x k convolution."""
    return out_hw * out_hw * c_out * c_in * k * k


def bottleneck_macs(out_hw, c_in, c, project):
    """1x1 (c_in->c), 3x3 (c->c), 1x1 (c->4c) and, where the shape changes, a
    1x1 projection (c_in->4c); every conv writes the block's output size."""
    m = conv_macs(out_hw, c, c_in, 1) + conv_macs(out_hw, c, c, 3) \
        + conv_macs(out_hw, 4 * c, c, 1)
    return m + (conv_macs(out_hw, 4 * c, c_in, 1) if project else 0)


def forward_macs_per_image(a):
    size = a["img_size"]
    macs = conv_macs(size // 2, 64, 3, 7)
    hw, c_in = size // 4, 64
    for stage, blocks in enumerate(STAGES[a["depth"]]):
        c = 64 * 2 ** stage
        if stage:
            hw //= 2
        for b in range(blocks):
            macs += bottleneck_macs(hw, c_in, c, b == 0)
            c_in = 4 * c
    return macs + 2048 * a["num_classes"]


def train_flops_per_step(a, feeds):
    """`feeds`: {feed name: shape} as the step saw them."""
    return 3 * 2 * feeds["image"][0] * forward_macs_per_image(a)
