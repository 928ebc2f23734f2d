"""Operations the algorithm needs, from shapes, kept here so that a later PR
cannot move the yardstick; recomputed work does not count.  The Transformer
count is bench.py's (_transformer_train_flops_per_token); the ResNet count is
this file's own, from the shapes."""

from __future__ import annotations

RESNET_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def resnet_forward_macs(depth: int = 50, image_size: int = 224,
                        class_num: int = 1000) -> float:
    """Multiply-adds of one image through the convolutions and the final
    layer of a bottleneck ResNet (He et al. 2015, table 1; stride on the
    3x3), counted from the shapes: 4.09e9 for ResNet-50 at 224x224, the
    figure torchvision and the paper's table ("3.8e9 FLOPs", v1, where a
    FLOP is a multiply-add) quote.  bench.py's RESNET50_TRAIN_FLOPS_PER_IMG
    took that figure for FLOPs and is half of this file's count."""
    side = (image_size + 1) // 2                     # 7x7 stride 2
    macs = 7 * 7 * 3 * 64 * side * side
    side = (side + 1) // 2                           # 3x3 max pool stride 2
    ch_in = 64
    for stage, count in enumerate(RESNET_STAGES[depth]):
        ch = 64 * 2 ** stage
        for block in range(count):
            stride = 2 if stage > 0 and block == 0 else 1
            out = (side + stride - 1) // stride
            if ch_in != 4 * ch or stride != 1:
                macs += ch_in * 4 * ch * out * out   # projection shortcut
            macs += ch_in * ch * side * side         # 1x1
            macs += 9 * ch * ch * out * out          # 3x3, strided
            macs += ch * 4 * ch * out * out          # 1x1
            ch_in, side = 4 * ch, out
    return float(macs + ch_in * class_num)


def resnet_train_flops_per_image(depth: int = 50, image_size: int = 224,
                                 class_num: int = 1000) -> float:
    """2 FLOPs a multiply-add; training = forward + 2 x backward."""
    return 3 * 2 * resnet_forward_macs(depth, image_size, class_num)


def transformer_train_flops_per_token(d_model: int, d_inner: int,
                                      n_layer: int, seq: int,
                                      trg_vocab: int) -> float:
    """Per target position of an encoder-decoder with `seq` source and
    `seq` target positions: 6 x the matmul parameters (2 forward, 4
    backward) plus the attention score and value matmuls (4*seq*d forward
    per position and attention block, three blocks per layer pair, x 3 for
    training; the causal half is not taken off)."""
    d, di, L = d_model, d_inner, n_layer
    matmul_params = (L * (4 * d * d + 2 * d * di)      # encoder
                     + L * (8 * d * d + 2 * d * di)    # decoder, self+cross
                     + d * trg_vocab)                  # output projection
    attn = 3 * 4 * seq * d * 3 * L
    return 6.0 * matmul_params + attn
