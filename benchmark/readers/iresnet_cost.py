"""Multiply-adds of one face through an IResNet, counted from the widths
and depths a configuration's file states (its ``embedder`` entry), with
nothing imported from the program: a 3x3 stem at stride 1; per stage a
first block of conv3x3(in -> out, stride 1), conv3x3(out -> out, stride
2) and a 1x1 stride-2 shortcut, then blocks of two 3x3 convolutions; a
linear head over the flattened last map. Norms, PReLUs and the adds are
left out, as the published count (6.31 G for r50 at 112x112) leaves them.
"""


def multiply_adds(net):
    h, w = (int(v) for v in net["input_size"])
    ch = int(net["stem_features"])
    total = 9 * int(net["in_channels"]) * ch * h * w
    for feats, blocks in zip(net["stage_features"], net["stage_blocks"]):
        feats = int(feats)
        total += 9 * ch * feats * h * w            # first block, conv1 at the stage's input extent
        h, w = (h - 1) // 2 + 1, (w - 1) // 2 + 1  # 3x3 pad 1 stride 2, 1x1 pad 0 stride 2
        total += (9 * feats + ch) * feats * h * w  # its strided conv2 and its shortcut
        total += (int(blocks) - 1) * 2 * 9 * feats * feats * h * w
        ch = feats
    return total + h * w * ch * int(net["embed_dim"])
