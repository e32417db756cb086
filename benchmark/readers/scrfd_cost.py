"""Multiply-adds of one frame through an SCRFD detector, counted from the
widths and depths a configuration's file states (its ``detector`` entry),
with nothing imported from the program: a deep stem of 3x3 convolutions (the
first at stride 2) and a stride-2 max-pool; per stage a first block of
conv3x3(in -> out, stride 2 from the second stage on), conv3x3(out -> out)
and, where the stage halves the extent or changes the width, a 1x1 shortcut
after a 2x2 average pool, then blocks of two 3x3 convolutions; a
path-aggregation neck over the last three stages (1x1 laterals, a 3x3
convolution a level, a strided 3x3 and a 3x3 more on the two coarser levels);
a head of ``head_convs`` 3x3 convolutions and the two 3x3 output convolutions
(A logits and 4A distances a cell) on every level. Norms, pools and adds are
left out, as the published count (9.98 GFLOPs for the 10GF at 640x480)
leaves them.
"""


def multiply_adds(net):
    h, w = (int(v) // 2 for v in net["input_size"])
    ch, total = int(net["in_channels"]), 0
    for feats in net["stem_features"]:
        total += 9 * ch * int(feats) * h * w
        ch = int(feats)
    h, w = h // 2, w // 2  # the max-pool
    levels = []
    for stage, (feats, blocks) in enumerate(zip(net["stage_features"],
                                                net["stage_blocks"])):
        feats = int(feats)
        if stage:
            h, w = h // 2, w // 2
        total += 9 * ch * feats * h * w + 9 * feats * feats * h * w  # first block
        if stage or ch != feats:
            total += ch * feats * h * w                              # its shortcut
        total += (int(blocks) - 1) * 2 * 9 * feats * feats * h * w
        ch = feats
        levels.append((feats, h * w))
    levels = levels[1:]
    c = int(net["neck_features"])
    cells = sum(hw for _feats, hw in levels)
    coarser = sum(hw for _feats, hw in levels[1:])
    total += sum(feats * c * hw for feats, hw in levels)  # laterals
    total += 9 * c * c * (cells + 2 * coarser)            # fpn, bottom-up, pafpn
    f, a = int(net["head_features"]), int(net["num_anchors"])
    tower = 9 * c * f + (int(net["head_convs"]) - 1) * 9 * f * f
    return total + (tower + 9 * f * 5 * a) * cells
