"""Multiply-adds of one face through a vision-transformer embedder, counted
from the sizes a configuration's file states (its ``embedder`` entry), with
nothing imported from the program: a patch embedding (``patch`` x ``patch``
x ``in_channels`` -> ``embed_dim`` on each whole patch of the crop: a
stride-``patch`` convolution without padding never reads past the last
whole patch); ``depth`` blocks of qkv (d -> 3d), q k^T and A v over all the
tokens (2 t^2 d for all heads together), proj (d -> d) and an MLP of
``mlp_ratio`` (d -> r d -> d); a head over the flattened tokens (t d ->
``out_dim`` -> ``out_dim``). Norms, the softmax and the adds are left out,
as the published counts leave them: 11.4 G for ViT-B (width 512, depth 24)
at 112x112 and patch 9, 1.5 / 5.7 / 25 G for ViT-T / -S / -L.
"""


def tokens(net):
    h, w = (int(v) for v in net["input_size"])
    return (h // int(net["patch"])) * (w // int(net["patch"]))


def multiply_adds(net):
    t, d, out = tokens(net), int(net["embed_dim"]), int(net["out_dim"])
    patch = t * int(net["patch"]) ** 2 * int(net["in_channels"]) * d
    block = (3 * t * d * d           # qkv
             + 2 * t * t * d         # q k^T and A v
             + t * d * d             # proj
             + 2 * int(net["mlp_ratio"]) * t * d * d)
    return patch + int(net["depth"]) * block + t * d * out + out * out
