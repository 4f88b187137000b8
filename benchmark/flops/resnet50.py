"""Operations of ResNet-50 from its convolution and dense shapes. A
multiply-add is two operations. Training needs, for every layer, the
forward product, the gradient of the weights and the gradient of the input
(the stem needs no input gradient); batch normalisation, ReLU, pooling and
the loss move bytes and are not counted.
"""
from __future__ import annotations

STAGES = (((64, 64, 256), 3, 1), ((128, 128, 512), 4, 2),
          ((256, 256, 1024), 6, 2), ((512, 512, 2048), 3, 2))


def _out(size: int, stride: int) -> int:
    return -(-size // stride)      # SAME padding


def conv_macs(cfg: dict):
    """[(name, multiply-adds per image)] of every convolution and the dense
    layer."""
    h, w, c = cfg["input_shape"]
    h, w = _out(h, 2), _out(w, 2)
    out = [("stem", h * w * 7 * 7 * c * 64)]
    h, w = _out(h, 2), _out(w, 2)      # max pool
    c_in = 64
    for si, ((f1, f2, f3), blocks, stride) in enumerate(STAGES, start=2):
        for b in range(blocks):
            s = stride if b == 0 else 1
            ho, wo = _out(h, s), _out(w, s)
            u = f"s{si}.{b}."
            out.append((u + "a", ho * wo * c_in * f1))
            out.append((u + "b", ho * wo * 9 * f1 * f2))
            out.append((u + "c", ho * wo * f2 * f3))
            if b == 0:
                out.append((u + "sc", ho * wo * c_in * f3))
            h, w, c_in = ho, wo, f3
    out.append(("fc", 2048 * cfg["num_classes"]))
    return out


def forward_flops(cfg: dict, rows: int) -> int:
    return 2 * rows * sum(m for _, m in conv_macs(cfg))


def step_flops(cfg: dict, rows: int) -> int:
    macs = conv_macs(cfg)
    stem = macs[0][1]
    return 2 * rows * (3 * sum(m for _, m in macs) - stem)
