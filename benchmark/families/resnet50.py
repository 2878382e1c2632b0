"""The family ``resnet50``: ResNet-50 v1.5 as the program's registry
builds ``resnet50``, at a configuration's ``image_size``, ``stage_blocks``,
``stage_widths`` and ``num_classes``.  The interface is in
``benchmark/families/__init__.py``.
"""

from __future__ import annotations

# No plain reference yet (its configuration says ``"reference": null``): a
# training cell is ``correct`` by a finite, falling loss and no compilation
# in the window, and the family cannot be served.


def _conv(h_out: int, k: int, c_in: int, c_out: int) -> float:
    return 2.0 * h_out * h_out * k * k * c_in * c_out


def resnet50_forward_flops_per_image(cfg: dict) -> float:
    """ResNet-50 v1.5 (stride 2 on the 3x3 of a stage's first block):
    convolutions and the classifier; BatchNorm, ReLU and pooling are not
    counted."""
    size = cfg["image_size"]
    h = size // 2                       # conv1 7x7 / 2
    flops = _conv(h, 7, 3, 64)
    h //= 2                             # max pool 3x3 / 2
    c_in = 64
    for stage, (blocks, width) in enumerate(zip(cfg["stage_blocks"],
                                                cfg["stage_widths"])):
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            h_out = h // stride
            flops += _conv(h, 1, c_in, width)           # 1x1 reduce
            flops += _conv(h_out, 3, width, width)      # 3x3 (strided: v1.5)
            flops += _conv(h_out, 1, width, 4 * width)  # 1x1 expand
            if b == 0:
                flops += _conv(h_out, 1, c_in, 4 * width)   # projection
            c_in, h = 4 * width, h_out
    return flops + 2.0 * c_in * cfg["num_classes"]


def train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    del traffic
    return 3.0 * resnet50_forward_flops_per_image(cfg)


TOY = {"train": {"batch_size": 4, "log_steps": 1}}
