"""Operations and bytes the algorithms need, computed from shapes.

These are the numerators of ``train_mfu`` and of every roofline share.
They count what the mathematics requires: forward plus backward (the
backward as twice the forward), causal attention once (the masked half is
not work), recomputation (remat, the flash backward's second look at the
scores aside — see ``flash_bwd``) not at all.  XLA's own cost analysis is
not used: it does not see inside Mosaic kernels.
"""

from __future__ import annotations


# --------------------------------------------------------------------- LM --
def gpt_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    qkv, out, fc1, fc2 of every block and the output head (embedding
    look-ups and LayerNorms are not matmuls)."""
    d, ff = cfg["n_embd"], cfg["n_inner"]
    per_layer = 3 * d * d + d * d + d * ff + ff * d
    return cfg["n_layer"] * per_layer + d * cfg["vocab_size"]


def gpt_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """2 FLOPs per matmul parameter, plus causal attention: each token
    attends to (seq_len + 1) / 2 positions on average, 2 matmuls (scores,
    values) of 2·d FLOPs per position per layer."""
    attn = cfg["n_layer"] * 2 * 2 * cfg["n_embd"] * (seq_len + 1) / 2
    return 2.0 * gpt_matmul_params(cfg) + attn


def gpt_train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    """One sample = one sequence of ``seq_len`` tokens, forward + backward."""
    s = traffic["seq_len"]
    return 3.0 * gpt_forward_flops_per_token(cfg, s) * s


# ----------------------------------------------------------------- ResNet --
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


def resnet50_train_flops_per_sample(cfg: dict, traffic: dict) -> float:
    del traffic
    return 3.0 * resnet50_forward_flops_per_image(cfg)


# ---------------------------------------------------------------- kernels --
def flash_fwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one causal flash-attention forward call: the
    scores and the weighted values over the unmasked half; q, k, v read
    and o written once."""
    flops = 0.5 * 2 * 2.0 * batch * heads * seq * seq * head_dim
    nbytes = 4.0 * batch * heads * seq * head_dim * itemsize
    return flops, nbytes


def flash_bwd(batch: int, heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of the backward of that call: five matmuls (scores
    again — flash attention keeps no score matrix, so this one is the
    algorithm, not remat — dP, dV, dQ, dK) over the unmasked half; q, k,
    v, o, do read and dq, dk, dv written once."""
    flops = 0.5 * 5 * 2.0 * batch * heads * seq * seq * head_dim
    nbytes = 8.0 * batch * heads * seq * head_dim * itemsize
    return flops, nbytes


def paged_decode(context_tokens: int, heads: int, head_dim: int,
                 itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one layer's paged decode attention over
    ``context_tokens`` cached positions in total over the batch's rows:
    every cached K and V row is read once and meets one query row."""
    flops = 2 * 2.0 * context_tokens * heads * head_dim
    nbytes = 2.0 * context_tokens * heads * head_dim * itemsize
    return flops, nbytes


def flash_train_step(cfg: dict, traffic: dict, chips: int) -> tuple:
    """(FLOPs, bytes) of every flash forward and backward call one chip
    makes in one train step of the LM: one of each per layer, on that
    chip's share of the batch."""
    heads = cfg["n_head"]
    head_dim = cfg["n_embd"] // heads
    per_chip = traffic["batch_size"] // chips
    f_f, b_f = flash_fwd(per_chip, heads, traffic["seq_len"], head_dim)
    f_b, b_b = flash_bwd(per_chip, heads, traffic["seq_len"], head_dim)
    n = cfg["n_layer"]
    return n * (f_f + f_b), n * (b_f + b_b)


SAMPLE_FLOPS = {
    "gpt2": gpt_train_flops_per_sample,
    "resnet50": resnet50_train_flops_per_sample,
}

STEP_COSTS = {
    "flash_train_step": flash_train_step,
}
