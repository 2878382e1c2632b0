"""Every Pallas kernel lowers for platform ``tpu`` at the shapes the
chip will see — checked here, on the CPU, in seconds.

``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the
Pallas→Mosaic lowering rules without a TPU: block shapes the TPU
lowering refuses (a squeezed dim second-to-last, a block that does not
tile (8, 128)) fail HERE instead of at the first decode step on the
chip.  Interpret-mode tests cannot see this class of error: the
interpreter accepts any block shape.

Shapes are ``transformer_tpu``'s (6 heads × 128, bf16; 3 heads under
``--serve_tp 2``), the default ``kv_page_size`` 16 over a one-slot-deep
pool, and the sequence lengths on either side of the fused/split flash
backward switch.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest

from dtf_tpu.ops.flash_attention import flash_attention

pa = importlib.import_module("dtf_tpu.ops.paged_attention")

D = 128
PAGE, POOL, M = 16, 129, 128     # 1 scratch page + one 2048-token slot


def _lower_for_tpu(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowering"
    return text


@pytest.mark.parametrize("heads", [6, 3])
@pytest.mark.parametrize("batch,s", [(8, 1), (1, 16), (1, 64)])
def test_paged_decode_kernel_lowers_for_tpu(heads, batch, s):
    """Decode (S = 1, every slot) and continuation prefill chunks
    (S = 16 … 64, one slot), full and TP-local head counts."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    _lower_for_tpu(
        pa.paged_flash_decode,
        ((batch, s, heads, D), bf16), ((POOL, PAGE, heads, D), bf16),
        ((POOL, PAGE, heads, D), bf16), ((batch, M), i32), ((batch,), i32))


@pytest.mark.parametrize("heads", [6, 3])
@pytest.mark.parametrize("seq", [2048, 8192])
def test_flash_fwd_bwd_lowers_for_tpu(heads, seq):
    """Forward + backward inside one grad: seq 2048 takes the fused
    backward kernel, 8192 the split dq / dk-dv pair."""
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               use_pallas=True).astype(jnp.float32).sum()

    shape = ((1, seq, heads, D), jnp.bfloat16)
    text = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)),
                          shape, shape, shape)
    # fwd + fused bwd = 2 kernels; fwd + dq + dkdv = 3
    assert text.count("tpu_custom_call") == (2 if seq <= 4096 else 3)
