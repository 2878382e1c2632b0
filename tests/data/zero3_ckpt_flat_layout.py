"""How ``zero3_ckpt_flat_layout.tar.gz`` was recorded (not a test; kept so the
fixture can be read and made again).

Run with ``REPO=<a checkout of 1b8c760, the parent of PR 35>`` and an output
directory: the parent's code, whose ZeRO slice is a contiguous 1/nd of the
flattened leaf (``zero.pad_flat``), trains a tiny transformer at ZeRO-3 on four
virtual devices, writes its checkpoint at step 2, and resumes a copy of it to
step 4.  The archive is ``<out>/ckpt`` with ``parent_losses.json`` beside it:
``tar -czf zero3_ckpt_flat_layout.tar.gz -C <out>/ckpt .``
(tests/test_checkpoint.py resumes it with the tree's own layout)."""
import os, sys, json, shutil, dataclasses, functools
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["REPO"])
import jax, numpy as np
import dtf_tpu
assert dtf_tpu.__file__.startswith(os.environ["REPO"]), dtf_tpu.__file__
import dtf_tpu.data.base as db
from dtf_tpu.cli import run
from dtf_tpu.config import Config
from dtf_tpu.models import registry
from dtf_tpu.models.transformer import TransformerLM
from dtf_tpu.train import zero
assert hasattr(zero, "pad_flat")          # the parent's layout
out = sys.argv[1]
shutil.rmtree(out, ignore_errors=True)
db._SPECS["lm"] = dataclasses.replace(db.LM, num_classes=64, seq_len=16, num_train=64, num_eval=16)
registry._REGISTRY["transformer"] = (functools.partial(
    TransformerLM, num_layers=1, d_model=16, num_heads=2, d_ff=32, max_seq_len=16, use_pallas=False), 64, 0.0)
base = dict(model="transformer", dataset="lm", batch_size=8, use_synthetic_data=True,
            skip_eval=True, log_steps=1, optimizer="adamw", num_devices=4,
            distribution_strategy="mirrored", zero_stage=3, checkpoint_steps=2, seed=7)
s2 = run(Config(**base, train_steps=2, model_dir=out + "/ckpt"))
shutil.copytree(out + "/ckpt", out + "/resumed")
s4 = run(Config(**base, train_steps=4, resume=True, model_dir=out + "/resumed"))
json.dump({"loss_step2": s2["loss"], "loss_step4": s4["loss"]}, open(out + "/parent_losses.json", "w"))
print(s2["loss"], s4["loss"])
