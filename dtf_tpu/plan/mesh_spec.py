"""Mesh descriptor for the parallelism planner.

A :class:`MeshSpec` is everything the analytic cost model needs to know
about the hardware — device count and layout (hosts × devices/host),
HBM bytes per device, achievable dense-matmul FLOP/s, and the two
collective-bandwidth tiers (intra-host ICI vs cross-host DCN).  It is
*simulatable*: a plan for a 4-host × 4-chip pod can be ranked on this
CPU box, because nothing here requires the described hardware to be
attached.

Numbers in the presets are order-of-magnitude engineering estimates
(achievable, not datasheet peak — e.g. the v4 entry uses ~50% of the
275 TFLOP/s bf16 peak, the sustained fraction a well-tiled matmul
reaches), good enough to *rank* plans; ``calibrate_device_flops`` runs
a short measured matmul probe for the calibration loop that compares
predicted vs measured step time on live hardware.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

KiB, MiB, GiB = 1024, 1024 ** 2, 1024 ** 3


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Hardware description consumed by the cost model."""

    name: str
    num_hosts: int
    devices_per_host: int
    hbm_bytes: int        # per-device HBM (host RAM share for CPU)
    device_flops: float   # achievable dense FLOP/s per device
    intra_bw: float       # bytes/s per device for intra-host collectives
    inter_bw: float       # bytes/s per device once a ring crosses hosts

    def __post_init__(self):
        if self.num_hosts < 1 or self.devices_per_host < 1:
            raise ValueError(f"mesh {self.name!r}: needs >= 1 host and "
                             f">= 1 device per host")
        if min(self.hbm_bytes, self.device_flops, self.intra_bw,
               self.inter_bw) <= 0:
            raise ValueError(f"mesh {self.name!r}: hbm/flops/bandwidth "
                             f"must all be positive")

    @property
    def num_devices(self) -> int:
        return self.num_hosts * self.devices_per_host

    def axis_bandwidth(self, stride: int, size: int) -> float:
        """Per-device collective bandwidth for a mesh axis whose ring
        neighbors are ``stride`` devices apart (the runtime lays the
        ('data','seq','model') mesh out row-major over the host-major
        device list, so an axis's span is stride × size): a ring whose
        whole span fits in one host runs at ICI speed, anything wider
        is gated by the cross-host link."""
        if size <= 1:
            return self.intra_bw
        return (self.intra_bw if stride * size <= self.devices_per_host
                else self.inter_bw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# Presets.  "cpu" is sized for the 8-virtual-device test mesh on a dev
# box (flops deliberately conservative — the calibration probe replaces
# it with a measurement); the TPU entries model one v4 host, one v5e
# host and the docs' worked 4-host × 4-device pod.
PRESETS: Dict[str, MeshSpec] = {
    "cpu": MeshSpec("cpu", num_hosts=1, devices_per_host=8,
                    hbm_bytes=4 * GiB, device_flops=8e9,
                    intra_bw=8e9, inter_bw=1e9),
    # one v4 host, 4 chips: 32 GiB HBM/chip, ~50% of 275 TFLOP/s bf16
    # peak achievable, ICI ~1e11 B/s effective allreduce bandwidth
    "v4-8": MeshSpec("v4-8", num_hosts=1, devices_per_host=4,
                     hbm_bytes=32 * GiB, device_flops=1.4e14,
                     intra_bw=1e11, inter_bw=2.5e10),
    # one v5e host, 4 chips (2x2): 16 GiB HBM/chip, ~50% of 197
    # TFLOP/s bf16 peak, ICI 1,600 Gbit/s per chip taken at a quarter
    # for an effective ring-allreduce rate.  Not yet anchored to a
    # measurement on the chip (ROADMAP S3)
    "v5e-4": MeshSpec("v5e-4", num_hosts=1, devices_per_host=4,
                      hbm_bytes=16 * GiB, device_flops=1.0e14,
                      intra_bw=5e10, inter_bw=2.5e10),
    # the README/DESIGN worked example: 4 hosts × 4 chips over DCN
    "4x4": MeshSpec("4x4", num_hosts=4, devices_per_host=4,
                    hbm_bytes=32 * GiB, device_flops=1.4e14,
                    intra_bw=1e11, inter_bw=2.5e10),
}

# jax ``device_kind`` (lower-cased substring) → the preset whose
# per-device rates describe it.  The live descriptor refuses a TPU
# kind that is not here: rates invented for an unknown chip rank plans
# wrongly without anyone noticing.
_KIND_PRESET = {"v4": "v4-8", "v5 lite": "v5e-4", "v5e": "v5e-4"}

_SUFFIX = {"k": 1e3, "m": 1e6, "g": 1e9, "t": 1e12, "p": 1e15}
# byte quantities use binary multipliers, so the documented descriptor
# "hbm=32g" means exactly the presets' 32 GiB — not 32e9 B, a 7%
# discrepancy that would flip feasibility between the two spellings
_BYTE_SUFFIX = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}


def _num(text: str, *, binary: bool = False) -> float:
    text = text.strip().lower()
    table = _BYTE_SUFFIX if binary else _SUFFIX
    if text and text[-1] in table:
        return float(text[:-1]) * table[text[-1]]
    return float(text)


def mesh_spec(spec: str = "", *, live_devices: Optional[int] = None
              ) -> MeshSpec:
    """Resolve a ``--plan_mesh`` value.

    "" (default)    — describe the live runtime: the actual jax
                      topology (process count × local devices), so
                      plans search the mesh a run would actually get.
                      On CPU the ``cpu`` preset's rates; on a TPU the
                      HBM the device reports and the rates of the
                      preset for its ``device_kind`` (an unlisted kind
                      is an error — pass an explicit descriptor).
    preset name     — one of PRESETS (``cpu``, ``v4-8``, ``v5e-4``,
                      ``4x4``).
    "k=v,…" string  — explicit descriptor, e.g.
                      ``hosts=4,devices=4,hbm=32g,flops=140t,intra=100g,inter=25g``
                      (numbers take k/m/g/t suffixes — binary for hbm
                      so ``32g`` ≡ 32 GiB like the presets, decimal for
                      the rates).  Unset keys inherit from the ``cpu``
                      preset.

    ``live_devices`` bounds the LIVE path's devices per host (an
    explicit ``--num_devices``); presets/descriptors ignore it.
    """
    if not spec:
        from dtf_tpu.runtime.mesh import topology
        topo = topology()
        base = PRESETS["cpu"]
        if topo["platform"] == "tpu":
            kind = topo["device_kind"].lower()
            preset = next((p for k, p in _KIND_PRESET.items()
                           if k in kind), None)
            if preset is None:
                raise ValueError(
                    f"no rate preset for TPU device_kind "
                    f"{topo['device_kind']!r} (have "
                    f"{sorted(_KIND_PRESET)}); describe the mesh "
                    f"explicitly: --plan_mesh 'hosts=..,devices=..,"
                    f"hbm=..,flops=..,intra=..,inter=..'")
            base = PRESETS[preset]
            if topo["hbm_bytes"]:
                base = dataclasses.replace(
                    base, hbm_bytes=int(topo["hbm_bytes"]))
        local = (live_devices if live_devices is not None
                 else topo["devices_per_host"])
        return dataclasses.replace(base, name="runtime",
                                   num_hosts=topo["num_hosts"],
                                   devices_per_host=local)
    if spec in PRESETS:
        return PRESETS[spec]
    if "=" not in spec:
        raise ValueError(
            f"unknown mesh preset {spec!r}; have {sorted(PRESETS)} or a "
            f"'hosts=4,devices=4,hbm=32g,flops=140t,intra=100g,inter=25g' "
            f"descriptor")
    base = PRESETS["cpu"]
    kw = dict(name=spec, num_hosts=base.num_hosts,
              devices_per_host=base.devices_per_host,
              hbm_bytes=base.hbm_bytes, device_flops=base.device_flops,
              intra_bw=base.intra_bw, inter_bw=base.inter_bw)
    keys = {"hosts": "num_hosts", "devices": "devices_per_host",
            "hbm": "hbm_bytes", "flops": "device_flops",
            "intra": "intra_bw", "inter": "inter_bw"}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        k = k.strip().lower()
        if k not in keys:
            raise ValueError(f"unknown mesh descriptor key {k!r}; have "
                             f"{sorted(keys)}")
        val = _num(v, binary=(k == "hbm"))
        kw[keys[k]] = int(val) if keys[k] in ("num_hosts",
                                              "devices_per_host",
                                              "hbm_bytes") else val
    return MeshSpec(**kw)


def calibrate_device_flops(repeats: int = 3) -> float:
    """Measured achievable FLOP/s for TRAINING-STEP-SHAPED work on one
    live device.

    A bare GEMM probe overestimates what a real step sustains by 5-50×
    on CPU (measured on this box: 1e12 FLOP/s for a 1024³ matmul chain
    vs ~3e10 achieved by an actual fwd+bwd — small per-op shapes,
    softmax/layernorm/optimizer traffic, dispatch overhead).  So the
    probe is a jitted forward+backward of the registry's
    ``transformer_small`` at a tiny batch, divided by its ANALYTIC flop
    count (the same accounting the cost model uses) — the resulting
    rate carries exactly the inefficiencies a predicted step will hit,
    which is what makes predicted-vs-measured land within the 2×
    calibration contract."""
    import jax
    import jax.numpy as jnp
    import optax

    from dtf_tpu.models import build_model
    from dtf_tpu.plan.model_stats import characterize

    batch, seq = 2, 64
    model, _ = build_model("transformer_small", dtype=jnp.float32)
    stats = characterize("transformer_small", seq_len=seq)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    params = jax.jit(model.init, static_argnames=("train",))(
        jax.random.key(0), tokens, train=False)["params"]

    def loss(p):
        logits, _ = model.apply({"params": p}, tokens, train=True,
                                mutable=["aux_loss"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens).mean()

    step = jax.jit(jax.grad(loss))
    jax.block_until_ready(step(params))  # compile + warm caches
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = step(params)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    # fwd + backward ≈ 3× forward MACs — the cost model's convention
    return repeats * 3.0 * stats.flops * batch / max(dt, 1e-9)
