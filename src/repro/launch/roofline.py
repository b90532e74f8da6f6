"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds, against the per-chip
peaks of one row of :data:`PEAKS` (the dry-run's production target is a
v5e pod, so it uses the v5e row; live cost attribution looks the row up
by the ``device_kind`` that ran):

  compute    = HLO_FLOPs   / (chips * peak FLOP/s bf16)
  memory     = HLO_bytes   / (chips * peak HBM B/s)
  collective = coll_bytes  / (chips * peak B/s per ICI link)

cost_analysis() provides FLOPs/bytes. Collective bytes are NOT in
cost_analysis: we parse the post-SPMD HLO text and sum operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

Note: jax lowers SPMD programs to a per-device module, so cost_analysis
numbers are per-device; we report both per-device and whole-mesh views.
MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) catches remat/redundancy
waste via the ratio MODEL_FLOPS / (HLO_FLOPs * chips).
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator."""
    flops: float                 # bf16 FLOP/s
    hbm_bw: float                # HBM bytes/s
    ici_bw: float                # bytes/s per ICI link, one direction
    source: str


# keyed by jax's ``device.device_kind``; a device that is not here gets no
# roofline numbers at all, never another chip's
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8 / 4,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI per chip over 4 links"),
}
V5E = PEAKS["TPU v5 lite"]


def device_peaks(device=None) -> Optional[Peaks]:
    """The :data:`PEAKS` row of ``device`` (default: the first jax
    device), or None when its ``device_kind`` is not in the table."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return PEAKS.get(device.device_kind)

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "bf16": 2, "f16": 2, "f32": 4,
    "f64": 8, "c64": 8, "c128": 16, "e4m3": 1, "e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# shape like bf16[16,4096,512]{2,1,0} or (tuple of those); capture
# dtype + dims of every tensor literal on an op line
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum *output* tensor sizes of every collective op, by kind.

    HLO line shape: `%name = TYPE op-name(...)` — the leading TYPE is the
    op's result shape, which for collectives equals the data landing on the
    wire per device (all-gather output, all-to-all shuffled tuple, ...).
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"^%?[\w\.\-]+\s*=\s*(.+)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        if re.search(rf"\b{kind}-done\(", rhs):
            continue  # size counted at -start
        # result type is everything before the op name
        idx = rhs.find(kind)
        result_t = rhs[:idx]
        size = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(
            result_t))
        out[kind] += size
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    model_flops: float           # 6*N_active*D for the step's token count
    useful_bytes: float = 0.0    # irreducible weight+cache traffic (global)
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    peaks: Peaks = V5E

    def finalize(self) -> "Roofline":
        self.t_compute = self.flops_per_dev / self.peaks.flops
        self.t_memory = self.bytes_per_dev / self.peaks.hbm_bw
        self.t_collective = self.coll_bytes_per_dev / self.peaks.ici_bw
        return self

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops_per_dev * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_time(self) -> float:
        """Ideal overlapped execution: max of the three streams."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Useful work time / bound time — the score we hillclimb.

        Compute-shaped steps (train/prefill): useful FLOP time vs the
        binding stream. Bandwidth-shaped steps (decode): the irreducible
        weight+cache byte time also counts as useful work — take the max
        of the two views so decode cells are scored against the memory
        roofline they actually live on.
        """
        if self.roofline_time <= 0:
            return 0.0
        t_useful_c = (self.model_flops / self.chips) / self.peaks.flops
        t_useful_b = (self.useful_bytes / self.chips) / self.peaks.hbm_bw
        return max(t_useful_c, t_useful_b) / self.roofline_time

    @property
    def bw_fraction(self) -> float:
        """Irreducible bytes / HLO bytes (decode: how lean is the traffic)."""
        total = self.bytes_per_dev * self.chips
        return self.useful_bytes / total if total else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["peaks"] = self.peaks.source
        d.update(bottleneck=self.bottleneck,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_time=self.roofline_time,
                 roofline_fraction=self.roofline_fraction,
                 bw_fraction=self.bw_fraction)
        return d


def model_flops_for(cfg, suite) -> float:
    """6*N_active*D with D = tokens processed by the step."""
    n = cfg.active_param_count()
    if suite.kind == "train":
        tokens = suite.global_batch * suite.seq_len
        return 6.0 * n * tokens
    if suite.kind == "prefill":
        tokens = suite.global_batch * suite.seq_len
        return 2.0 * n * tokens          # forward only
    tokens = suite.global_batch           # one token per sequence
    return 2.0 * n * tokens


def useful_bytes_for(cfg, suite, serve_weights: str = "fp16") -> float:
    """Irreducible global bytes for the step: every active weight read once

    (packed bits when serving QMC) + the valid KV/SSM cache (decode) or
    activation residency floor (train/prefill: params + grads touched)."""
    from repro.memsys.workload import kv_bits_per_step
    n = cfg.active_param_count()
    w_bits = n * (5.2 if serve_weights == "qtensor"
                  and suite.kind == "decode" else 16.0)
    if suite.kind == "train":
        # fwd + bwd touch params twice, grads once, opt state twice
        return (3 * w_bits + 2 * cfg.param_count() * 32) / 8.0
    if suite.kind == "prefill":
        return w_bits / 8.0
    cache_bits = kv_bits_per_step(cfg, suite.seq_len) * suite.global_batch
    return (w_bits + cache_bits) / 8.0


def from_artifacts(arch: str, shape: str, mesh_name: str, chips: int,
                   cost: Dict, coll: Dict, model_flops: float,
                   useful_bytes: float = 0.0,
                   peaks: Peaks = V5E) -> Roofline:
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_dev=float(cost.get("flops", 0.0)),
        bytes_per_dev=float(cost.get("bytes accessed", 0.0)),
        coll_bytes_per_dev=float(coll.get("total", 0.0)),
        model_flops=model_flops,
        useful_bytes=useful_bytes, peaks=peaks).finalize()
