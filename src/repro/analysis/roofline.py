"""Three-term roofline from the compiled dry-run artifact (TPU v5e target).

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / link_bw

Sources & loop correction (DESIGN.md §5):
  * FLOPs: dot/conv ops parsed from post-SPMD HLO text with while-loop trip
    multipliers (analysis/hlo_parse.py) — cost_analysis() counts loop bodies
    once, so it UNDERCOUNTS scanned models; we report both.
  * bytes: cost_analysis()['bytes accessed'] scaled by the flops correction
    ratio for loop-body traffic, cross-checked against the analytic model
    (weights-read + activation traffic + cache traffic); we report the
    analytic term as primary because the XLA byte counter double-counts
    fusion-internal traffic.
  * collective bytes: parsed from HLO with loop multipliers.

MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) cross-checks how much of
compiled compute is useful.
"""
from __future__ import annotations

import dataclasses


from repro.analysis.hlo_parse import analyze_hlo
from repro.analysis.mfu import DEVICE_DB
from repro.configs.base import ArchConfig, SHAPES, ShapeSpec, get_config

# --- TPU v5e hardware constants (per chip) ---------------------------------
# The per-chip peaks live in the MFU device database (analysis/mfu.py) so
# the LLM roofline and the smallNet workload model can never disagree about
# what the hardware can do; these module-level names remain the v5e view
# this three-term model is calibrated for.
_V5E = DEVICE_DB["tpu-v5e"]
PEAK_FLOPS_BF16 = _V5E.peak("bf16")
PEAK_FLOPS_INT8 = _V5E.peak("int8")
HBM_BW = _V5E.mem_bw
ICI_BW_PER_LINK = 50e9      # ~50 GB/s/link; v5e has 4 links usable per chip


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops_per_device: float
    hlo_flops_raw: float               # cost_analysis (loop bodies once)
    bytes_per_device: float
    collective_bytes_per_device: float      # bf16-wire corrected (primary)
    collective_bytes_raw: float             # as parsed (f32-legalized upper bound)
    collective_breakdown: dict
    model_flops_total: float           # 6ND / 6N_active*D
    useful_ratio: float                # MODEL_FLOPS / (HLO_FLOPs * devices)
    devices: int

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step estimate: overlapped model = max of the three."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of peak at the roofline step time (MFU
        upper bound implied by the compiled program)."""
        if self.step_time_s <= 0:
            return 0.0
        per_dev = self.model_flops_total / self.devices
        return per_dev / (self.step_time_s * PEAK_FLOPS_BF16)


def param_count(cfg: ArchConfig) -> tuple[float, float]:
    """(total params, active params) analytic."""
    d, L, ff, hd = cfg.d_model, cfg.n_layers, cfg.d_ff, cfg.head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp_dense = (3 if cfg.mlp == "gated" else 2) * d * ff
    embed = cfg.vocab_padded * d * (1 if cfg.tie_embeddings else 2)
    if cfg.family == "moe":
        moe = cfg.n_experts * 3 * d * ff + d * cfg.n_experts
        total = L * (attn + moe) + embed
        active = L * (attn + cfg.top_k * 3 * d * ff) + embed
        return float(total), float(active)
    if cfg.family == "ssm":
        # rwkv block: 5 square proj + lora + channel mix (ck, cv, cr)
        blk = 5 * d * d + d * ff * 2 + d * d + 10 * 32 * d
        total = L * blk + embed
        return float(total), float(total)
    if cfg.family == "hybrid":
        P = cfg.attn_period
        n_super = L // P
        d_in = 2 * d
        mamba = d * 2 * d_in + d_in * (max(1, d // 16) + 32) + \
            max(1, d // 16) * d_in + d_in * d
        moe = cfg.n_experts * 3 * d * ff
        per_super = (P - 1) * mamba + attn + (P // cfg.moe_every) * moe + \
            (P - P // cfg.moe_every) * mlp_dense
        active_super = (P - 1) * mamba + attn + \
            (P // cfg.moe_every) * cfg.top_k * 3 * d * ff + \
            (P - P // cfg.moe_every) * mlp_dense
        return float(n_super * per_super + embed), float(n_super * active_super + embed)
    if cfg.family == "audio":
        enc = cfg.encoder_layers * (attn + mlp_dense)
        dec = L * (2 * attn + mlp_dense)
        return float(enc + dec + embed), float(enc + dec + embed)
    total = L * (attn + mlp_dense) + embed
    return float(total), float(total)


def model_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    """6*N_active*D for train; 2*N_active*D for prefill; 2*N_active*B for
    one decode step (+ attention term where applicable)."""
    _, active = param_count(cfg)
    if shape.kind == "train":
        D = shape.global_batch * shape.seq_len
        return 6.0 * active * D
    if shape.kind == "prefill":
        D = shape.global_batch * shape.seq_len
        return 2.0 * active * D
    # decode: one token per sequence + attention over the cache
    flops = 2.0 * active * shape.global_batch
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        attn_layers = cfg.n_layers
    elif cfg.family == "hybrid":
        attn_layers = cfg.n_layers // cfg.attn_period
    else:
        attn_layers = 0
    flops += (4.0 * shape.global_batch * cfg.n_heads * cfg.head_dim
              * shape.seq_len * attn_layers)
    return flops


def analytic_bytes(cfg: ArchConfig, shape: ShapeSpec, devices: int) -> float:
    """Per-device HBM traffic model (documented in EXPERIMENTS.md §Roofline):
    train:   n_micro*(2 reads + 1 grad write of params) + 3x optimizer state
             + 4x layer-boundary activations
    prefill: params once + 2x activations + KV write
    decode:  params once + full KV/state cache read + write-back of one slot
    Parameter/cache bytes use the actual sharded layout (/devices).
    """
    total, _ = param_count(cfg)
    pb = total * (2 if cfg.param_dtype.__name__ == "bfloat16" else 4)
    dt = 2  # activation bytes (bf16)
    if shape.kind == "train":
        n_micro = max(1, shape.global_batch // max(1, cfg.micro_batch))
        acts = cfg.n_layers * shape.global_batch * shape.seq_len * cfg.d_model * dt
        opt = 3 * pb
        traffic = n_micro * 3 * pb + opt + 4 * acts
    elif shape.kind == "prefill":
        acts = cfg.n_layers * shape.global_batch * shape.seq_len * cfg.d_model * dt
        kv = (2 * cfg.n_layers * shape.global_batch * shape.seq_len
              * cfg.n_kv_heads * cfg.head_dim * dt)
        traffic = pb + 2 * acts + kv
    else:
        if cfg.family == "ssm":
            cache = (cfg.n_layers * shape.global_batch * cfg.n_heads
                     * cfg.head_dim * cfg.head_dim * 4)
        elif cfg.family == "hybrid":
            n_super = cfg.n_layers // cfg.attn_period
            cache = (2 * n_super * shape.global_batch * shape.seq_len
                     * cfg.n_kv_heads * cfg.head_dim * dt)
            cache += (cfg.n_layers - n_super) * shape.global_batch * \
                2 * cfg.d_model * 16 * 4
        else:
            cache = (2 * cfg.n_layers * shape.global_batch * shape.seq_len
                     * cfg.n_kv_heads * cfg.head_dim * dt)
        traffic = pb + cache
    return traffic / devices


def roofline_from_artifacts(arch: str, shape_name: str, hlo_text: str,
                            cost: dict, devices: int) -> Roofline:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    summ = analyze_hlo(hlo_text)
    mf = model_flops(cfg, shape)
    bytes_dev = analytic_bytes(cfg, shape, devices)
    hlo_flops = summ.flops
    # primary collective term uses the bf16-wire correction: XLA-CPU
    # legalizes bf16 matmul operands to f32 before SPMD partitioning, so
    # collectives a TPU lowering moves in bf16 parse as f32 here (the raw
    # number is also recorded as the upper bound)
    coll = summ.total_collective_bytes_bf16wire
    return Roofline(
        arch=arch, shape=shape_name,
        compute_s=hlo_flops / PEAK_FLOPS_BF16,
        memory_s=bytes_dev / HBM_BW,
        collective_s=coll / (4 * ICI_BW_PER_LINK),
        hlo_flops_per_device=hlo_flops,
        hlo_flops_raw=float(cost.get("flops", 0.0)),
        bytes_per_device=bytes_dev,
        collective_bytes_per_device=coll,
        collective_bytes_raw=summ.total_collective_bytes,
        collective_breakdown={k: float(v) for k, v in summ.collective_bytes.items()},
        model_flops_total=mf,
        useful_ratio=mf / max(hlo_flops * devices, 1.0),
        devices=devices,
    )


def to_dict(r: Roofline) -> dict:
    d = dataclasses.asdict(r)
    d["dominant"] = r.dominant
    d["step_time_s"] = r.step_time_s
    d["roofline_fraction"] = r.roofline_fraction
    return d
