"""Cell lowering: (arch x shape x mesh) -> lowered/compiled artifacts + analysis.

Shared by launch/dryrun.py (the deliverable) and analysis/roofline.py.
Never sets XLA flags itself — the caller controls device count.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, SHAPES, ShapeSpec, get_config
from repro.distributed import sharding as shd
from repro.models import model as M
from repro.models import transformer
from repro.optim import AdamConfig, adam_init
from repro.runtime.steps import make_train_step


def rules_for(cfg: ArchConfig, shape: ShapeSpec, mesh) -> dict:
    return shd.make_rules(
        mesh_axes=tuple(mesh.axis_names), global_batch=shape.global_batch,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        decode=(shape.kind == "decode"), seq_len=shape.seq_len,
        family=cfg.family)


def batch_pspecs(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    """PartitionSpecs for the input batch (under an active rules context)."""
    sp = lambda *names: shd.logical_spec(*names)
    if shape.kind == "decode":
        return {"token": sp("batch", None), "pos": sp(), "cache": cache_pspecs(cfg)}
    specs = {"tokens": sp("batch", None)}
    if shape.kind == "train":
        specs["labels"] = sp("batch", None)
    if cfg.family == "audio":
        specs["frames"] = sp("batch", None, None)
    if cfg.family == "vlm":
        specs["vision"] = sp("batch", None, None)
    return specs


def cache_pspecs(cfg: ArchConfig):
    """Decode-cache PartitionSpecs (structure matches init_cache_shape)."""
    sp = shd.logical_spec
    fam = cfg.family
    kv_k = sp(None, "cache_batch", "cache_seq", None, None)
    if fam in ("dense", "moe", "vlm"):
        return {"k": kv_k, "v": kv_k}
    if fam == "ssm":
        return {"wkv": sp(None, "cache_batch", None, "cache_head_dim", None),
                "x_tm": sp(None, "cache_batch", None),
                "x_cm": sp(None, "cache_batch", None)}
    if fam == "hybrid":
        return {"k": kv_k, "v": kv_k,
                "mamba_conv": sp(None, None, "cache_batch", None, "ffn"),
                "mamba_ssm": sp(None, None, "cache_batch", "ffn", None)}
    if fam == "audio":
        # cross-attention cache has frames=1500 (not 16-divisible): hd-shard
        cross = sp(None, "cache_batch", None, None, "cache_head_dim")
        return {"k": kv_k, "v": kv_k, "cross_k": cross, "cross_v": cross}
    raise ValueError(fam)


def opt_pspecs(param_specs):
    from repro.optim.adam import AdamState
    return AdamState(step=P(), mu=param_specs, nu=param_specs)


@dataclasses.dataclass
class CellArtifacts:
    arch: str
    shape: str
    mesh_kind: str
    lowered: Any
    compiled: Any
    n_devices: int


def lower_cell(arch: str, shape_name: str, mesh, *, do_compile: bool = True,
               cfg_override: ArchConfig | None = None,
               int8_serving: bool = False) -> CellArtifacts:
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    model = M.build(cfg)
    rules = rules_for(cfg, shape, mesh)
    box = {}

    def _abstract_init():
        p, a = transformer.init_params(cfg, jax.random.key(0))
        box["axes"] = a          # static side-channel: axes are strings
        return p

    params_abs = jax.eval_shape(_abstract_init)
    axes = box["axes"]
    if int8_serving:
        # the paper's baked-quantized deployment: int8 weights + f32 scales
        # (serving shapes only; training keeps float master weights)
        from repro.core import ptq
        assert shape.kind in ("decode", "prefill"), "int8_serving is a serving mode"
        axes = ptq.quantize_axes(params_abs, axes)
        params_abs = ptq.abstract_quantize_tree(params_abs)

    with jax.set_mesh(mesh), shd.sharding_rules(rules):
        pspecs = shd.specs_from_axes(axes)
        bspecs = batch_pspecs(cfg, shape)
        inputs = M.input_specs(cfg, shape)

        if shape.kind == "train":
            ocfg = AdamConfig(moment_dtype=cfg.param_dtype)
            opt_abs = jax.eval_shape(lambda p: adam_init(p, ocfg), params_abs)
            step = make_train_step(model, ocfg)
            ospecs = opt_pspecs(pspecs)
            metric_specs = {"loss": P(), "grad_norm": P()}
            jitted = jax.jit(step,
                             in_shardings=(pspecs, ospecs, bspecs),
                             out_shardings=(pspecs, ospecs, metric_specs),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, inputs)
        elif shape.kind == "prefill":
            cspecs = cache_pspecs(cfg)
            jitted = jax.jit(model.prefill,
                             in_shardings=(pspecs, bspecs),
                             out_shardings=(shd.logical_spec("batch", "vocab"), cspecs))
            lowered = jitted.lower(params_abs, inputs)
        else:  # decode
            jitted = jax.jit(model.decode_step,
                             in_shardings=(pspecs, bspecs["cache"],
                                           bspecs["token"], bspecs["pos"]),
                             out_shardings=(shd.logical_spec("batch", "vocab"),
                                            bspecs["cache"]),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, inputs["cache"], inputs["token"],
                                   inputs["pos"])
        compiled = lowered.compile() if do_compile else None
    return CellArtifacts(arch, shape_name, mesh_kind="multi_pod" if "pod" in mesh.axis_names
                         else "single_pod", lowered=lowered, compiled=compiled,
                         n_devices=mesh.devices.size)


def cell_report(art: CellArtifacts) -> dict:
    """JSON-serializable summary of one compiled cell."""
    out = {"arch": art.arch, "shape": art.shape, "mesh": art.mesh_kind,
           "devices": art.n_devices, "ok": art.compiled is not None}
    if art.compiled is None:
        return out
    ma = art.compiled.memory_analysis()
    if ma is not None:
        out["memory"] = {
            "argument_bytes_per_device": int(ma.argument_size_in_bytes),
            "output_bytes_per_device": int(ma.output_size_in_bytes),
            "temp_bytes_per_device": int(ma.temp_size_in_bytes),
            "alias_bytes_per_device": int(ma.alias_size_in_bytes),
            "peak_estimate_per_device": int(ma.argument_size_in_bytes
                                            + ma.output_size_in_bytes
                                            + ma.temp_size_in_bytes
                                            - ma.alias_size_in_bytes),
        }
    ca = art.compiled.cost_analysis()
    if ca:
        out["cost"] = {k: float(ca[k]) for k in ("flops", "bytes accessed")
                       if k in ca}
    return out
