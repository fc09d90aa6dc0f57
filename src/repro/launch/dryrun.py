import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # CPU-backend-only fix: XLA's while-loop invariant code motion hoists the
    # per-layer bf16->f32 convert of the remat'd residual stack into a whole
    # -stack f32 copy (verified absent at jaxpr level; TPU backend schedules
    # this differently).  Disabling keeps memory_analysis faithful.
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion,"
    "while-loop-expensive-invariant-code-motion")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay the first statements in this file: jax locks
the device count at first init, and the dry-run needs 512 placeholder
devices for the production meshes.  Nothing else in the repo sets this flag
(smoke tests and benches see 1 device).

Usage:
    python -m repro.launch.dryrun                      # full sweep, JSON cache
    python -m repro.launch.dryrun --arch llama3-405b --shape train_4k --mesh single_pod
    python -m repro.launch.dryrun --hlo-dir /tmp/hlo   # also dump HLO text

Per-cell results append to dryrun_results.json at the checkout root (idempotent:
already-recorded OK cells are skipped unless --force).
"""
import argparse
import gc
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.launch.lowering import lower_cell, cell_report
from repro.launch.mesh import make_production_mesh

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "dryrun_results.json"


def load_results() -> dict:
    if RESULTS.exists():
        return json.loads(RESULTS.read_text())
    return {}


def save_results(res: dict) -> None:
    tmp = RESULTS.with_suffix(".tmp")
    tmp.write_text(json.dumps(res, indent=1, sort_keys=True))
    tmp.replace(RESULTS)


def cell_key(arch: str, shape: str, mesh_kind: str) -> str:
    return f"{arch}|{shape}|{mesh_kind}"


def iter_cells(mesh_kinds):
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for s in SHAPES.values():
            if s.name == "long_500k" and not cfg.supports_long_context():
                continue  # documented skip: quadratic attention at 512k
            for mk in mesh_kinds:
                yield arch, s.name, mk


def run_cell(arch: str, shape: str, mesh_kind: str, hlo_dir: str | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    t0 = time.time()
    art = lower_cell(arch, shape, mesh)
    rep = cell_report(art)
    rep["compile_seconds"] = round(time.time() - t0, 1)
    if hlo_dir:
        p = pathlib.Path(hlo_dir)
        p.mkdir(parents=True, exist_ok=True)
        (p / f"{arch}__{shape}__{mesh_kind}.hlo.txt").write_text(
            art.compiled.as_text())
    del art
    gc.collect()
    return rep


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single_pod", "multi_pod"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--hlo-dir", default=None)
    args = ap.parse_args()

    assert len(jax.devices()) == 512, "dry-run expects 512 placeholder devices"
    mesh_kinds = [args.mesh] if args.mesh else ["single_pod", "multi_pod"]
    results = load_results()
    failures = 0
    for arch, shape, mk in iter_cells(mesh_kinds):
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape != args.shape:
            continue
        key = cell_key(arch, shape, mk)
        if not args.force and results.get(key, {}).get("ok"):
            continue
        print(f"[dryrun] {key} ...", flush=True)
        try:
            rep = run_cell(arch, shape, mk, args.hlo_dir)
            print(f"[dryrun] {key} OK {rep['compile_seconds']}s "
                  f"peak={rep.get('memory', {}).get('peak_estimate_per_device', 0)/2**30:.2f} GiB",
                  flush=True)
        except Exception as e:
            failures += 1
            rep = {"arch": arch, "shape": shape, "mesh": mk, "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
            print(f"[dryrun] {key} FAIL: {rep['error']}", flush=True)
            traceback.print_exc(limit=3)
        results[key] = rep
        save_results(results)
    print(f"[dryrun] done; {failures} failures; results -> {RESULTS}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
