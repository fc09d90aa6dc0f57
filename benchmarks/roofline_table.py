"""Roofline table: LLM three-term rows (from the 512-device dry-run sweep)
plus smallNet's own analytic hot-path rows (tiler / composed sweep /
megakernel sweep, ref + fixed_pallas numerics) — both read from
benchmarks/roofline_results.json, produced by
`python -m repro.analysis.run_roofline [--smoke]`.

    PYTHONPATH=src python -m benchmarks.roofline_table --smoke

--smoke recomputes the smallnet rows in-process (no JSON required) and
exits nonzero on NaN/zero-denominator rooflines or HLO-model drift — the
CI bench-smoke gate for the observability layer.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

_HERE = pathlib.Path(__file__).resolve().parent


def _smallnet_row(key: str, v: dict):
    return (f"roofline/{key}", None,
            f"bound={v['bound']} flops={v['flops']:.3g} "
            f"bytes={v['bytes']:.3g} intensity={v['intensity']:.1f} "
            f"attainable={v['attainable_flops']:.3g}FLOP/s "
            f"device={v.get('device', v.get('dtype', ''))}")


def run():
    rows = []
    p = _HERE / "roofline_results.json"
    if not p.exists():
        rows.append(("roofline/missing", None,
                     "run: PYTHONPATH=src python -m repro.analysis.run_roofline"))
        return rows
    res = json.loads(p.read_text())
    for key, v in sorted(res.items()):
        if "error" in v:
            rows.append((f"roofline/{key}", None, f"ERROR {v['error'][:60]}"))
            continue
        if key.startswith("smallnet"):
            rows.append(_smallnet_row(key, v))
            continue
        rows.append((f"roofline/{key}", v["step_time_s"] * 1e6,
                     f"dom={v['dominant']} comp={v['compute_s']*1e3:.1f}ms "
                     f"mem={v['memory_s']*1e3:.1f}ms coll={v['collective_s']*1e3:.1f}ms "
                     f"frac={v['roofline_fraction']:.3f} useful={v['useful_ratio']:.2f}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="recompute smallnet rooflines and gate finiteness "
                         "(nonzero exit on NaN/zero denominators)")
    ap.add_argument("--device", default="tpu-v5e")
    args = ap.parse_args()
    from repro.core import runtime
    runtime.init_compile_cache()

    print("name,us_per_call,derived")
    if args.smoke:
        from repro.analysis.run_roofline import smallnet_rows
        rows, failures = smallnet_rows(args.device)
        for key in sorted(rows):
            name, _, derived = _smallnet_row(key, rows[key])
            print(f"{name},,{derived}")
        for f in failures:
            print(f"roofline/FAIL,,{f}")
        print(f"roofline/result,,{'FAIL' if failures else 'OK'}")
        sys.exit(1 if failures else 0)

    for name, val, derived in run():
        val_s = f"{val:.2f}" if val is not None else ""
        print(f"{name},{val_s},{derived}")


if __name__ == "__main__":
    main()
